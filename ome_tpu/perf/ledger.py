"""Program cost ledger: what each compiled device program moves.

The decode roofline in bench.py is a hand-maintained bytes-per-token
model; the compiler already knows the truth. When the engine
dispatches a program for the first time (one ledger entry per
(program, static-args) pair — the jit compile key), the ledger asks
the AOT path for it: `fn.lower(...).compile()` then
`cost_analysis()` (FLOPs, bytes accessed), `memory_analysis()`
(argument/output/temp bytes), how many Mosaic kernels the compiled
program holds (`tpu_custom_call`), the scope path of each of its
instructions (`instruction_paths`: a device trace names operations
by instruction and carries no metadata) and which kernels declined
while it was traced (ops/__init__.py). Off-TPU — where a second CPU
compile of a production-sized model would be pure waste and the
analysis is not the one serving runs — the ledger degrades to the analytic
byte model the quantizer already maintains (models/quant.py
`quantized_bytes` + KV-capacity arithmetic), flagged
`source: "model"` so a reader never mistakes an estimate for a
measurement.

Expected ms is the roofline max of the memory and compute terms
against the device spec table bench.py shares from here. The entry
set is bounded by construction: programs are compiled, and
compilation is expensive — a serving process accumulates a handful
of entries, not a stream.

The ledger also keeps what compiling cost (PR 39). `listen()` hooks
JAX's monitoring events, which fire on compile paths only and never
on the dispatch of a compiled program: seconds by stage
(telemetry/scopes.py `COMPILE_STAGES`) and persistent-cache hits and
misses, summed for the process and booked on the entry whose
`capture` ran last on the compiling thread (what arrives before the
thread's next `capture` is that program's; what arrives before its
first goes to `other`). A program is compiled ONCE: the dispatch
that follows `capture` finds the jaxpr, the lowering and the
executable that `_introspect`'s `fn.lower(...).compile()` left in
JAX's own caches, so what JAX reports from inside `_build_entry` is
that program's trace, lowering and compile (or cache load), booked
under those stages, and the stage `introspect` is what is left of
`_build_entry`'s wall time: the cost analyses, the compiled text and
the pass over its instructions.

Surfaces: GET /debug/programs (guarded by --debug-endpoints),
`ome_engine_program_flops` / `ome_engine_program_bytes` gauges,
`ome_engine_compile_seconds_total{stage,when}` /
`ome_engine_compile_events_total{outcome}` counters, the
`program_compiled` flight event, attrs on `engine.decode_chunk`
spans, and the POST /debug/profile response body.
"""

from __future__ import annotations

import logging
import re
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from .. import device
from ..ops import collect_declines
from ..telemetry.scopes import (COMPILE_OUTCOMES, COMPILE_STAGES,
                                COMPILE_WHEN, PROGRAM_COMPILED)

# Published per-chip peaks, keyed by a substring of `device_kind`:
# HBM bandwidth (GB/s) and bf16 peak (TFLOP/s). Source: Google Cloud
# documentation, the "TPU v5e" page (197 TFLOP/s bf16, 819 GB/s, 16 GB
# HBM; a v5e reports device_kind "TPU v5 lite") and its sibling pages
# for v4, v5p and v6e. bench.py imports these so the offline and
# online rooflines can never disagree about the device spec. An
# accelerator that is not in the table is an error, never "a v5e".
DEVICE_HBM_GBPS = {"v5 lite": 819.0, "v5e": 819.0, "v5p": 2765.0,
                   "v6e": 1640.0, "v4": 1228.0}
DEVICE_PEAK_TFLOPS = {"v5 lite": 197.0, "v5e": 197.0, "v5p": 459.0,
                      "v6e": 918.0, "v4": 275.0}
# The CPU row is not a measurement of anything: tier-1 builds ledger
# entries for tiny programs on the CPU backend and the roofline
# arithmetic needs a divisor. Whatever is priced from it carries
# `platform: "cpu"` and is never printed under a device metric's name.
_CPU_SPEC = {"hbm_gbps": 50.0, "peak_tflops": 0.2}

LEDGER_MODES = ("auto", "full", "model", "off")

# JAX's monitoring events (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py) -> the stage or outcome each is booked under
_DURATION_STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_EVENT_OUTCOME = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
# the entry's name for compile events that belong to no captured
# program: the random-weights init, `jit_convert_element_type` and the
# other helpers a thread compiles before its first `capture`
OTHER_PROGRAM = "other"

log = logging.getLogger("ome.perf.ledger")

# jax.monitoring keeps listeners for the life of the process and
# cannot drop one by owner: the two callbacks are registered once and
# (three with the scalar one) forward to whichever ledger called
# `listen()` last
_listening: Optional["ProgramLedger"] = None
_registered = False


def _on_duration(event: str, duration: float, **_kw) -> None:
    led = _listening
    if led is not None and event in _DURATION_STAGE:
        led._on_duration(_DURATION_STAGE[event], duration)


def _on_scalar(event: str, _value, **_kw) -> None:
    # JAX marks the START of a timed stretch with a scalar of the
    # same name. Only tracing nests (a jitted function called inside
    # a traced body is traced inside it, and both report): the depth
    # says which report is the outermost
    led = _listening
    if led is not None and event == _TRACE_EVENT:
        led._tl.depth += 1


def _on_event(event: str, **_kw) -> None:
    led = _listening
    if led is not None and event in _EVENT_OUTCOME:
        led._on_outcome(_EVENT_OUTCOME[event])


class _ThreadBook(threading.local):
    """What one thread's compile events are booked by."""
    entry: Optional[dict] = None   # captured last on this thread
    depth = 0        # traces under way, one inside the other
    loaded = 0.0     # cache retrieval inside the compile under way
    booked = 0.0     # seconds booked so far (`capture` takes what JAX
    #                  reported from inside `_build_entry` off its wall)


def _compile_book() -> dict:
    return {"compile_s": dict.fromkeys(COMPILE_STAGES, 0.0),
            "cache": None}


def device_spec(device=None) -> Dict[str, object]:
    """{kind, platform, hbm_gbps, peak_tflops} for `device` (default:
    jax.devices()[0]; a process with no backend raises there). An
    accelerator whose device_kind matches no table key raises
    LookupError: pricing it as some other chip would put a wrong
    roofline under a true device name."""
    import jax
    if device is None:
        device = jax.devices()[0]
    platform = str(device.platform)
    kind = str(device.device_kind).lower()
    if platform == "cpu":
        return {"kind": kind, "platform": platform, **_CPU_SPEC}
    for key in DEVICE_HBM_GBPS:
        if key in kind:
            return {"kind": kind, "platform": platform,
                    "hbm_gbps": DEVICE_HBM_GBPS[key],
                    "peak_tflops": DEVICE_PEAK_TFLOPS[key]}
    raise LookupError(
        f"no published peaks for device_kind {device.device_kind!r} "
        f"(platform {platform!r}); add it to perf/ledger.py with its "
        f"source")


_HLO_COMPUTATION = re.compile(
    r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OPCODE = re.compile(r"[)}\]] ([a-z][a-z\-]*)\(")
_HLO_REF = re.compile(r"%([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# attributes through which a control-flow instruction runs a
# computation whose instructions are timed on their own
_HLO_CONTROL = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?"
    r"([\w.\-]+)|branch_computations=\{([^}]*)\}")
_HLO_FREE = frozenset({"parameter", "constant", "get-tuple-element",
                       "tuple", "bitcast"})


def instruction_paths(hlo_text: str) -> Dict[str, str]:
    """`instruction name -> op_name path` for the instructions of a
    compiled module that run as operations of their own (the entry
    computation and the bodies of its loops and branches), from
    `compiled.as_text()`.

    A device trace names an operation by its instruction
    (`%fusion.2`) and carries no metadata; the scope path
    (`jit(_decode_paged)/decode/sample/...`, telemetry/scopes.py) is
    in the compiled text. Instructions the compiler made itself (a
    scatter expanded into a sort, the copies around a loop's carried
    buffers, `cumsum`'s reduce-window chain) have no path, or a bare
    one without a scope: each takes the path of the nearest producer
    that has one — its operands in order, and for a loop body's
    parameter the loop instruction itself. What still resolves to
    nothing is left out."""
    rows = []                          # (name, computation, opcode, own, refs)
    caller: Dict[str, str] = {}        # computation -> its caller's own path
    entry = comp = None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            if line.startswith("ENTRY"):
                entry = comp
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        meta = rest.find(", metadata={")
        body = rest if meta < 0 else rest[:meta]
        found = _HLO_OP_NAME.search(rest)
        own = found.group(1) if found and "/" in found.group(1) else ""
        for one, many in _HLO_CONTROL.findall(body):
            for ref in [one] + _HLO_REF.findall(many):
                if ref:
                    caller[ref] = own
        op = _HLO_OPCODE.search(body)
        rows.append((name, comp, op.group(1) if op else "", own,
                     _HLO_REF.findall(body)))
    # the text lists an instruction after its operands, and a loop's
    # body before the loop: one pass in order resolves every producer
    # first, once a body's parameter stands for the loop instruction
    resolved: Dict[str, str] = {}
    out: Dict[str, str] = {}
    control = {entry} | set(caller)
    for name, comp, opcode, own, refs in rows:
        if not own and opcode == "parameter":
            own = caller.get(comp, "")
        resolved[name] = own or next(
            (resolved[r] for r in refs if resolved.get(r)), "")
        if (resolved[name] and comp in control
                and opcode not in _HLO_FREE):
            out[name] = resolved[name]
    return out


def roofline_ms(flops: float, bytes_moved: float, hbm_gbps: float,
                peak_tflops: float) -> float:
    """Expected program ms at the roofline: the slower of streaming
    `bytes_moved` at spec bandwidth and computing `flops` at peak."""
    mem_s = bytes_moved / max(hbm_gbps * 1e9, 1e-9)
    compute_s = flops / max(peak_tflops * 1e12, 1e-9)
    return max(mem_s, compute_s) * 1000.0


class ProgramLedger:
    """One entry per compiled engine program, captured at first
    dispatch (the engine calls `capture` immediately before every
    program call; repeats only bump the dispatch count).

    mode: "auto" = full AOT introspection on TPU, analytic model
    off-TPU (TPU-less CI must not pay a second compile of every
    program — and its numbers would describe the CPU fallback, not
    the device serving runs on); "full"/"model" force a path (tests
    force "full" on tiny CPU models); "off" disables capture.
    """

    def __init__(self, mode: str = "auto", registry=None, flight=None):
        if mode not in LEDGER_MODES:
            raise ValueError(
                f"ledger mode {mode!r} not in {LEDGER_MODES}")
        self.mode = mode
        self.flight = flight
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._last: Optional[dict] = None
        self._tl = _ThreadBook()
        # compile seconds {(stage, when): s} and cache events
        # {outcome: n} of the process; exported through the counters
        # below once `bind` has a registry
        self._compile_s = {(stage, when): 0.0 for stage in COMPILE_STAGES
                           for when in COMPILE_WHEN}
        self._compile_events = dict.fromkeys(COMPILE_OUTCOMES, 0)
        self._when = COMPILE_WHEN[0]
        self._other = dict(_compile_book(), program=OTHER_PROGRAM)
        # what the listeners themselves cost: calls and wall seconds
        self._listener = [0, 0.0]
        self._c_compile_s: Dict[tuple, object] = {}
        self._c_compile_events: Dict[str, object] = {}
        self._g_flops = None
        self._g_bytes = None
        self._spec: Optional[dict] = None
        self._warned = False
        if registry is not None:
            self.bind(registry)

    # -- wiring --------------------------------------------------------

    def bind(self, registry, flight=None) -> None:
        """Attach the serving registry (and optionally the flight
        ring) after construction — the scheduler owns both and the
        engine is built first. Entries captured before the bind are
        exported retroactively."""
        # program label values are compile keys — bounded by
        # construction (entries exist only for compiled programs)
        self._g_flops = registry.gauge(
            "ome_engine_program_flops",
            "FLOPs per dispatch of each compiled engine program, from "
            "XLA cost_analysis (or the analytic model off-TPU)",
            labelnames=("program",))
        self._g_bytes = registry.gauge(
            "ome_engine_program_bytes",
            "HBM bytes moved per dispatch of each compiled engine "
            "program, from XLA cost_analysis (or the analytic model "
            "off-TPU)", labelnames=("program",))
        c_seconds = registry.counter(
            "ome_engine_compile_seconds_total",
            "Seconds spent compiling or loading programs, by stage, "
            "before (startup) and after (serving) the listener was "
            "up; work of the compiling threads, not wall time",
            labelnames=("stage", "when"))
        c_events = registry.counter(
            "ome_engine_compile_events_total",
            "Persistent compile cache entries read (cache_hit) and "
            "written after a compile (cache_miss)",
            labelnames=("outcome",))
        if flight is not None:
            self.flight = flight
        with self._lock:
            entries = list(self._entries.values())
            # every series exists from the bind on, at 0 or at what
            # was compiled before it (the random-weights init)
            for stage in COMPILE_STAGES:
                for when in COMPILE_WHEN:
                    child = c_seconds.labels(stage=stage, when=when)
                    child.inc(self._compile_s[(stage, when)])
                    self._c_compile_s[(stage, when)] = child
            for outcome in COMPILE_OUTCOMES:
                child = c_events.labels(outcome=outcome)
                child.inc(self._compile_events[outcome])
                self._c_compile_events[outcome] = child
        for e in entries:
            self._export(e)

    def listen(self) -> None:
        """Book JAX's compile events on this ledger from now on (the
        serving process calls this once, in `main`). Costs nothing
        where nothing compiles."""
        global _listening, _registered
        _listening = self
        if not _registered:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_scalar_listener(_on_scalar)
            _registered = True

    def mark_serving(self) -> None:
        """`server.start()` returned: compile seconds from here on
        count under `when="serving"`."""
        self._when = COMPILE_WHEN[1]

    @property
    def bound(self) -> bool:
        return self._g_flops is not None

    def device_spec(self) -> Dict[str, object]:
        if self._spec is None:
            self._spec = device_spec()
        return self._spec

    # -- capture -------------------------------------------------------

    def _resolved_mode(self) -> str:
        if self.mode != "auto":
            return self.mode
        return "full" if device.on_tpu() else "model"

    def capture(self, name: str, static_desc: str, fn, args,
                static_kwargs: Dict[str, object],
                model: Dict[str, float]) -> Optional[dict]:
        """Record program `name` (e.g. "decode_multi", static args
        described by `static_desc`, e.g. "n=8") about to be
        dispatched as `fn(*args, **static_kwargs)`. `model` is the
        engine's analytic {flops, bytes} estimate — the fallback
        when compiler introspection is off or fails. Returns the
        (shared, mutable) entry; None when the ledger is off."""
        if self.mode == "off":
            return None
        key = f"{name}[{static_desc}]" if static_desc else name
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry["dispatches"] += 1
                self._last = self._tl.entry = entry
                return entry
        # `introspect` is the wall time of building the entry less
        # what JAX reported from inside it (booked on the entry, under
        # its own stages, as it arrived)
        tl = self._tl
        booked = tl.booked
        t0 = time.monotonic()
        entry = self._build_entry(key, name, static_desc, fn, args,
                                  static_kwargs, model)
        took = time.monotonic() - t0
        own = max(took - (tl.booked - booked), 0.0)
        with self._lock:
            entry = self._entries.setdefault(key, entry)
            entry["dispatches"] += 1
            self._last = tl.entry = entry
        self._book(entry, "introspect", own)
        self._export(entry)
        if self.flight is not None:
            self.flight.record(
                "program_captured", program=key,
                source=entry["source"],
                expected_ms=entry["expected_ms"],
                bytes=entry["bytes"], flops=entry["flops"])
        return entry

    def _build_entry(self, key, name, static_desc, fn, args,
                     static_kwargs, model) -> dict:
        spec = self.device_spec()
        entry = {
            "program": key,
            "name": name,
            "static": static_desc,
            "source": "model",
            "flops": float(model.get("flops", 0.0)),
            "bytes": float(model.get("bytes", 0.0)),
            "argument_bytes": None,
            "output_bytes": None,
            "temp_bytes": None,
            # Mosaic custom calls in the compiled text, and the
            # kernels that declined while it was traced (full mode)
            "mosaic_calls": None,
            "kernel_declines": None,
            # instruction -> scope path of the compiled text (full
            # mode): what joins a device trace's operation names to
            # the program's scopes (benchmark/phases.py)
            "op_names": None,
            "device": spec["kind"],
            "platform": spec["platform"],
            "dispatches": 0,
            "captured_unix": time.time(),
            # seconds by COMPILE_STAGES and "hit" | "miss" | None of
            # the persistent cache (a miss anywhere wins), `listen()`
            **_compile_book(),
        }
        # compile events from here on are this program's
        self._tl.entry = entry
        if self._resolved_mode() == "full" and fn is not None:
            self._introspect(entry, fn, args, static_kwargs)
        entry["expected_ms"] = roofline_ms(
            entry["flops"], entry["bytes"],
            spec["hbm_gbps"], spec["peak_tflops"])
        return entry

    def _introspect(self, entry, fn, args, static_kwargs) -> None:
        """AOT compiler introspection; any failure leaves the
        analytic-model numbers in place (never break a dispatch over
        observability)."""
        try:
            with collect_declines() as declines:
                lowered = fn.lower(*args, **static_kwargs)
        except Exception as e:
            self._warn_once("lower", entry["program"], e)
            return
        entry["kernel_declines"] = sorted(set(declines))
        ca = None
        try:
            ca = lowered.cost_analysis()
        except Exception:
            pass
        try:
            compiled = lowered.compile()
        except Exception as e:
            # compile failed but the pre-compile HLO cost analysis may
            # still have real numbers — flag the weaker provenance
            if self._apply_cost(entry, ca):
                entry["source"] = "lowered"
            self._warn_once("compile", entry["program"], e)
            return
        try:
            cca = compiled.cost_analysis()
            if isinstance(cca, (list, tuple)):
                cca = cca[0] if cca else None
        except Exception:
            cca = None
        if self._apply_cost(entry, cca):
            entry["source"] = "compiled"
        elif self._apply_cost(entry, ca):
            entry["source"] = "lowered"
        try:
            ma = compiled.memory_analysis()
        except Exception:
            ma = None
        try:
            text = compiled.as_text()
            entry["mosaic_calls"] = text.count("tpu_custom_call")
            entry["op_names"] = instruction_paths(text)
        except Exception:
            pass
        if ma is not None:
            entry["argument_bytes"] = int(
                getattr(ma, "argument_size_in_bytes", 0))
            entry["output_bytes"] = int(
                getattr(ma, "output_size_in_bytes", 0))
            entry["temp_bytes"] = int(
                getattr(ma, "temp_size_in_bytes", 0))

    @staticmethod
    def _apply_cost(entry, analysis) -> bool:
        if not analysis:
            return False
        flops = analysis.get("flops")
        bytes_ = analysis.get("bytes accessed")
        if flops is None and bytes_ is None:
            return False
        if flops is not None:
            entry["flops"] = float(flops)
        if bytes_ is not None:
            entry["bytes"] = float(bytes_)
        return True

    def _warn_once(self, stage, program, exc) -> None:
        if not self._warned:
            self._warned = True
            log.warning("ledger introspection (%s) failed for %s: %s "
                        "— keeping the analytic model estimate",
                        stage, program, exc)

    # -- compile events ------------------------------------------------

    def _on_duration(self, stage: str, seconds: float) -> None:
        t0 = time.perf_counter()
        tl = self._tl
        nested = False
        if stage == "trace":
            nested = tl.depth > 1   # inside a trace that reports it too
            tl.depth = max(tl.depth - 1, 0)
        elif stage == "cache_load":
            tl.loaded += seconds
        elif stage == "backend_compile":
            # JAX times the cache's retrieval inside this one
            seconds = max(seconds - tl.loaded, 0.0)
            tl.loaded = 0.0
        if not nested:
            tl.booked += seconds
            self._book(tl.entry or self._other, stage, seconds)
        self._listener[0] += 1
        self._listener[1] += time.perf_counter() - t0

    def _on_outcome(self, outcome: str) -> None:
        t0 = time.perf_counter()
        entry = self._tl.entry or self._other
        with self._lock:
            self._compile_events[outcome] += 1
            child = self._c_compile_events.get(outcome)
            if entry["cache"] != "miss":   # a miss anywhere wins
                entry["cache"] = outcome[len("cache_"):]
        if child is not None:
            child.inc()
        self._listener[0] += 1
        self._listener[1] += time.perf_counter() - t0

    def _book(self, entry: dict, stage: str, seconds: float) -> None:
        key = (stage, self._when)
        with self._lock:
            entry["compile_s"][stage] += seconds
            self._compile_s[key] += seconds
            child = self._c_compile_s.get(key)
            cache = entry["cache"]
        if child is not None:
            child.inc(seconds)
            if self.flight is not None:
                self.flight.record(
                    PROGRAM_COMPILED, program=entry["program"],
                    stage=stage, seconds=round(seconds, 6), cache=cache)

    def compile_totals(self) -> dict:
        """{seconds: {stage: {when: s}}, events: {outcome: n},
        other: the book of events no program owns, listeners: what
        the callbacks cost}: the /debug/programs body beside the
        entries."""
        with self._lock:
            seconds = {stage: {when: self._compile_s[(stage, when)]
                               for when in COMPILE_WHEN}
                       for stage in COMPILE_STAGES}
            return {"seconds": seconds,
                    "events": dict(self._compile_events),
                    "other": dict(self._other,
                                  compile_s=dict(self._other["compile_s"])),
                    "listeners": {"calls": self._listener[0],
                                  "seconds": self._listener[1]}}

    # -- reads ---------------------------------------------------------

    def last_dispatch(self) -> Optional[dict]:
        """The entry of the most recently captured dispatch — the
        scheduler reads its bytes for the online roofline right after
        the engine call returns."""
        return self._last

    def snapshot(self) -> List[dict]:
        """Entry copies in first-compile order (the /debug/programs
        body)."""
        with self._lock:
            return [dict(e, compile_s=dict(e["compile_s"]))
                    for e in self._entries.values()]

    def summary(self) -> List[dict]:
        """Compact {program, expected_ms, source} list — rides along
        in the POST /debug/profile response."""
        with self._lock:
            return [{"program": e["program"],
                     "expected_ms": round(e["expected_ms"], 4),
                     "source": e["source"]}
                    for e in self._entries.values()]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def _export(self, entry) -> None:
        if self._g_flops is None:
            return
        self._g_flops.labels(program=entry["program"]).set(
            entry["flops"])
        self._g_bytes.labels(program=entry["program"]).set(
            entry["bytes"])
