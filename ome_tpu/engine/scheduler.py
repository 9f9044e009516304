"""Continuous-batching scheduler.

Host-side orchestration around InferenceEngine's three compiled
programs: admit pending requests into free slots (prefill + insert),
then run decode steps for the whole batch, streaming tokens out to
per-request queues. One scheduler thread drives decode; request
threads (HTTP handlers) only touch queues.

Prefill/decode overlap (the JetStream separation, round-2 review
weak #3): prefill runs on a dedicated admission thread, so the decode
cadence never waits for a prefill to COMPLETE — the admission thread
blocks on the prefill result (and, in PD-disaggregated decode mode, on
the remote KV fetch) while the scheduler thread keeps stepping the
batch; `insert` is the only synchronization point. A slot semaphore
paces admission: the thread holds at most max_slots in-flight
prefills, and a finished request releases its slot back.

The decode loop is a planner/executor pair (docs/step-plan.md):
`_plan_step` decides once per iteration which compiled-program family
runs (plain decode / K-token chunk / spec verify) and what it carries
(grammar masks, chunk budgets, draft tokens); `_execute` dispatches
any plan the same way. Pipelining, multi-token chunks, speculative
verify, and structured-output masking are plan features that compose
rather than modes that carve each other out; when the planner cannot
meet a plan's precondition it flushes and counts the cause on
`ome_engine_step_degradations_total`.

Multi-host leaders (engine/multihost.ReplicatedEngine) disable the
overlap: followers replay the leader's op stream strictly in order, so
ops must be published from one thread in execution order.

Failure semantics (docs/failure-semantics.md): an engine-step fault
fails only the in-flight batch; queued requests survive, the decode
state is rebuilt after an exponential-backoff pause, and admission
resumes — up to `max_restarts` consecutive attempts, after which the
scheduler goes permanently dead (the pre-recovery behavior, and what
a liveness probe should restart the pod on). Status is tri-state:
`ok` (serving), `degraded` (recovering — requests queue), `dead`.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import math
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from ..priority import CLASS_LEVEL, DEFAULT_PRIORITY, PRIORITY_CLASSES
from ..priority import class_wait_caps as _wait_caps_table
from ..priority import class_weights as _weights_table
from ..telemetry import Registry
from ..telemetry.flight import FlightRecorder
from ..telemetry.scopes import ADMIT_PREFILL, SCHED_PHASES, SCHED_PREFIX
from ..telemetry.tracing import Span, SpanContext, coerce_span_log, \
    new_trace
from . import spec as spec_drafter
from .core import DecodeState, InferenceEngine
from .sampling import row_filters

_ids = itertools.count()

# ome_engine_kv_cache_bytes{kind=...}: a fixed enum
KV_CACHE_KINDS = ("global", "window")

# engine-step latencies cluster well under the Prometheus default
# buckets' floor on TPU; extend downward so the histogram resolves
# per-step time instead of lumping everything into the first bucket
STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                0.1, 0.25, 0.5, 1.0, 2.5)

# stats-key -> help text; every counter the scheduler keeps is
# mirrored into the shared registry under ome_engine_<key>
_COUNTER_HELP = {
    "requests_total": "Requests submitted to the scheduler",
    "tokens_generated_total": "Decode tokens emitted across requests",
    "prefill_total": "Prefill forwards executed",
    "decode_steps_total": "Batched decode steps executed",
    "preemptions_total": "Sequences preempted by KV pool pressure",
    "timeouts_total": "Requests finished with finish_reason=timeout",
    "rejected_total": "Requests rejected at admission (429)",
    "engine_faults_total": "Engine-step faults (crash recovery runs)",
    "restarts_total": "Successful scheduler crash recoveries",
    "spec_steps_total": "Speculative verify steps dispatched",
    "spec_proposed_tokens_total":
        "Draft tokens proposed by the n-gram drafter",
    "spec_accepted_tokens_total":
        "Draft tokens accepted by verify forwards",
}


class _SpecStep:
    """Lag-queue payload of one speculative verify step: the device-
    resident [B, k+1] emitted-token matrix and [B] accepted counts
    (host copies already in flight, like plain decode tokens), plus
    the host-side draft lengths for acceptance-rate accounting and
    the dispatch timestamp for the spec_verify span (emitted when the
    step drains — verify steps pipeline like any other plan)."""

    __slots__ = ("out", "accepted", "draft_len", "t_dispatch")

    def __init__(self, out, accepted, draft_len, t_dispatch=0.0):
        self.out = out
        self.accepted = accepted
        self.draft_len = draft_len
        self.t_dispatch = t_dispatch


class _MultiStep:
    """Lag-queue payload of one multi-token decode chunk
    (docs/multi-step-decode.md): the device-resident [B, k] sampled-
    token matrix and [B] advanced counts (host copies in flight), the
    chunk size, and the dispatch timestamp for the decode_chunk
    span."""

    __slots__ = ("out", "advanced", "k", "t_dispatch", "cost")

    def __init__(self, out, advanced, k, t_dispatch, cost=None):
        self.out = out
        self.advanced = advanced
        self.k = k
        self.t_dispatch = t_dispatch
        # the ledger entry of the dispatched program (perf/ledger.py)
        # — the drain attributes program/expected_ms on the
        # decode_chunk span when present
        self.cost = cost


class _Dispatched:
    """What the drain needs to time one dispatched step from its
    completion: the step number its `sched.*` spans carry, the
    dispatch START (monotonic), how many decode iterations it fused,
    and the slow-step event's context (host gap before it, mask
    seconds, the ledger entry of its program)."""

    __slots__ = ("n", "t_dispatch", "k_steps", "gap_s", "mask_s",
                 "entry")

    def __init__(self, n, t_dispatch, k_steps, gap_s, mask_s, entry):
        self.n = n
        self.t_dispatch = t_dispatch
        self.k_steps = k_steps
        self.gap_s = gap_s
        self.mask_s = mask_s
        self.entry = entry


class _Phase:
    """The two timestamps of one `Scheduler._phase` block."""

    __slots__ = ("t0", "t1")

    def __init__(self):
        self.t0 = time.monotonic()
        self.t1 = self.t0

    @property
    def dt(self) -> float:
        return self.t1 - self.t0


class StepPlan:
    """One scheduler iteration's device work, decided entirely at
    plan time (docs/step-plan.md): which compiled-program family runs
    (plain decode / K-token chunk / spec verify), the per-slot
    constraints it carries (grammar masks, chunk budgets, draft
    tokens), how many KV rows per slot it may commit, and whether its
    results must drain synchronously because a sampled token the next
    plan depends on cannot be known in advance. The executor
    dispatches every plan the same way; composition decisions —
    what rides with what — live only in the planner."""

    __slots__ = ("kind", "k", "sync", "mask", "mask_stack",
                 "mask_idx", "mask_stack_idx", "drafts", "dlen",
                 "budget", "rows", "mask_s")

    def __init__(self, kind, k=1, sync=False, mask=None,
                 mask_stack=None, mask_idx=None, mask_stack_idx=None,
                 drafts=None, dlen=None, budget=None,
                 rows=1, mask_s=0.0):
        self.kind = kind              # "decode" | "chunk" | "verify"
        self.k = k                    # chunk length / max draft tokens
        self.sync = sync              # drain everything after dispatch
        self.mask = mask              # [B, V] allowed-token mask
        self.mask_stack = mask_stack  # [B, k, V] per-iteration masks
        # device mask-table row indices replacing the dense arrays
        # above when every referenced grammar state is resident
        # (docs/structured-outputs.md): row 0 is the reserved
        # all-True row unmasked slots point at
        self.mask_idx = mask_idx            # [B] or [B, k+1] int32
        self.mask_stack_idx = mask_stack_idx  # [B, k] int32
        self.drafts = drafts          # [B, k] draft tokens (verify)
        self.dlen = dlen              # [B] draft lengths (verify)
        self.budget = budget          # [B] per-slot chunk budget
        self.rows = rows              # KV rows this plan writes/slot
        self.mask_s = mask_s          # host seconds building masks


# degradation causes the planner can count — a fixed enum so the
# counter's label cardinality is bounded by construction. `masked`
# and `spec_verify` name the old hard carve-outs (structured-output
# batches forfeiting pipelining/chunking, verify steps forcing a
# synchronous drain); with the shipped grammar maskers both stay 0 —
# `masked` only counts for a masker whose automaton cannot be copied
# (no grammar walk), and any other nonzero value is a composition
# regression.
DEGRADE_CAUSES = ("masked", "spec_verify", "spec_realign",
                  "engine_multi_step", "engine_verify")


# fixed width of the per-slot device stop table: stop ids past this
# count are detected on host only (the device just freezes later —
# overshoot is discarded at the drain, so streams stay identical)
_STOP_TABLE_WIDTH = 4


# WDRR quantum: deficit credit per class visit is weight x this many
# tokens — large enough that one visit usually covers a typical head
# request in one accumulation, small enough that a giant
# max_new_tokens request cannot monopolize a rotation
QUANTUM_TOKENS = 64


class ClassQueues:
    """Per-priority-class pending queues with a weighted deficit
    round-robin pick order (Shreedhar & Varghese DRR), presenting the
    queue.Queue surface the scheduler and its callers already use:
    `maxsize` (per-class bound), `qsize()`, `empty()`, `put_nowait()`
    raising queue.Full, `get(timeout)`/`get_nowait()` raising
    queue.Empty, and a flat `.queue` snapshot view.

    Each pick visits classes in a fixed rotation; a visit credits the
    class's deficit counter with weight x QUANTUM_TOKENS and the head
    request is served once the deficit covers its cost (its
    max_new_tokens budget), staying on the class while credit lasts
    so a large deficit serves a burst before the rotation moves on. A
    class that empties forfeits its banked deficit, so an idle class
    cannot hoard credit and later burst past its share. With a single
    class enqueued — or with ``enabled=False`` — every pick
    degenerates to plain FIFO, which keeps single-class streams
    byte-identical to the pre-priority scheduler.

    ``classes`` generalizes the rotation beyond the fixed priority
    enum: the fleet simulator's WDRR-fairness scenarios instantiate
    hundreds of tenant classes against the SAME pick loop the
    production scheduler runs. Default (None) keeps the priority
    enum and the default weight table, bit-for-bit the historical
    behavior; with explicit classes, ``weights`` maps class -> weight
    directly (missing classes weigh 1)."""

    def __init__(self, maxsize: int, weights=None,
                 enabled: bool = True, classes=None):
        self.maxsize = maxsize
        self.enabled = bool(enabled)
        if classes is None:
            self.classes = PRIORITY_CLASSES
            self.weights = _weights_table(weights)
            self._default_class = DEFAULT_PRIORITY
        else:
            self.classes = tuple(classes)
            if not self.classes:
                raise ValueError("classes must be non-empty")
            self.weights = {c: max(1, int((weights or {}).get(c, 1)))
                            for c in self.classes}
            self._default_class = (DEFAULT_PRIORITY
                                   if DEFAULT_PRIORITY in self.classes
                                   else self.classes[0])
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._q: Dict[str, "collections.deque[Request]"] = {
            c: collections.deque() for c in self.classes}
        self._deficit = {c: 0.0 for c in self.classes}
        self._cursor = 0
        # True when the cursor has just ARRIVED at a class: the DRR
        # quantum is credited once per arrival, not once per pick —
        # crediting per pick would let the cursor's class refill
        # forever and serve to empty, which is strict priority, not
        # weighted sharing
        self._fresh = True

    def _cls(self, req) -> str:
        if not self.enabled:
            return self._default_class
        cls = getattr(req, "priority", self._default_class)
        return cls if cls in self._q else self._default_class

    def qsize(self, cls: Optional[str] = None) -> int:
        with self._lock:
            if cls is not None:
                return len(self._q.get(cls, ()))
            return sum(len(d) for d in self._q.values())

    def depths(self) -> Dict[str, int]:
        with self._lock:
            return {c: len(d) for c, d in self._q.items()}

    def empty(self) -> bool:
        return self.qsize() == 0

    @property
    def queue(self) -> List["Request"]:
        """Flat snapshot (highest class first, FIFO within class) —
        the `pending.queue` view debug surfaces and tests read."""
        with self._lock:
            out: List[Request] = []
            for c in self.classes:
                out.extend(self._q[c])
            return out

    def put_nowait(self, req: "Request") -> None:
        with self._lock:
            dq = self._q[self._cls(req)]
            if self.maxsize and len(dq) >= self.maxsize:
                raise queue.Full
            dq.append(req)
            self._not_empty.notify()

    def _pick_locked(self) -> Optional["Request"]:
        if all(not d for d in self._q.values()):
            return None
        n = len(self.classes)
        while True:
            cls = self.classes[self._cursor % n]
            dq = self._q[cls]
            if not dq:
                # an empty class forfeits banked credit (classic DRR)
                self._deficit[cls] = 0.0
                self._cursor += 1
                self._fresh = True
                continue
            cost = max(int(dq[0].max_new_tokens), 1)
            if self._fresh:
                self._deficit[cls] += (self.weights[cls]
                                       * QUANTUM_TOKENS)
                self._fresh = False
            if self._deficit[cls] >= cost:
                self._deficit[cls] -= cost
                return dq.popleft()
            # credit exhausted (or one quantum is still short of an
            # oversized head request — it accumulates across rounds):
            # move to the next class
            self._cursor += 1
            self._fresh = True

    def get_nowait(self) -> "Request":
        with self._lock:
            req = self._pick_locked()
        if req is None:
            raise queue.Empty
        return req

    def get(self, timeout: Optional[float] = None) -> "Request":
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._not_empty:
            while True:
                req = self._pick_locked()
                if req is not None:
                    return req
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise queue.Empty
                self._not_empty.wait(remaining)


class SchedulerOverloaded(RuntimeError):
    """The pending queue would exceed a bounded wait; the client
    should back off for `retry_after` seconds (HTTP 429/Retry-After
    rather than an indefinitely blocked handler)."""

    def __init__(self, msg: str, retry_after: float = 1.0):
        super().__init__(msg)
        self.retry_after = retry_after


class SchedulerDraining(RuntimeError):
    """The replica received SIGTERM and is draining: in-flight work
    finishes, new admissions answer 503 + Retry-After so the client
    (or the router) resubmits elsewhere."""

    def __init__(self, msg: str, retry_after: float = 2.0):
        super().__init__(msg)
        self.retry_after = retry_after


@dataclass
class Request:
    prompt_ids: List[int]
    max_new_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_ids: Sequence[int] = ()
    # structured outputs: a TokenMasker (engine/structured.py)
    # constrains sampling to valid continuations of its grammar
    masker: Optional[object] = None
    # multi-LoRA: adapter name (engine register_adapter); None = base
    adapter: Optional[str] = None
    # cross-replica prefix reuse (docs/kv-hierarchy.md): the router's
    # fleet prefix directory names a peer replica that owns this
    # prompt's prefix (X-OME-Prefix-Peer); admission tries fetching
    # the prefix KV from it before computing the prefill locally
    prefix_peer: Optional[str] = None
    # multi-tenant priority class (docs/multi-tenancy.md): drives the
    # WDRR pick order, per-class admission caps, and preemption
    # victim ranking; journaled so kill-resume restores it
    priority: str = DEFAULT_PRIORITY
    # absolute time.monotonic() deadline; an expired request is shed
    # at admission (never occupies a slot) or finished mid-decode
    # with finish_reason="timeout"
    deadline: Optional[float] = None
    # request-lifecycle tracing: the SpanContext the HTTP layer
    # adopted from (or minted for) this request; flows into the JSONL
    # request log so router and engine records share one trace id
    trace: Optional[object] = None
    # durable requests (engine/journal.py): the journal id this
    # request is recorded under; assigned at admit, carried by
    # restart-resumed requests so progress keeps appending to the
    # original journal entry
    journal_id: Optional[int] = None
    id: int = field(default_factory=lambda: next(_ids))
    created: float = field(default_factory=time.monotonic)
    # results
    output_ids: List[int] = field(default_factory=list)
    finish_reason: Optional[str] = None
    # phase timestamps (monotonic): created -> scheduled (first decode
    # slot) -> first token -> finished; the deltas are the queue-wait/
    # TTFT/TPOT histograms and request-log fields
    scheduled_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # host-observed seconds of this request's FIRST prefill call (a
    # preempted request prefills again on resume; that is not added):
    # the request log's `prefill_s`, None if it never prefilled
    prefill_s: Optional[float] = None
    # one-shot observer the scheduler installs at submit(); must not
    # block or take scheduler locks (finish() may run under them)
    on_finish: Optional[object] = None
    done: threading.Event = field(default_factory=threading.Event)
    stream: "queue.Queue[Optional[int]]" = field(
        default_factory=queue.Queue)  # token ids; None = EOS sentinel

    def emit(self, token: int):
        if self.first_token_at is None:
            self.first_token_at = time.monotonic()
        self.output_ids.append(token)
        self.stream.put(token)

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (time.monotonic() if now is None else now)
                >= self.deadline)

    def finish(self, reason: str):
        # first finish wins: the server may time a request out while
        # the scheduler concurrently finishes it (benign race)
        if self.done.is_set():
            return
        self.finish_reason = reason
        self.finished_at = time.monotonic()
        self.stream.put(None)
        self.done.set()
        cb, self.on_finish = self.on_finish, None
        if cb is not None:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — telemetry must never
                pass  # turn a finished request into a failure

    def wait_output(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} timed out")
        return self.output_ids


def _mirror(counter, value) -> None:
    """Bring a registry counter up to a tally kept as a plain int
    (bumped off the registry's locks, read at scrape)."""
    delta = value - counter.value
    if delta > 0:
        counter.inc(delta)


class Scheduler:
    """Drives one InferenceEngine; thread-safe submit()."""

    # overlap is opt-in (serve.py enables it for single-host serving):
    # it needs the admission thread from start(), while tests and
    # multi-host leaders drive step() synchronously
    def __init__(self, engine: InferenceEngine, max_pending: int = 512,
                 overlap: bool = False, max_restarts: int = 3,
                 restart_backoff: float = 0.05,
                 max_queue_wait: float = 30.0,
                 pipeline_depth: int = 1,
                 spec_tokens: int = 0,
                 steps_per_dispatch: int = 1,
                 registry: Optional[Registry] = None,
                 journal=None,
                 span_log=None,
                 flight: Optional[FlightRecorder] = None,
                 flight_dump_dir: Optional[str] = None,
                 span_chunk_steps: int = 8,
                 class_weights=None,
                 class_wait_caps=None,
                 priority_scheduling: bool = True,
                 slow_step_factor: float = 4.0,
                 grammar_table: bool = True):
        self.engine = engine
        # slow-step outlier threshold: a step slower than this factor
        # times the rolling median records a slow_step flight event
        # (docs/perf-attribution.md)
        self.slow_step_factor = float(slow_step_factor)
        # span timeline (docs/tracing-timeline.md): per-phase spans
        # (queue, prefill, chunked decode, spec verify, journal
        # replay) written to the `--span-log` JSONL; a None path is a
        # no-op, so the hot path pays one `enabled` check when off
        self.span_log = coerce_span_log(span_log, component="engine")
        # decode spans are CHUNKED — one span per up-to-N drained
        # steps per request — so span volume scales with N, not with
        # every token, and no extra host sync is ever introduced
        # (timestamps come from points the loop already crosses)
        self.span_chunk_steps = max(int(span_chunk_steps), 1)
        # scheduler-lifetime trace for spans that belong to no single
        # request (spec verify batches, journal replay)
        self._span_ctx = new_trace()
        # flight recorder (telemetry/flight.py): always-on bounded
        # ring of lifecycle events; served at /debug/events, dumped
        # into flight_dump_dir on crash recovery
        self.flight = flight if flight is not None else FlightRecorder()
        self.flight_dump_dir = flight_dump_dir
        self._flight_dumps = 0
        # cross-replica prefix reuse (engine/peering.py): built on the
        # first X-OME-Prefix-Peer request; holds per-peer breakers
        self._peer_client = None
        # (proposed, accepted) of the most recently drained verify
        # step, read by the spec-verify span right after the drain
        self._spec_last = (0, 0)
        # durable requests (engine/journal.py, docs/durability.md):
        # when set, every unmasked admission is journaled, progress
        # records append at each step boundary, and restart resume
        # replays whatever has no tombstone. Masked (structured-
        # output) requests are NOT journaled — their grammar state is
        # not serializable, so a resumed fold could not rebuild it.
        self.journal = journal
        # speculative decoding (docs/speculative-decoding.md): max
        # draft tokens per slot per step proposed by the host-side
        # n-gram drafter (engine/spec.py) and verified in ONE batched
        # forward. 0 = off (plain decode, the default); steps where no
        # slot drafts and slots near the cache capacity fall back to
        # plain decode — so the emitted streams are identical either
        # way for greedy slots, and distributionally identical for
        # temperature > 0. Verify steps pipeline and compose with
        # chunking and grammar masks (docs/step-plan.md).
        self.spec_tokens = max(int(spec_tokens), 0)
        # decode pipelining (docs/decode-pipelining.md): number of
        # decode steps dispatched ahead of token emission. 0 = fetch
        # every step synchronously (pre-pipelining behavior); 1 = the
        # JetStream shape — step k's tokens are read only after step
        # k+1 was dispatched, hiding the host-side bubble. Plans the
        # planner marks `sync` (a sampled token the next plan depends
        # on) drain immediately for that step only.
        self.pipeline_depth = max(int(pipeline_depth), 0)
        # multi-token device decode (docs/multi-step-decode.md): K
        # decode iterations run inside ONE jitted program, the host
        # syncing once per K-token chunk. 1 = one dispatch per token
        # (the pre-multi-step behavior). Grammar-masked slots ride
        # chunks through forced-token runs; only engines without the
        # decode_multi op clamp K back to 1 (counted once below).
        self.steps_per_dispatch = max(int(steps_per_dispatch), 1)
        init_degrades = []
        if self.steps_per_dispatch > 1 and not (
                callable(getattr(engine, "decode_multi", None))
                and getattr(engine, "supports_multi_step", False)):
            import logging
            logging.getLogger("ome.engine").warning(
                "steps_per_dispatch=%d requested but engine %s has no "
                "multi-step decode; running at 1",
                self.steps_per_dispatch, type(engine).__name__)
            self.steps_per_dispatch = 1
            init_degrades.append("engine_multi_step")
        # speculative verify needs the engine's verify op; fakes and
        # wrappers without one run plain (counted once, not per step)
        self._spec_ok = callable(getattr(engine, "verify", None))
        if self.spec_tokens > 0 and not self._spec_ok:
            init_degrades.append("engine_verify")
        # per-slot predicted continuation beyond the committed stream
        # (docs/step-plan.md): [] = in sync with the device, a token
        # list = exactly what the plans still in flight will emit
        # (forced grammar tokens, full-accept draft predictions),
        # None = unknown until a drain or flush re-anchors it
        self._planned_tail: List[Optional[List[int]]] = \
            [[] for _ in range(engine.max_slots)]
        # shared telemetry registry: the EngineServer scrapes it on
        # /metrics; stats-dict counters below are mirrored into it
        self.registry = registry or Registry()
        if self.journal is not None:
            self.journal.bind(self.registry)
        # engines with their own metrics (the PD prefill pool) attach
        # them to the shared registry; getattr resolves through
        # delegating wrappers (ReplicatedEngine) on purpose
        bind = getattr(engine, "bind_registry", None)
        if callable(bind):
            bind(self.registry)
        # the PD fetch path logs its peer failovers into the same
        # lifecycle ring as the scheduler's own events
        bindf = getattr(engine, "bind_flight", None)
        if callable(bindf):
            bindf(self.flight)
        # performance attribution (ome_tpu/perf): the engine's program
        # cost ledger exports through the scheduler's registry/flight,
        # and a real engine gets an HBM accountant refreshed from
        # update_gauges() (fakes in tests have no params/cfg -> None)
        led = getattr(engine, "ledger", None)
        if led is not None and callable(getattr(led, "bind", None)):
            led.bind(self.registry, self.flight)
        from ..perf.hbm import HbmAccountant
        self.hbm = HbmAccountant.for_engine(engine, self.registry,
                                            self.flight)
        # crash recovery: consecutive engine-fault restarts tolerated
        # before going permanently dead (0 = first fault is fatal, the
        # pre-recovery fail-fast behavior)
        self.max_restarts = max_restarts
        self.restart_backoff = restart_backoff
        # admission control: reject (429) when the estimated queue
        # wait exceeds this many seconds
        self.max_queue_wait = max_queue_wait
        # multi-tenant priority scheduling (docs/multi-tenancy.md):
        # per-class WDRR queues, per-class queue-wait caps (standard
        # keeps exactly the global cap so single-class behavior is
        # unchanged), and class-aware preemption ranking. Disabled =
        # every request rides the standard FIFO, the pre-priority
        # scheduler bit for bit.
        self.priority_scheduling = bool(priority_scheduling)
        self.class_weights = _weights_table(class_weights)
        self.class_wait_caps = _wait_caps_table(max_queue_wait,
                                               class_wait_caps)
        self.state: DecodeState = engine.new_state()
        self.pending: "ClassQueues" = ClassQueues(
            max_pending, weights=self.class_weights,
            enabled=self.priority_scheduling)
        # class-aware KV-pressure preemption: the engine picks
        # victims through this rank hook (over-quota classes first,
        # then lowest class; the engine's own least-progress
        # tie-break preserves the single-class victim choice)
        setr = getattr(engine, "set_preempt_rank", None)
        if callable(setr):
            setr(self._preempt_rank)
        self.slots: List[Optional[Request]] = [None] * engine.max_slots
        B = engine.max_slots
        self.overlap = overlap
        # prefilled-and-awaiting-insert items from the admission thread
        self._ready: "queue.Queue[tuple]" = queue.Queue()
        self._free_slots = threading.Semaphore(B)
        self._temp = np.zeros(B, np.float32)
        self._top_k = np.zeros(B, np.int32)
        self._top_p = np.ones(B, np.float32)
        self._true_len = np.zeros(B, np.int32)  # admitted prompt len/slot
        # outputs already present at admission (preempted resumes):
        # capacity accounting must not count them twice
        self._base_out = np.zeros(B, np.int64)
        # paged-KV backpressure: requests bounced by KVPoolExhausted
        # and preempted mid-stream sequences re-enter HERE, ahead of
        # new arrivals (their generated tokens ride along as prompt)
        self._requeue: "collections.deque[Request]" = \
            collections.deque()
        # pipelined decode: dispatched-but-not-yet-read steps, each a
        # (device tokens, slot-occupancy snapshot, generation
        # snapshot, _Dispatched) tuple; _drain_inflight is the ONLY
        # place these tokens are fetched to the host
        self._inflight: "collections.deque[tuple]" = collections.deque()
        # dispatch counter: the `step` attribute that joins a step's
        # sched.dispatch span, its device module and its drain spans
        self._step_seq = 0
        # when the host last learned that a step had ended; the next
        # step's completion time starts here (or at its own dispatch,
        # whichever is later)
        self._last_fetched = 0.0
        # per-slot occupancy generation: bumped on EVERY occupancy
        # change (admit, finish, preempt, fail), so a lagged token is
        # emitted only if its slot still holds the same admission it
        # was sampled for — a requeued request re-admitted into the
        # same slot must not absorb the old admission's stale token
        self._slot_gen = [0] * B
        # device-resident sampling params (temperature/top_k/top_p as
        # one jnp tuple), rebuilt only when a slot's occupancy or
        # params change — not three np.asarray uploads per step
        self._sampling_dev: Optional[tuple] = None
        # which tier of sampling.filtered_logits the cached params
        # select; re-evaluated with the device copy (_sampling)
        self._sample_tier = "plain"
        # device-resident [B, NS] per-slot stop table for multi-step
        # chunks, cached on the same invalidation rule
        self._stops_dev = None
        # monotonic timestamp of the last dispatch RETURN; the gap to
        # the next dispatch START is the host-side bubble the
        # pipelining removes (None after idle/recovery so those pauses
        # don't pollute the histogram)
        self._dispatch_end: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._admit_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()  # guards submit-vs-stop + stats
        # tri-state health: ok (serving) / degraded (mid-recovery,
        # requests queue) / dead (restart budget exhausted)
        self._status = "ok"
        # graceful drain (SIGTERM): new submissions are rejected with
        # 503 while in-flight and queued work keeps running to
        # completion; stop() then evicts whatever the grace window
        # did not finish
        self._draining = False
        # requests the admission thread holds between popping them
        # from a queue and parking them in _ready — drain_idle() must
        # see them as in-flight work
        self._admitting = 0
        self._restarts = 0  # consecutive faults since last good step
        # the admission thread signals a local engine fault here; the
        # scheduler thread owns recovery (one recoverer, no races)
        self._fault_event = threading.Event()
        # EWMAs for the queue-wait estimate (admission control)
        self._ewma_step_s: Optional[float] = None
        self._ewma_req_steps: Optional[float] = None
        self.stats: Dict[str, float] = {
            "requests_total": 0, "tokens_generated_total": 0,
            "prefill_total": 0, "decode_steps_total": 0,
            "queue_depth": 0, "active_slots": 0,
            "preemptions_total": 0, "timeouts_total": 0,
            "rejected_total": 0, "engine_faults_total": 0,
            "restarts_total": 0, "spec_steps_total": 0,
            "spec_proposed_tokens_total": 0,
            "spec_accepted_tokens_total": 0,
        }
        R = self.registry
        self._counters = {
            key: R.counter(f"ome_engine_{key}", help)
            for key, help in _COUNTER_HELP.items()}
        self._h_queue_wait = R.histogram(
            "ome_engine_queue_wait_seconds",
            "Seconds between admission and first decode slot")
        self._h_prefill = R.histogram(
            "ome_engine_prefill_seconds",
            "Per-request prefill forward seconds", buckets=STEP_BUCKETS)
        self._h_decode_step = R.histogram(
            "ome_engine_decode_step_seconds",
            "Batched decode step seconds (one token per active slot), "
            "from the step's completion: result fetched minus the later "
            "of its dispatch and the previous step's fetch",
            buckets=STEP_BUCKETS)
        self._h_step_gap = R.histogram(
            "ome_engine_step_gap_seconds",
            "Host-side gap between consecutive decode dispatches (the "
            "bubble decode pipelining hides; idle/recovery pauses are "
            "excluded)", buckets=STEP_BUCKETS)
        self._h_ttft = R.histogram(
            "ome_engine_ttft_seconds",
            "Time to first token (admission to first emit)")
        self._h_tpot = R.histogram(
            "ome_engine_tpot_seconds",
            "Per-request mean time per output token after the first",
            buckets=STEP_BUCKETS)
        self._h_e2e = R.histogram(
            "ome_engine_e2e_seconds",
            "End-to-end request seconds (admission to finish)")
        self._g_queue_depth = R.gauge(
            "ome_engine_queue_depth", "Pending-queue depth")
        self._g_active = R.gauge(
            "ome_engine_active_slots", "Occupied decode slots")
        self._g_occupancy = R.gauge(
            "ome_engine_batch_occupancy_ratio",
            "Occupied decode slots / max_slots")
        self._g_status = R.gauge(
            "ome_engine_status",
            "Scheduler health state", labelnames=("state",))
        self._h_spec_accept = R.histogram(
            "ome_engine_spec_accept_rate",
            "Per-verify-step fraction of proposed draft tokens "
            "accepted (steps where at least one slot drafted)",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
        self._h_spec_accepted = R.histogram(
            "ome_engine_spec_accepted_tokens_per_step",
            "Accepted draft tokens per drafting slot per verify step",
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0))
        # prefix-cache observability (engine counters are plain ints;
        # update_gauges mirrors them by delta so /metrics sees them)
        self._g_state_bytes = R.gauge(
            "ome_engine_state_bytes",
            "Device bytes of recurrent state all decode slots hold "
            "together (a hybrid model's linear-attention layers: a "
            "float32 state matrix a head and a conv tail a slot); 0 "
            "for a model whose slots own KV rows only")
        kv_kinds = R.gauge(
            "ome_engine_kv_cache_bytes",
            "Device bytes of the decode slots' KV rows by kind: "
            "`global` the full-length rows (the dense slab or the "
            "paged pool, every layer of a model with one kind), "
            "`window` the rings of a periodic window / global "
            "model's window layers (docs/window-cache.md), 0 for a "
            "model with none", labelnames=("kind",))
        self._g_kv_bytes = {k: kv_kinds.labels(kind=k)
                            for k in KV_CACHE_KINDS}
        self._c_moe = {
            "layer_steps": R.counter(
                "ome_engine_moe_layer_steps_total",
                "Expert layers run by decode programs (layers x "
                "steps) on an engine that holds a share of its "
                "experts; the denominator of the two below"),
            "experts_hit": R.counter(
                "ome_engine_moe_experts_hit_total",
                "Held experts that at least one routed pair of the "
                "batch reached, summed over expert layers and decode "
                "steps (counted on the device, read at scrape)"),
            "pairs": R.counter(
                "ome_engine_moe_pairs_total",
                "Routed token-expert pairs that landed on an expert "
                "held here, summed over expert layers and decode "
                "steps; the rest were routed to absent experts and "
                "cost no grouped-matmul rows"),
        }
        attn_blocks = R.counter(
            "ome_engine_prefill_attn_blocks_total",
            "Grid steps of the prefill attention kernel "
            "(flash_prefill) by kind, summed over the prefills run "
            "and the model's layers: whole = every pair of the block "
            "is seen, no mask arithmetic; edge = a mask edge crosses "
            "it, the mask is built; none = nothing to do. Reckoned on "
            "the host from each prefill's shape; all zero where "
            "prompts take XLA's attention", labelnames=("kind",))
        self._c_attn_blocks = {k: attn_blocks.labels(kind=k)
                               for k in ("none", "whole", "edge")}
        self._c_pc_hits = R.counter(
            "ome_engine_prefix_cache_hits_total",
            "Prefix-cache hits (prompts that reused cached KV)")
        self._c_pc_misses = R.counter(
            "ome_engine_prefix_cache_misses_total",
            "Prefix-cache misses")
        self._c_pc_evictions = R.counter(
            "ome_engine_prefix_cache_evictions_total",
            "Prefix-cache leaf blocks evicted by the byte budget")
        self._g_pc_bytes = R.gauge(
            "ome_engine_prefix_cache_bytes",
            "Device bytes resident in the prefix cache")
        # host-DRAM spill tier (zeros unless --prefix-cache-host-mb)
        self._c_pc_host_hits = R.counter(
            "ome_engine_prefix_host_hits_total",
            "Prefix blocks found host-resident on match (each kicks "
            "an async swap-in; the current request recomputes)")
        self._c_pc_host_swapins = R.counter(
            "ome_engine_prefix_host_swapins_total",
            "Prefix blocks promoted host -> device by the swap thread")
        self._c_pc_host_recomputes = R.counter(
            "ome_engine_prefix_host_recomputes_total",
            "Requests that recomputed a host-resident prefix locally "
            "instead of waiting for the swap-in")
        self._g_pc_host_bytes = R.gauge(
            "ome_engine_prefix_host_bytes",
            "Host-DRAM bytes resident in the prefix-cache spill tier")
        # step-phase attribution: where the scheduler thread's time
        # goes, each phase observed by `_phase` from the same two
        # timestamps as its `sched.<phase>` profiler span — plan (the
        # whole of _plan_step; it contains mask_apply, the grammar
        # mask build), dispatch (the compiled decode call returning:
        # an enqueue under pipelining; device_loop for a K-token
        # chunk), device_wait (blocking at the lag-queue read),
        # host_sample (token emit/offload after the read), insert (a
        # prefilled request entering its slot). The device sets the
        # pace when device_wait dominates; decode_step_seconds is the
        # step's completion time and is NOT their sum.
        self._h_step_phase = R.histogram(
            "ome_engine_step_phase_seconds",
            "Scheduler-thread time by phase (plan / mask_apply / "
            "dispatch / device_loop / device_wait / host_sample / "
            "insert)",
            labelnames=("phase",), buckets=STEP_BUCKETS)
        self._ph = {name: self._h_step_phase.labels(phase=name)
                    for name in SCHED_PHASES}
        self._g_steps_per_dispatch = R.gauge(
            "ome_engine_steps_per_dispatch",
            "Decode iterations fused per device dispatch (the "
            "--steps-per-dispatch K; 1 = per-token dispatch)")
        self._g_steps_per_dispatch.set(self.steps_per_dispatch)
        # which tier of sampling.filtered_logits each decode step ran:
        # `plain` when no slot filters (no sort on the device),
        # `filtered` when one does. Decided on the host from the same
        # [B] vectors the device reads; the two sum to decode_steps.
        _tier = R.counter(
            "ome_engine_sample_tier_steps_total",
            "Decode steps by the sampling tier their batch selected "
            "(plain: no slot sets top_k/top_p, nothing is sorted / "
            "filtered: at least one sampling slot does)",
            labelnames=("tier",))
        self._c_sample_tier = {t: _tier.labels(tier=t)
                               for t in ("plain", "filtered")}
        self._c_flight_events = R.counter(
            "ome_engine_flight_events_total",
            "Scheduler lifecycle events recorded by the flight ring")
        self._c_flight_dumps = R.counter(
            "ome_engine_flight_dumps_total",
            "Flight-recorder dumps written on crash recovery")
        # step-plan degradation visibility (docs/step-plan.md):
        # counted whenever the planner gives up a composition feature
        # (a pipeline flush to re-anchor drafts, an engine capability
        # clamp). Children are pre-created for the fixed cause enum so
        # absent causes scrape as explicit zeros — `masked` (walkable
        # grammars) and `spec_verify` in particular stay 0; they name
        # the old carve-outs the plan/execute loop removed.
        _deg = R.counter(
            "ome_engine_step_degradations_total",
            "Steps where the planner degraded a composition feature, "
            "by cause (masked / spec_verify / spec_realign / "
            "engine_multi_step / engine_verify)",
            labelnames=("cause",))
        self._c_degrade = {c: _deg.labels(cause=c)
                           for c in DEGRADE_CAUSES}
        for cause in init_degrades:
            self._c_degrade[cause].inc()
        # device-resident grammar-mask cache (engine/maskcache.py,
        # docs/structured-outputs.md): compiled automaton-state masks
        # live as rows of the engine's [S, V] device mask table and
        # step plans reference them by row index instead of shipping
        # dense [B, K, V] bools. None = dense masks only (an engine
        # without a mask table, or grammar_table=False — the
        # byte-identical dense baseline tests diff against).
        self._c_gmask_hit = R.counter(
            "ome_engine_grammar_mask_cache_hits_total",
            "Grammar-state mask lookups served by the device-resident "
            "row cache")
        self._c_gmask_miss = R.counter(
            "ome_engine_grammar_mask_cache_misses_total",
            "Grammar-state mask lookups that compiled a fresh mask "
            "(uploading a row when one was free)")
        self._c_gmask_evict = R.counter(
            "ome_engine_grammar_mask_cache_evictions_total",
            "Grammar-state mask rows reused for a new state (LRU; the "
            "overwriting upload is the invalidation)")
        self._g_gmask_resident = R.gauge(
            "ome_engine_grammar_states_resident",
            "Automaton states currently resident in the device mask "
            "table")
        self._gcache = None
        _mrows = int(getattr(engine, "mask_table_rows", 0) or 0)
        if grammar_table and _mrows >= 2 and callable(
                getattr(engine, "set_mask_row", None)):
            from .maskcache import GrammarMaskCache
            self._gcache = GrammarMaskCache(
                _mrows, upload=engine.set_mask_row,
                on_hit=self._c_gmask_hit.inc,
                on_miss=self._c_gmask_miss.inc,
                on_evict=self._c_gmask_evict.inc)
        # per-class observability (docs/multi-tenancy.md): children
        # are pre-created for the fixed class enum ONLY, so label
        # cardinality is bounded by construction (the
        # metrics-label-cardinality lint enforces this pattern)
        def _by_class(fam):
            return {c: fam.labels(**{"class": c})
                    for c in PRIORITY_CLASSES}
        self._c_class_requests = _by_class(R.counter(
            "ome_engine_class_requests_total",
            "Requests submitted, by priority class",
            labelnames=("class",)))
        self._c_class_rejected = _by_class(R.counter(
            "ome_engine_class_rejected_total",
            "Admission rejections (429), by priority class",
            labelnames=("class",)))
        self._c_class_preempt = _by_class(R.counter(
            "ome_engine_class_preemptions_total",
            "KV-pressure preemptions, by priority class",
            labelnames=("class",)))
        self._c_class_tokens = _by_class(R.counter(
            "ome_engine_class_tokens_total",
            "Decode tokens emitted, by priority class",
            labelnames=("class",)))
        self._h_class_queue_wait = _by_class(R.histogram(
            "ome_engine_class_queue_wait_seconds",
            "Seconds between admission and first decode slot, by "
            "priority class", labelnames=("class",)))
        self._h_class_ttft = _by_class(R.histogram(
            "ome_engine_class_ttft_seconds",
            "Time to first token by priority class",
            labelnames=("class",)))
        self._h_class_e2e = _by_class(R.histogram(
            "ome_engine_class_e2e_seconds",
            "End-to-end request seconds by priority class (the "
            "fleet SLO rollup's e2e objective source; docs/slo.md)",
            labelnames=("class",)))
        self._g_class_depth = _by_class(R.gauge(
            "ome_engine_class_queue_depth",
            "Pending-queue depth by priority class",
            labelnames=("class",)))
        self._c_slow_steps = R.counter(
            "ome_engine_slow_steps_total",
            "Decode steps exceeding slow_step_factor x the rolling "
            "median step time (each also records a slow_step flight "
            "event with the phase breakdown)")
        # rolling per-step-time window feeding the slow-step outlier
        # detector; deque append/iterate under the GIL is safe from
        # the single decode thread
        self._step_window: "collections.deque[float]" = \
            collections.deque(maxlen=64)
        self._journal_compactions_seen = (
            self.journal.compactions if self.journal is not None else 0)

    @property
    def status(self) -> str:
        return self._status

    @property
    def degradations(self) -> Dict[str, int]:
        """Per-cause degradation counts for /health — the scrape-
        visible view of every composition the planner had to give up
        (docs/step-plan.md). `masked` and `spec_verify` staying 0 is
        the contract the plan/execute refactor introduced."""
        return {c: int(ch.value) for c, ch in self._c_degrade.items()}

    def _degrade(self, cause: str) -> None:
        self._c_degrade[cause].inc()
        self._flight_event("step_degradation", cause=cause)

    # backward-compat boolean view of the tri-state (degraded still
    # accepts work, so it reads healthy)
    @property
    def healthy(self) -> bool:
        return self._status != "dead"

    @healthy.setter
    def healthy(self, value: bool):
        self._status = "ok" if value else "dead"

    def _inc_locked(self, key: str, by: float = 1):
        """Caller holds self._lock. Mirrors into the registry — the
        counter's own leaf lock nests safely under ours."""
        self.stats[key] += by
        c = self._counters.get(key)
        if c is not None:
            c.inc(by)

    def _inc(self, key: str, by: float = 1):
        with self._lock:
            self._inc_locked(key, by)

    def _class_of(self, req: Request) -> str:
        """The request's priority class, coerced onto the fixed enum
        (per-class metric children and caps exist only for it)."""
        cls = getattr(req, "priority", DEFAULT_PRIORITY)
        return cls if cls in self._c_class_requests else \
            DEFAULT_PRIORITY

    def _preempt_rank(self, slot: int):
        """Victim-ranking hook installed on the engine (lower sorts
        first): over-quota classes — holding more decode slots than
        their weight share of the active batch — are preempted before
        in-quota ones, then lowest class first. The engine breaks the
        remaining tie by least progress, which preserves the
        pre-priority victim choice for single-class batches. Runs on
        the scheduler thread (inside the decode dispatch that grows
        KV blocks), so reading self.slots needs no lock."""
        if not self.priority_scheduling:
            return (1, 1)
        req = self.slots[slot] if 0 <= slot < len(self.slots) else None
        if req is None:
            return (1, len(PRIORITY_CLASSES))
        cls = self._class_of(req)
        counts: Dict[str, int] = {}
        for r in self.slots:
            if r is not None:
                c = self._class_of(r)
                counts[c] = counts.get(c, 0) + 1
        total = sum(counts.values())
        wsum = sum(self.class_weights[c] for c in counts)
        fair = (total * self.class_weights[cls] / wsum) if wsum \
            else float(total)
        over = counts.get(cls, 0) > fair + 1e-9
        return (0 if over else 1, CLASS_LEVEL.get(cls, 1))

    def _observe_finish(self, req: Request):
        """One-shot per-request latency observations, installed as
        req.on_finish at submit. Runs on whatever thread called
        finish() — touches only leaf-locked histograms."""
        end = req.finished_at if req.finished_at is not None \
            else time.monotonic()
        self._h_e2e.observe(end - req.created)
        self._h_class_e2e[self._class_of(req)].observe(
            end - req.created)
        if req.first_token_at is not None:
            self._h_ttft.observe(req.first_token_at - req.created)
            self._h_class_ttft[self._class_of(req)].observe(
                req.first_token_at - req.created)
            n = len(req.output_ids)
            if n > 1:
                self._h_tpot.observe(
                    (end - req.first_token_at) / (n - 1))

    def _request_finished(self, req: Request):
        """Installed as req.on_finish at submit: latency observations
        plus the journal's terminal record. A `shutdown` finish
        (drain-timeout eviction) or an `engine_fault` from a dead
        scheduler leaves the journal entry live — the process is
        going away and a restart resumes the work; every other reason
        means the request is DONE and tombstones it."""
        self._observe_finish(req)
        self._flush_decode_chunk(req, final=True)
        span = getattr(req, "_span", None)
        if span is not None and self.span_log.enabled:
            span.end(req.finished_at)
            span.set(request=req.id, finish_reason=req.finish_reason,
                     prompt_tokens=len(req.prompt_ids),
                     output_tokens=len(req.output_ids))
            self.span_log.write(span)
        if self.journal is not None:
            resumable = req.finish_reason == "shutdown" or (
                req.finish_reason == "engine_fault"
                and self._status == "dead")
            self.journal.finish(req, resumable=resumable)

    def _mark_scheduled(self, req: Request):
        """First time a request leaves the queue for a decode slot:
        the queue-wait phase ends here. Requeued/preempted requests
        keep their original mark (their wait was already served)."""
        if req.scheduled_at is None:
            req.scheduled_at = time.monotonic()
            self._h_queue_wait.observe(req.scheduled_at - req.created)
            self._h_class_queue_wait[self._class_of(req)].observe(
                req.scheduled_at - req.created)
            span = getattr(req, "_span", None)
            if span is not None and self.span_log.enabled:
                now_wall = time.time()
                q = Span("engine.queue", trace_id=span.trace_id,
                         parent_id=span.span_id,
                         start_mono=req.created,
                         start_wall=now_wall - (req.scheduled_at
                                                - req.created))
                q.end(req.scheduled_at).set(request=req.id)
                self.span_log.write(q)

    # -- flight recorder + span plumbing -------------------------------

    @contextlib.contextmanager
    def _phase(self, name: str, **attrs):
        """One scheduler phase: a `sched.<name>` span on the
        profiler's clock (kept only while POST /debug/profile
        captures; otherwise one atomic load) and an observation of
        ome_engine_step_phase_seconds{phase=<name>} from the same two
        timestamps, so the histogram and the span cannot drift.
        Yields the timestamps. `attrs` are small scalars (a step
        number, a plan kind, a request id) — never a prompt or a
        token list."""
        with jax.profiler.TraceAnnotation(SCHED_PREFIX + name, **attrs):
            ph = _Phase()
            try:
                yield ph
            finally:
                ph.t1 = time.monotonic()
                self._ph[name].observe(ph.dt)

    def _flight_event(self, event: str, **fields):
        self.flight.record(event, **fields)
        self._c_flight_events.inc()

    def _flight_autodump(self, reason: str) -> Optional[str]:
        """Dump the event ring to flight_dump_dir (crash recovery /
        dead transitions) so the lead-up to a fault survives the
        process. Best-effort: a failed dump never worsens recovery."""
        if self.flight_dump_dir is None:
            return None
        self._flight_dumps += 1
        path = os.path.join(
            self.flight_dump_dir,
            f"flight-{os.getpid()}-{self._flight_dumps}.json")
        try:
            os.makedirs(self.flight_dump_dir, exist_ok=True)
            self.flight.dump(path, reason=reason)
        except OSError:
            return None
        self._c_flight_dumps.inc()
        return path

    def _note_slot_assign(self, slot: int, req: Request):
        """Flight event + decode-chunk window start for a request
        entering a decode slot (fresh admission or preempt resume)."""
        self._flight_event("slot_assign", slot=slot, request=req.id)
        if self.span_log.enabled and getattr(req, "_span", None) \
                is not None:
            req._chunk = [time.monotonic(), time.time(), 0, 0,
                          getattr(req, "_chunk_base", 0)]

    def _begin_prefill_span(self, req: Request) -> Optional[Span]:
        """Minted BEFORE the prefill call so a PD remote fetch can
        parent its per-peer attempt spans on this span's id (the
        traceparent forwarded to `/pd/prefill` is a child of it)."""
        span = getattr(req, "_span", None)
        if span is None or not self.span_log.enabled:
            return None
        return Span("engine.prefill", trace_id=span.trace_id,
                    parent_id=span.span_id)

    def _end_prefill_span(self, req: Request, pspan: Optional[Span]):
        if pspan is None:
            return
        pspan.end().set(request=req.id,
                        prompt_tokens=len(req.prompt_ids))
        self.span_log.write(pspan)

    def _note_decode_progress(self, req: Request, tokens: int = 1):
        """Advance the request's decode-chunk accounting by one
        drained step; flushes a chunk span every span_chunk_steps.
        Called only from the drain path — never adds a host sync."""
        ch = getattr(req, "_chunk", None)
        if ch is None:
            return
        ch[2] += 1
        ch[3] += tokens
        if ch[2] >= self.span_chunk_steps:
            self._flush_decode_chunk(req)

    def _flush_decode_chunk(self, req: Request, final: bool = False):
        """Write the pending decode-chunk span (if any steps were
        drained since the last flush) and roll the chunk window
        forward so consecutive chunks tile without overlap."""
        ch = getattr(req, "_chunk", None)
        if ch is None:
            return
        span = getattr(req, "_span", None)
        if ch[2] > 0 and span is not None and self.span_log.enabled:
            end_mono = time.monotonic()
            s = Span("engine.decode", trace_id=span.trace_id,
                     parent_id=span.span_id,
                     start_mono=ch[0], start_wall=ch[1])
            s.end(end_mono)
            s.set(steps=ch[2], tokens=ch[3], chunk=ch[4],
                  request=req.id)
            self.span_log.write(s)
            ch[0] = end_mono
            ch[1] += s.dur_s
            ch[2] = 0
            ch[3] = 0
            ch[4] += 1
        if final:
            # remember where the numbering got to, so a preempted
            # request re-admitted later continues its chunk sequence
            req._chunk_base = ch[4]
            req._chunk = None

    def debug_state(self) -> dict:
        """Point-in-time JSON snapshot behind GET /debug/state: live
        slots, queue/pool/journal counters, flight-ring state. Reads
        are lock-free on purpose (the scheduler thread owns the
        structures); a concurrent mutation can skew one field by one
        request, which is fine for a debug surface."""
        slots = []
        owned = getattr(self.engine, "_owned", None)
        for slot, req in enumerate(list(self.slots)):
            if req is None:
                continue
            entry = {"slot": slot, "request": req.id,
                     "journal_id": req.journal_id,
                     "prompt_tokens": len(req.prompt_ids),
                     "committed_tokens": len(req.output_ids),
                     "adapter": req.adapter,
                     "class": req.priority}
            if owned is not None:
                try:
                    entry["kv_blocks_owned"] = len(owned[slot])
                except (IndexError, TypeError):
                    pass
            slots.append(entry)
        state = {
            "status": self._status,
            "draining": self._draining,
            "queue_depth": self.pending.qsize(),
            "queue_depths": self.pending.depths(),
            "priority_scheduling": self.priority_scheduling,
            "requeued": len(self._requeue),
            "ready": self._ready.qsize(),
            "inflight_steps": len(self._inflight),
            "admitting": self._admitting,
            "max_slots": self.engine.max_slots,
            "active_slots": len(slots),
            "slots": slots,
            "flight": self.flight.state(),
        }
        pool = getattr(self.engine, "kv_pool_stats", None)
        if pool and pool.get("kv_block_tokens"):
            state["kv_pool"] = dict(pool)
        j = self.journal
        state["journal"] = None if j is None else {
            "path": j.path, "appends": j.appends, "errors": j.errors,
            "compactions": j.compactions, "replayed": j.replayed,
            "degraded": j.degraded,
            "bytes": getattr(j, "_bytes", None)}
        return state

    def update_gauges(self):
        """Refresh point-in-time gauges (called by /metrics scrapes
        and after each step; counters stream in continuously)."""
        self._g_queue_depth.set(self.pending.qsize())
        for cls, depth in self.pending.depths().items():
            self._g_class_depth[cls].set(depth)
        active = sum(r is not None for r in self.slots)
        self._g_active.set(active)
        self._g_occupancy.set(active / max(self.engine.max_slots, 1))
        status = self._status
        for state in ("ok", "degraded", "dead"):
            self._g_status.labels(state=state).set(
                1 if state == status else 0)
        pc = getattr(self.engine, "prefix_cache", None)
        if pc is not None:
            # counters on the cache are plain ints (bumped inside the
            # prefill path without registry locks); mirror by delta
            for counter, value in ((self._c_pc_hits, pc.hits),
                                   (self._c_pc_misses, pc.misses),
                                   (self._c_pc_evictions,
                                    pc.evictions),
                                   (self._c_pc_host_hits,
                                    getattr(pc, "host_hits", 0)),
                                   (self._c_pc_host_swapins,
                                    getattr(pc, "host_swapins", 0)),
                                   (self._c_pc_host_recomputes,
                                    getattr(pc, "host_recomputes", 0))):
                _mirror(counter, value)
            self._g_pc_bytes.set(pc.bytes)
            self._g_pc_host_bytes.set(getattr(pc, "host_bytes", 0))
        pool = getattr(self.engine, "kv_pool_stats", None)
        if pool and pool.get("kv_block_tokens"):  # paged engines only
            total = pool.get("kv_blocks", 0)
            free = pool.get("kv_blocks_free", 0)
            self.registry.gauge(
                "ome_engine_kv_blocks_free",
                "Free paged-KV blocks").set(free)
            self.registry.gauge(
                "ome_engine_kv_block_utilization_ratio",
                "Occupied fraction of the paged-KV pool").set(
                (total - free) / total if total else 0.0)
            conserve = getattr(self.engine, "kv_conservation", None)
            if callable(conserve):
                ok, owned = conserve()
                self.registry.gauge(
                    "ome_engine_kv_blocks_owned",
                    "Paged-KV blocks held by live slots").set(owned)
                # the share of the block table that holds a block: the
                # attention kernel walks these cells and no others, so
                # it is the share of a (slots x table width) grid that
                # would have been work
                cells = self.engine.max_slots * self.engine.max_blocks
                self.registry.gauge(
                    "ome_engine_kv_table_fill_ratio",
                    "Block-table cells that hold a block, over slots "
                    "x table width").set(owned / cells)
                # authoritative at quiescence; a concurrent
                # insert/free can briefly read as 0 mid-scrape
                self.registry.gauge(
                    "ome_engine_kv_conservation_ok",
                    "1 when free + owned blocks account for the whole "
                    "pool (checked per scrape; authoritative when "
                    "idle)").set(1 if ok else 0)
        # what the prefill kernel's grid held, by kind: plain ints the
        # engine adds to at each prefill it runs; mirror by delta
        blocks = getattr(self.engine, "prefill_attn_blocks", None) or {}
        for kind, n in blocks.items():
            _mirror(self._c_attn_blocks[kind], n)
        pd = getattr(self.engine, "update_pd_gauges", None)
        if callable(pd):
            pd()
        # the second kind of per-slot state, and what an expert layer
        # that holds a share of its experts counted on the device:
        # both read here, at scrape, never on the step path
        state_fn = getattr(self.engine, "state_bytes", None)
        if callable(state_fn):
            self._g_state_bytes.set(state_fn())
        ring_fn = getattr(self.engine, "ring_bytes", None)
        if callable(ring_fn):
            from ..perf.hbm import kv_capacity_bytes
            self._g_kv_bytes["window"].set(ring_fn())
            self._g_kv_bytes["global"].set(
                kv_capacity_bytes(self.engine))
        counts_fn = getattr(self.engine, "moe_counters", None)
        counts = counts_fn() if callable(counts_fn) else None
        if counts:
            for name, counter in self._c_moe.items():
                _mirror(counter, counts[name])
        # live HBM partition (perf/hbm.py): refreshed per scrape, not
        # per step — memory_stats() is a host call the decode loop
        # should not pay
        if self.hbm is not None:
            self.hbm.update(self.engine)

    # -- public --------------------------------------------------------

    def _queue_wait_estimate(self, depth: int) -> Optional[float]:
        """Rough seconds until a newly queued request would start
        decoding: queue depth in batch waves x observed per-request
        decode steps x observed step time. None until both EWMAs have
        samples (cold start admits optimistically)."""
        if depth <= 0 or self._ewma_step_s is None \
                or self._ewma_req_steps is None:
            return None
        waves = math.ceil(depth / self.engine.max_slots)
        return waves * self._ewma_req_steps * self._ewma_step_s

    def _class_wait_estimate(self, cls: str,
                             depth: int) -> Optional[float]:
        """Per-class queue-wait estimate: the class's own backlog
        drains at roughly its weight share of the active classes'
        total weight, so the plain estimate is scaled up by the
        inverse share. With one active class the factor is 1 — the
        global estimate exactly, which keeps single-class admission
        identical with priority scheduling on or off."""
        base = self._queue_wait_estimate(depth)
        if base is None or not self.priority_scheduling:
            return base
        w = self.class_weights
        active = {c for c in PRIORITY_CLASSES
                  if self.pending.qsize(c) > 0}
        active.add(cls)
        share = sum(w[c] for c in active)
        return base * (share / w[cls]) if share else base

    def retry_after_hint(self, default: float = 1.0) -> int:
        """Seconds a rejected/bounced client should back off, from
        the live queue-wait estimate, clamped to [1, 30] — the
        server's Retry-After header for its 429/503 paths."""
        est = self._queue_wait_estimate(self.pending.qsize() + 1)
        val = est if est is not None else default
        return int(min(max(math.ceil(val), 1), 30))

    def submit(self, req: Request) -> Request:
        # the lock makes submit-vs-stop atomic: a request either gets
        # queued before the shutdown drain, or is rejected here
        with self._lock:
            if self._stop.is_set() or self._status == "dead":
                raise RuntimeError("scheduler unavailable")
            if self._draining:
                raise SchedulerDraining(
                    "scheduler draining (shutdown signal received); "
                    "resubmit to another replica")
            self._inc_locked("requests_total")
            req.on_finish = self._request_finished
            if req.expired():
                # dead on arrival: never queued, never slotted
                self._inc_locked("timeouts_total")
                req.finish("timeout")
                return req
            cls = self._class_of(req)
            self._c_class_requests[cls].inc()
            # per-class admission control: a class sheds on ITS OWN
            # queue depth and wait cap, so a batch flood 429s batch
            # traffic (its estimate grows with backlog and shrinks
            # with weight) long before interactive admission feels it
            # — shedding hits the lowest class first by construction
            if self.priority_scheduling:
                depth = self.pending.qsize(cls)
                cap = self.class_wait_caps.get(cls,
                                               self.max_queue_wait)
            else:
                depth = self.pending.qsize()
                cap = self.max_queue_wait
            est = self._class_wait_estimate(cls, depth + 1)
            if depth >= self.pending.maxsize or \
                    (est is not None and est > cap):
                self._inc_locked("rejected_total")
                self._c_class_rejected[cls].inc()
                retry = min(max(est if est is not None else 1.0, 0.5),
                            30.0)
                raise SchedulerOverloaded(
                    f"{cls} queue saturated (depth {depth}, "
                    f"estimated wait {est if est is not None else '?'}"
                    f"s, cap {cap:g}s)", retry_after=retry)
            if self.span_log.enabled:
                # the engine-side request span: parented under the span
                # id the router forwarded in `traceparent` (so the
                # router's attempt span encloses it); every scheduler
                # phase span hangs off this one. Written at finish.
                # Minted BEFORE the queue put — once the request is
                # visible, the (overlap) admission thread may schedule
                # it immediately, and the phase spans key off _span.
                req._span = Span.begin("engine.request", ctx=req.trace,
                                       start_mono=req.created)
            journal_it = self.journal is not None and \
                req.masker is None
        # journal the admit with the scheduler lock RELEASED: the
        # append fsyncs (policy "always"), and the decode thread takes
        # self._lock per emitted token — an fsync inside the region
        # stalls every inflight decode. Writing before the queue put
        # also pins the replay ordering: once the request is visible,
        # a fast finish may call journal.finish immediately, and the
        # tombstone must land after an admit record, not before one.
        if journal_it:
            self.journal.admit(req)
        reject: Optional[Tuple[str, Exception]] = None
        with self._lock:
            # re-check what can have flipped while the journal synced;
            # the submit-vs-stop atomicity now holds at THIS region
            if self._stop.is_set() or self._status == "dead":
                reject = ("shutdown",
                          RuntimeError("scheduler unavailable"))
            elif self._draining:
                reject = ("draining", SchedulerDraining(
                    "scheduler draining (shutdown signal received); "
                    "resubmit to another replica"))
            else:
                depth = self.pending.qsize()
                try:
                    self.pending.put_nowait(req)
                except queue.Full:
                    self._inc_locked("rejected_total")
                    self._c_class_rejected[cls].inc()
                    reject = ("rejected", SchedulerOverloaded(
                        f"{cls} pending queue full", retry_after=1.0))
                else:
                    self._flight_event("admit", request=req.id,
                                       cls=cls, depth=depth + 1)
        if reject is not None:
            # tombstone OUTSIDE the lock too — it appends + fsyncs
            self._journal_tombstone(req, journal_it, reject[0])
            raise reject[1]
        return req

    def _journal_tombstone(self, req: Request, journal_it: bool,
                           reason: str):
        """A request was journaled as admitted but then rejected in
        the re-check window (stop/drain/queue-full raced the journal
        fsync). Without the tombstone the admit record stays live and
        the next process would replay a request the client was told
        to retry elsewhere — a duplicate."""
        if not journal_it or self.journal is None:
            return
        if req.finish_reason is None:
            req.finish_reason = reason
        self.journal.finish(req, resumable=False)

    def start(self):
        # idempotent: EngineServer.start() also starts its scheduler, so
        # a caller that started it explicitly must not end up with TWO
        # driver threads racing donated state buffers
        if self._thread is not None and self._thread.is_alive():
            return
        self._thread = threading.Thread(target=self._run,
                                        name="ome-scheduler", daemon=True)
        self._thread.start()
        if self.overlap:
            self._admit_thread = threading.Thread(
                target=self._admit_loop, name="ome-admission",
                daemon=True)
            self._admit_thread.start()

    def stop(self):
        with self._lock:
            self._stop.set()
        if self._thread:
            self._thread.join(timeout=10)
        if self._admit_thread:
            self._admit_thread.join(timeout=10)
        # `shutdown` (vs `engine_fault`): an orderly eviction — the
        # work was fine, the process is going away. The router may
        # safely retry these, and a journal keeps them resumable.
        self._fail_all("shutdown")
        self.span_log.close()

    # -- graceful drain (docs/durability.md) ---------------------------

    def begin_drain(self):
        """Stop admitting NEW requests (503 SchedulerDraining) while
        queued and in-flight work keeps running to completion. The
        decode loop is untouched — drain is an admission-side state,
        not a stop."""
        with self._lock:
            self._draining = True
        self._flight_event("drain_begin",
                           queue_depth=self.pending.qsize(),
                           active=sum(r is not None
                                      for r in self.slots))

    @property
    def draining(self) -> bool:
        return self._draining

    def drain_idle(self) -> bool:
        """True when no admitted work remains anywhere in the
        scheduler: the drain controller polls this to know the grace
        window can end early."""
        return (self.pending.empty() and not self._requeue
                and self._ready.empty() and not self._inflight
                and self._admitting == 0
                and all(r is None for r in self.slots))

    # -- restart resume (docs/durability.md) ---------------------------

    def resume_from_journal(self) -> int:
        """Re-admit every unfinished request the journal replays,
        with generated-so-far tokens folded into the prompt — the
        exact recompute-resume fold paged-KV preemption uses, so a
        greedy stream continues byte-identical to an uninterrupted
        run. Original deadlines are honored (journaled as epoch,
        converted back to this process's monotonic clock); an entry
        that expired while the replica was down finishes `timeout`
        through the normal DOA shedding. Returns the number of
        requests re-admitted."""
        import logging
        log = logging.getLogger("ome.engine")
        j = self.journal
        if j is None:
            return 0
        t0_mono = time.monotonic()
        t0_wall = time.time()
        try:
            entries = j.replay()
        except Exception:  # noqa: BLE001 — a corrupt journal must not
            # stop the replica from serving new work
            log.exception("journal replay failed; starting empty")
            j._count(j._c_errors, "errors")
            return 0
        n = 0
        now_mono = time.monotonic()
        now_wall = time.time()
        for e in entries:
            deadline = None
            if e.deadline_epoch is not None:
                deadline = now_mono + (e.deadline_epoch - now_wall)
            req = Request(
                prompt_ids=list(e.prompt_ids) + list(e.output_ids),
                max_new_tokens=e.max_new_tokens,
                temperature=e.temperature, top_k=e.top_k,
                top_p=e.top_p, stop_ids=list(e.stop_ids),
                adapter=e.adapter, deadline=deadline,
                priority=getattr(e, "cls", DEFAULT_PRIORITY),
                journal_id=e.jid,
                output_ids=list(e.output_ids))
            if len(req.output_ids) >= req.max_new_tokens:
                # it had already produced its whole budget; only the
                # tombstone was lost to the crash
                req.finish("length")
                j.finish(req)
                continue
            try:
                self.submit(req)
            except SchedulerOverloaded:
                # more journal than queue: leave the entry live for
                # the next restart rather than dropping it
                log.warning("journal: queue full, request %d not "
                            "resumed (stays journaled)", e.jid)
                continue
            n += 1
        if n:
            j.note_replayed(n)
            log.info("journal: resumed %d unfinished request(s)", n)
        self._flight_event("journal_replay", entries=len(entries),
                           resumed=n)
        if self.span_log.enabled:
            s = Span("engine.journal_replay",
                     trace_id=self._span_ctx.trace_id,
                     parent_id=self._span_ctx.span_id,
                     start_mono=t0_mono, start_wall=t0_wall)
            s.end().set(entries=len(entries), resumed=n)
            self.span_log.write(s)
        return n

    def _next_pending(self) -> Request:
        """Requeued (bounced / preempted) requests go first; raises
        queue.Empty like pending.get_nowait(). Expired or already-
        finished (server-side timeout) requests are shed here — they
        never occupy a decode slot."""
        while True:
            try:
                req = self._requeue.popleft()
            except IndexError:
                req = self.pending.get_nowait()  # Empty propagates
            if self._shed_if_expired(req):
                continue
            return req

    def _shed_if_expired(self, req: Request) -> bool:
        if req.done.is_set():
            return True  # finished elsewhere (server-side timeout)
        if req.expired():
            self._inc("timeouts_total")
            req.finish("timeout")
            return True
        return False

    def _fail_all(self, reason: str):
        self._inflight.clear()  # unread steps die with their batch
        self._dispatch_end = None
        with self._lock:
            while True:
                try:
                    self._requeue.popleft().finish(reason)
                except IndexError:
                    break
            while True:
                try:
                    self.pending.get_nowait().finish(reason)
                except queue.Empty:
                    break
            while True:
                try:
                    item = self._ready.get_nowait()
                except queue.Empty:
                    break
                item[0].finish(reason)
                self._free_slots.release()
            for slot, r in enumerate(self.slots):
                if r is not None:
                    self.slots[slot] = None
                    self._slot_changed(slot)
                    free = getattr(self.engine, "free_slot", None)
                    if free is not None:
                        try:
                            free(slot)
                        except Exception:  # noqa: BLE001 — draining a
                            pass  # faulted engine must not abort
                    r.finish(reason)
                    if self.overlap:
                        self._free_slots.release()

    # -- core loop -----------------------------------------------------

    def step(self) -> bool:
        """One admission + decode round; returns True if work was done.

        Overlap mode inserts whatever the admission thread finished
        prefilling since the last step (insert is cheap — one compiled
        dynamic_update_slice). Synchronous mode (multi-host leaders)
        admits at most ONE prefill per decode step while streams are
        active — the JetStream slicing pattern — so a burst of long
        prompts adds bounded latency instead of stalling the batch.
        """
        if self.overlap:
            admitted = self._insert_ready()
        else:
            active = any(r is not None for r in self.slots)
            admitted = self._admit(limit=1 if active else None)
        decoded = self._decode()
        if self.journal is not None:
            # progress records cover everything emitted up to this
            # step boundary, so a crash never loses a token a client
            # already saw; the batch fsync policy piggybacks here
            self.journal.poll()
            comp = self.journal.compactions
            if comp > self._journal_compactions_seen:
                self._journal_compactions_seen = comp
                self._flight_event("journal_compaction", count=comp)
        with self._lock:
            self.stats["queue_depth"] = self.pending.qsize()
            self.stats["active_slots"] = sum(
                r is not None for r in self.slots)
        return admitted or decoded

    # -- overlap mode: admission thread prefills, step() inserts -------

    def _admit_loop(self):
        while not self._stop.is_set() and self._status != "dead":
            if self._status != "ok" or self._fault_event.is_set():
                # recovery in flight: hold admission (requests queue)
                # until the scheduler thread restores the engine state
                time.sleep(0.005)
                continue
            # slot credit first: at most max_slots prefills in flight
            # ahead of their inserts
            if not self._free_slots.acquire(timeout=0.05):
                continue
            try:
                req = self._requeue.popleft()
            except IndexError:
                try:
                    req = self.pending.get(timeout=0.05)
                except queue.Empty:
                    self._free_slots.release()
                    continue
            # from here until the request lands in _ready (or
            # finishes), it is invisible to every queue — the counter
            # keeps drain_idle() honest about it
            self._admitting += 1
            try:
                if self._shed_if_expired(req):
                    self._free_slots.release()
                    continue
                if not self._fits_pool(req):
                    req.finish("error")
                    self._free_slots.release()
                    continue
                if not self._pool_ready(req):
                    # saturated pool: back off instead of re-prefilling
                    self._requeue.appendleft(req)
                    self._free_slots.release()
                    time.sleep(0.01)
                    continue
                self._mark_scheduled(req)
                pspan = self._begin_prefill_span(req)
                try:
                    tok, kv, true_len, bucket = self._timed_prefill(
                        req, pspan)
                except Exception as e:  # noqa: BLE001
                    import logging

                    from .core import UnknownAdapterError

                    # engines that fetch prefill remotely (PD decode
                    # nodes) declare which errors are TRANSIENT — a peer
                    # restarting mid-rollout fails one request, not every
                    # in-flight stream on this node. An unknown LoRA
                    # adapter (request racing a hot unload) is likewise
                    # that request's problem, never an engine fault.
                    transient = (UnknownAdapterError,) + tuple(
                        getattr(self.engine, "transient_prefill_errors",
                                ()))
                    if isinstance(e, transient):
                        logging.getLogger("ome.engine").warning(
                            "transient prefill failure for request "
                            "%s: %s", req.id, e)
                        req.finish("error")
                        self._free_slots.release()
                        continue
                    # local engine fault: this request is lost, but the
                    # SCHEDULER thread owns recovery — signal it and keep
                    # the admission thread alive to resume after restart
                    logging.getLogger("ome.engine").exception(
                        "prefill failed; requesting engine recovery")
                    req.finish("error")
                    self._free_slots.release()
                    self._fault_event.set()
                    continue
                self._end_prefill_span(req, pspan)
                self._inc("prefill_total")
                # under _lock so a prefill that outlives stop()'s join
                # or a scheduler-thread death (e.g. a slow remote PD
                # fetch) cannot strand its request in _ready after
                # _fail_all drained it — the waiter would hang forever
                with self._lock:
                    if self._stop.is_set() or not self.healthy:
                        req.finish("shutdown" if self._stop.is_set()
                                   else "error")
                        self._free_slots.release()
                        return
                    self._ready.put((req, tok, kv, true_len, bucket))
            finally:
                self._admitting -= 1

    def _insert_ready(self) -> bool:
        did = False
        while True:
            try:
                req, tok, kv, true_len, bucket = self._ready.get_nowait()
            except queue.Empty:
                break
            slot = self.slots.index(None)  # semaphore guarantees one
            with self._phase("insert", request=req.id, slot=slot):
                did = self._insert_one(req, tok, kv, true_len, bucket,
                                       slot) or did
        return did

    def _insert_one(self, req: Request, tok, kv, true_len, bucket,
                    slot: int) -> bool:
        """Move one prefilled request into `slot`; False when it was
        requeued or failed instead."""
        ikw = {} if req.adapter is None else {"adapter": req.adapter}
        try:
            self.state = self.engine.insert(
                self.state, kv, slot, true_len, tok, bucket, **ikw)
        except Exception as e:  # noqa: BLE001
            from .core import KVPoolExhausted, UnknownAdapterError
            if isinstance(e, KVPoolExhausted):
                # paged-KV backpressure: requeue until running
                # streams free blocks (prefilled KV is dropped —
                # the request re-prefills on its next turn)
                self._requeue.appendleft(req)
                self._free_slots.release()
                return False
            transient = (UnknownAdapterError,) + tuple(
                getattr(self.engine, "transient_prefill_errors", ()))
            if isinstance(e, transient):
                # adapter hot-unloaded between prefill and insert,
                # or a PD insert of fetched KV failed: this
                # request fails, the node stays up
                req.finish("error")
                self._free_slots.release()
                return False
            # engine fault: req is out of every queue so _recover
            # cannot see it — fail it (and return its slot credit)
            # before propagating to the recovery handler in _run
            req.finish("error")
            self._free_slots.release()
            raise
        self._seat(req, slot, tok, true_len)
        return True

    def _seat(self, req: Request, slot: int, tok, true_len) -> None:
        """Slot bookkeeping of a request whose KV the engine just
        inserted, then its first token."""
        self.slots[slot] = req
        self._slot_changed(slot)
        self._note_slot_assign(slot, req)
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        self._true_len[slot] = true_len
        self._base_out[slot] = len(req.output_ids)
        req.emit(tok)
        self._maybe_finish(slot, tok)

    def _admit(self, limit: Optional[int] = None) -> bool:
        did = False
        admitted = 0
        for slot, occupant in enumerate(self.slots):
            if occupant is not None:
                continue
            if limit is not None and admitted >= limit:
                break
            try:
                req = self._next_pending()
            except queue.Empty:
                break
            # between the pop and the slot assignment (or a requeue)
            # the request is in no queue — the counter keeps
            # drain_idle() honest about it, exactly as in the overlap
            # admission thread
            self._admitting += 1
            try:
                if not self._fits_pool(req):
                    req.finish("error")
                    continue
                if not self._pool_ready(req):
                    # pool saturated: retry next step WITHOUT burning
                    # a prefill forward that insert would just bounce
                    self._requeue.appendleft(req)
                    break
                self._mark_scheduled(req)
                pspan = self._begin_prefill_span(req)
                try:
                    tok, kv, true_len, bucket = self._timed_prefill(
                        req, pspan)
                    self._end_prefill_span(req, pspan)
                    ikw = {} if req.adapter is None \
                        else {"adapter": req.adapter}
                    with self._phase("insert", request=req.id,
                                     slot=slot):
                        self.state = self.engine.insert(
                            self.state, kv, slot, true_len, tok,
                            bucket, **ikw)
                except Exception as e:
                    from .core import (KVPoolExhausted,
                                       UnknownAdapterError)
                    if isinstance(e, KVPoolExhausted):
                        # paged-KV backpressure: retry next step,
                        # after running streams have freed blocks
                        self._requeue.appendleft(req)
                        break
                    transient = (UnknownAdapterError,) + tuple(
                        getattr(self.engine,
                                "transient_prefill_errors", ()))
                    if isinstance(e, transient):
                        # racing a hot adapter unload — or a PD
                        # fetch/insert failure on a synchronous-step
                        # node — fails ONE request, not the engine
                        req.finish("error")
                        continue
                    # req is out of the queue but not yet slotted, so
                    # the recovery handler cannot see it — fail it
                    # here before propagating to _recover in _run
                    req.finish("error")
                    raise
                self._inc("prefill_total")
                self._seat(req, slot, tok, true_len)
                did = True
                admitted += 1
            finally:
                self._admitting -= 1
        return did

    def _slot_changed(self, slot: int):
        """Every slot-occupancy change funnels through here: the
        generation bump retires any in-flight lagged token sampled for
        the previous occupant, the planner's predicted tail resets
        (a new occupant has nothing beyond its committed stream), and
        the device sampling cache is dropped so the next dispatch
        re-uploads the new [B] params."""
        self._slot_gen[slot] += 1
        self._planned_tail[slot] = []
        self._sampling_dev = None
        self._stops_dev = None

    def _sampling(self):
        """Device-resident (temperature, top_k, top_p) for the whole
        batch, re-uploaded only after an occupancy/param change — not
        three fresh host arrays per decode step."""
        if self._sampling_dev is None:
            self._sampling_dev = (jnp.asarray(self._temp),
                                  jnp.asarray(self._top_k),
                                  jnp.asarray(self._top_p))
            # the predicate sampling.filtered_logits evaluates on the
            # device, here on the host's copy of the same vectors
            self._sample_tier = "filtered" if row_filters(
                self._temp, self._top_k, self._top_p).any() else "plain"
        return self._sampling_dev

    def _stop_table(self):
        """Device-resident [B, NS] stop table for multi-step chunks
        (-1 padding never matches a sampled token), cached like the
        sampling params: re-uploaded only on occupancy change. Stop
        ids past the fixed width stay host-detected — the device
        table being a SUBSET of each request's stop set only costs
        discarded overshoot, never a wrong stream."""
        if self._stops_dev is None:
            tab = np.full((self.engine.max_slots, _STOP_TABLE_WIDTH),
                          -1, np.int32)
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                ids = list(req.stop_ids)[:_STOP_TABLE_WIDTH]
                tab[slot, :len(ids)] = ids
            self._stops_dev = jnp.asarray(tab)
        return self._stops_dev

    def _multi_budget(self, k: int) -> np.ndarray:
        """Per-slot remaining-token cap for one chunk. Under
        pipelining this over-counts by whatever is still in flight
        (output_ids lags the device) — deliberately: the device may
        only run LONG, and _maybe_finish cuts the stream at the exact
        budget when the chunk drains, so K=1 and K=8 emit identical
        bytes."""
        budget = np.zeros(self.engine.max_slots, np.int32)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            budget[slot] = min(
                max(req.max_new_tokens - len(req.output_ids), 0), k)
        return budget

    @staticmethod
    def _flight_rows(payload) -> int:
        """Device KV rows one lag-queue entry may commit per slot —
        the unit the paged reserve / lookahead / spec-headroom
        accounting sums over plans still in flight (shape reads are
        metadata only, never a device sync)."""
        if isinstance(payload, _SpecStep):
            return int(payload.out.shape[1])
        if isinstance(payload, _MultiStep):
            return int(payload.k)
        return 1

    def _inflight_rows(self) -> int:
        """Summed per-slot KV rows of every plan still in flight."""
        return sum(self._flight_rows(e[0]) for e in self._inflight)

    def _note_actual(self, slot: int, toks) -> None:
        """Reconcile one drained slot against the planner's predicted
        tail: an exact prefix match consumes it; any divergence marks
        the slot's device-side continuation unknown, and the next plan
        that needs it re-anchors by flushing (docs/step-plan.md)."""
        tail = self._planned_tail[slot]
        if tail is None:
            return
        toks = [int(t) for t in toks]
        n = len(toks)
        if len(tail) >= n and tail[:n] == toks:
            self._planned_tail[slot] = tail[n:]
        else:
            self._planned_tail[slot] = None

    def _flush_inflight(self) -> bool:
        """Drain every lagged step and re-anchor the planner's
        predicted tails at the committed stream (host and device now
        agree). Returns True when the drain finished every slot."""
        self._drain_inflight()
        for s in range(len(self._planned_tail)):
            self._planned_tail[s] = []
        return not any(r is not None for r in self.slots)

    def _drain_inflight(self, keep: int = 0) -> bool:
        """Read dispatched steps older than the newest `keep`, oldest
        first, emitting each token whose slot still holds the SAME
        admission it was sampled for. Slots that finished, preempted,
        failed, or were re-admitted since dispatch had their
        generation bumped, so their speculative token is discarded
        here. This is the decode loop's only device->host token fetch
        (enforced by scripts/check_decode_sync.py) — under pipelining
        it runs AFTER the next step was dispatched, and the async copy
        decode() started is usually already complete."""
        did = False
        drained = 0
        while len(self._inflight) > keep:
            toks, snap_slots, snap_gens, sent = self._inflight.popleft()
            if isinstance(toks, _SpecStep):
                self._drain_spec(toks, snap_slots, snap_gens, sent)
                did = True
                drained += 1
                continue
            if isinstance(toks, _MultiStep):
                self._drain_multi(toks, snap_slots, snap_gens, sent)
                did = True
                drained += 1
                continue
            # phase attribution: the block below is the lag-queue
            # read — the only point the host waits on the device —
            # and the emit loop after it is host-side sampling/offload
            with self._phase("device_wait", step=sent.n) as wait:
                host_toks = np.asarray(toks)
            self._step_done(sent, wait.t1)
            with self._phase("host_sample", step=sent.n):
                for slot, req in enumerate(snap_slots):
                    if (req is None or self.slots[slot] is not req
                            or self._slot_gen[slot] != snap_gens[slot]):
                        continue
                    tok = int(host_toks[slot])
                    self._note_actual(slot, (tok,))
                    req.emit(tok)
                    self._inc("tokens_generated_total")
                    self._c_class_tokens[self._class_of(req)].inc()
                    self._note_decode_progress(req)
                    self._maybe_finish(slot, tok)
            did = True
            drained += 1
        if drained:
            self._flight_event("pipeline_drain", steps=drained,
                               kept=keep)
        return did

    def _drain_spec(self, step: _SpecStep, snap_slots, snap_gens,
                    sent: _Dispatched):
        """Emit one drained verify step: slot b produced
        out[b, :accepted[b]+1] (accepted draft prefix + one sampled
        token). Runs only from _drain_inflight — the host fetch below
        completes the async copies verify() started. A slot that
        finishes mid-prefix (stop token / deadline / length) discards
        the rest of its accepted tokens, exactly as those steps would
        never have run without speculation; the usual generation
        check discards whole slots that changed occupant since
        dispatch."""
        with self._phase("device_wait", step=sent.n) as wait:
            host_out = np.asarray(step.out)
            host_acc = np.asarray(step.accepted)
        self._step_done(sent, wait.t1)
        with self._phase("host_sample", step=sent.n):
            self._emit_spec(step, host_out, host_acc, snap_slots,
                            snap_gens)

    def _emit_spec(self, step: _SpecStep, host_out, host_acc,
                   snap_slots, snap_gens):
        dlen = step.draft_len
        proposed = int(dlen.sum())
        accepted = 0
        if proposed:
            # acceptance accounting covers every drafting slot, even
            # ones whose tokens are later discarded — the drafter/
            # verify quality signal is about what the model accepted
            accepted = int(host_acc.sum())
            self._h_spec_accept.observe(accepted / proposed)
            for slot in np.nonzero(dlen)[0]:
                self._h_spec_accepted.observe(int(host_acc[slot]))
            self._inc("spec_accepted_tokens_total", accepted)
        self._spec_last = (proposed, accepted)
        self._flight_event("spec_accept", proposed=proposed,
                           accepted=accepted)
        commit = getattr(self.engine, "commit_spec", None)
        # later plans were dispatched against block pre-allocations
        # covering their rows; commit must not trim those
        reserve = self._inflight_rows()
        for slot, req in enumerate(snap_slots):
            if (req is None or self.slots[slot] is not req
                    or self._slot_gen[slot] != snap_gens[slot]):
                continue
            n = int(host_acc[slot]) + 1
            if commit is not None:
                # paged KV: reconcile the host length mirror and
                # return the speculative surplus blocks to the pool
                commit(slot, n, reserve=reserve)
            self._note_actual(slot, host_out[slot, :n])
            self._note_decode_progress(req, tokens=n)
            for tok in host_out[slot, :n]:
                req.emit(int(tok))
                self._inc("tokens_generated_total")
                self._c_class_tokens[self._class_of(req)].inc()
                self._maybe_finish(slot, int(tok))
                if self.slots[slot] is not req:
                    break  # finished mid-prefix: drop the tail
        if self.span_log.enabled and step.t_dispatch:
            # one span per verify round, timed dispatch-to-drain (the
            # lag a pipelined verify rides shows up as span length)
            s = Span("engine.spec_verify",
                     trace_id=self._span_ctx.trace_id,
                     parent_id=self._span_ctx.span_id,
                     start_mono=step.t_dispatch,
                     start_wall=time.time() - (time.monotonic()
                                               - step.t_dispatch))
            s.end().set(proposed=proposed, accepted=accepted)
            self.span_log.write(s)

    def _drain_multi(self, step: _MultiStep, snap_slots, snap_gens,
                     sent: _Dispatched):
        """Emit one drained multi-token chunk: slot b produced
        step.out[b, :advanced[b]] (docs/multi-step-decode.md). Runs
        only from _drain_inflight — the host fetch below completes
        the async copies decode_multi() started; it is the chunk's
        single device sync. The overshoot/discard rule: _maybe_finish
        applies every host finish condition (full stop set, deadline,
        exact budget, capacity) token by token, so everything the
        device ran past a host finish is dropped here — including a
        mid-chunk EOS tail — and the usual generation check drops
        whole slots whose occupant changed since dispatch. Paged
        engines reconcile allocator state per slot via commit_spec,
        reserving rows for chunks still in flight."""
        with self._phase("device_wait", step=sent.n) as wait:
            host_out = np.asarray(step.out)       # [B, k]
            host_adv = np.asarray(step.advanced)  # [B]
        self._step_done(sent, wait.t1)
        with self._phase("host_sample", step=sent.n):
            self._emit_multi(step, host_out, host_adv, snap_slots,
                             snap_gens)

    def _emit_multi(self, step: _MultiStep, host_out, host_adv,
                    snap_slots, snap_gens):
        commit = getattr(self.engine, "commit_spec", None)
        # later plans were dispatched against block pre-allocations
        # covering their rows; commit must not trim those
        reserve = self._inflight_rows()
        emitted = 0
        for slot, req in enumerate(snap_slots):
            if (req is None or self.slots[slot] is not req
                    or self._slot_gen[slot] != snap_gens[slot]):
                continue
            n = int(host_adv[slot])
            if commit is not None:
                commit(slot, n, reserve=reserve)
            self._note_actual(slot, host_out[slot, :n])
            if n:
                self._note_decode_progress(req, tokens=n)
            for tok in host_out[slot, :n]:
                req.emit(int(tok))
                emitted += 1
                self._inc("tokens_generated_total")
                self._c_class_tokens[self._class_of(req)].inc()
                self._maybe_finish(slot, int(tok))
                if self.slots[slot] is not req:
                    break  # finished mid-chunk: overshoot discarded
        if self.span_log.enabled:
            s = Span("engine.decode_chunk",
                     trace_id=self._span_ctx.trace_id,
                     parent_id=self._span_ctx.span_id,
                     start_mono=step.t_dispatch,
                     start_wall=time.time() - (time.monotonic()
                                               - step.t_dispatch))
            s.end().set(steps_per_dispatch=step.k, tokens=emitted)
            if step.cost is not None:
                # cost attribution from the program ledger: which
                # compiled program this chunk ran and what the
                # roofline said it should have cost
                s.set(program=step.cost["program"],
                      expected_ms=round(step.cost["expected_ms"], 3),
                      program_bytes=step.cost["bytes"])
            self.span_log.write(s)
        self._flight_event("multi_chunk", k=step.k, emitted=emitted)

    def _decode(self) -> bool:
        if not any(r is not None for r in self.slots):
            # the batch drained while a step was still in flight: read
            # it out (every token discards — its slot finished) so the
            # entry cannot strand
            self._dispatch_end = None
            return self._drain_inflight()
        # deterministic fault injection (tests, chaos drills): only
        # real decode steps count as hits. A fault here leaves the
        # lag queue to _recover, which drops it unread — lagged
        # tokens of a failed batch are never emitted.
        faults.fire("engine_step")
        with self._phase("plan"):
            plan = self._plan_step()
        if plan is None:
            return True  # a precondition drain finished every slot
        return self._execute(plan)

    def _plan_step(self) -> Optional[StepPlan]:
        """Build this iteration's StepPlan (docs/step-plan.md).

        Composition is decided here, once: grammar-masked slots are
        walked ahead through forced-token runs so they ride chunks
        and the pipeline; speculative drafts are built over each
        slot's predicted continuation so verify steps pipeline too;
        a plan is marked `sync` only where a sampled token the NEXT
        plan depends on cannot be known in advance (a grammar
        boundary). Preconditions the planner cannot meet are
        re-established by flushing the lag queue — counted in the
        degradation counter, never silently. Returns None when such
        a flush finished every slot."""
        B = self.engine.max_slots
        # with nothing in flight the committed stream IS the device
        # state: re-anchor every predicted tail
        if not self._inflight:
            for s in range(B):
                self._planned_tail[s] = []
        k_steps = self.steps_per_dispatch
        masked_slots = [s for s, r in enumerate(self.slots)
                        if r is not None and r.masker is not None]
        # -- grammar walk: advance a COPY of each masked slot's
        # automaton over its predicted tail, then through enough
        # future positions for whichever plan shape wins (one mask
        # each, jumping ahead through forced tokens). Rows looked up
        # during this plan are pinned until the next one.
        spec_on = self.spec_tokens > 0 and self._spec_ok
        horizon = max(k_steps, self.spec_tokens + 1 if spec_on else 1,
                      1)
        if self._gcache is not None and masked_slots:
            self._gcache.begin_plan()
        mask_s = 0.0
        walks: Dict[int, tuple] = {}
        if masked_slots:
            with self._phase("mask_apply") as masking:
                legacy_masked = self._walk_maskers(masked_slots,
                                                   horizon, walks)
                if legacy_masked:
                    # plan precondition re-established by draining: a
                    # grammar that cannot be walked ahead is only
                    # consistent with the committed stream, so nothing
                    # may be in flight when its mask is built — one
                    # synchronous masked step, exactly the pre-plan
                    # behavior for copyless maskers, and the one case
                    # that still counts as a masked degradation
                    self._degrade("masked")
                    if self._inflight and self._flush_inflight():
                        return None
                    mask = self._build_mask()
            mask_s = masking.dt
            if legacy_masked:
                return StepPlan("decode", sync=True, mask=mask,
                                mask_s=mask_s)
        # -- speculative drafts over predicted continuations. Masked
        # slots draft THROUGH the grammar when their mask rows are
        # device-resident: forced runs verbatim (the masked target
        # distribution accepts them with certainty) plus
        # grammar-screened n-gram proposals past a free boundary —
        # a proposal leaving the grammar just truncates the draft.
        # Without resident rows they ride verify steps at draft
        # length 0 with their position-0 mask applied densely. A
        # batch where any slot is within the in-flight-rows + k+1
        # headroom of cache capacity falls back for the step (the
        # verify write needs that many rows).
        drafts = dlen = None
        vrows = None
        if spec_on:
            k = self.spec_tokens
            drafts, dlen = self._build_drafts(k)
            if dlen.any() and self._inflight and any(
                    dlen[s] and self._planned_tail[s] is None
                    for s in range(B) if self.slots[s] is not None):
                # draft positional alignment is a plan precondition:
                # a drafting slot whose device-side continuation is
                # unpredicted would draft against a stale stream and
                # the verify would reject nearly everything. Flush,
                # re-anchor, re-draft — and count it: realign
                # flushes are the price of a mispredicted pipeline.
                self._degrade("spec_realign")
                if self._flush_inflight():
                    return None
                drafts, dlen = self._build_drafts(k)
            if masked_slots and self._gcache is not None and all(
                    (not walks[s][0]) or walks[s][3][0] is not None
                    for s in masked_slots if s in walks):
                vrows = {}
                for s in masked_slots:
                    if self.slots[s] is None or not walks[s][0]:
                        continue
                    dm = self._draft_masked(s, walks[s], k)
                    if dm is None:
                        vrows = None
                        break
                    vrows[s] = dm
                if vrows is not None:
                    # only now that EVERY masked slot has resident
                    # rows may masked drafts land: a dense fallback
                    # masks position 0 only, so a half-applied plan
                    # would let rejected drafts emit unmasked tokens
                    for s, (rows_s, toks_s, bonus_free) in \
                            vrows.items():
                        if toks_s:
                            drafts[s, :len(toks_s)] = toks_s
                            dlen[s] = len(toks_s)
            if not dlen.any() or not self._spec_headroom(k):
                drafts = dlen = None  # nobody drafted: plain/chunk
                vrows = None
        if drafts is not None:
            # verify plan: a multi-token-shaped dispatch that
            # pipelines like any chunk; sync only when a masked
            # slot's next free sample lands at or before its bonus
            # position (the token only the device can decide)
            mask = None
            mask_idx = None
            sync = False
            if masked_slots:
                V = self.engine.cfg.vocab_size
                if vrows is not None:
                    mask_idx = np.zeros((B, self.spec_tokens + 1),
                                        dtype=np.int32)
                    for s, (rows_s, _toks, bonus_free) in \
                            vrows.items():
                        mask_idx[s, :len(rows_s)] = rows_s
                        if bonus_free:
                            sync = True
                else:
                    mask = np.ones((B, V), dtype=bool)
                    for s in masked_slots:
                        if s not in walks:
                            continue
                        w_masks, w_forced, w_boundary, _ = walks[s]
                        if w_masks:
                            mask[s] = w_masks[0]
                        if w_boundary and not w_forced:
                            sync = True
            plan = StepPlan("verify", k=self.spec_tokens, sync=sync,
                            mask=mask, mask_idx=mask_idx,
                            drafts=drafts, dlen=dlen,
                            rows=self.spec_tokens + 1, mask_s=mask_s)
            self._predict_verify(plan, walks)
            return plan
        # -- chunk length: the device may not run PAST a grammar
        # boundary (the token sampled there decides every later
        # mask), so the nearest boundary clamps K for the whole
        # batch; a boundary inside the chunk also marks it sync
        n = max(k_steps, 1)
        for s in masked_slots:
            w_masks, w_forced, w_boundary, _ = walks[s]
            if w_boundary:
                n = min(n, len(w_forced) + 1)
        sync = any(walks[s][2] and len(walks[s][1]) < n
                   for s in masked_slots)
        if n > 1:
            budget = self._multi_budget(n)
            stack = None
            stack_idx = None
            if masked_slots:
                V = self.engine.cfg.vocab_size
                if self._gcache is not None and all(
                        all(r is not None for r in walks[s][3][:n])
                        for s in masked_slots):
                    stack_idx = np.zeros((B, n), dtype=np.int32)
                    for s in masked_slots:
                        rows_s = walks[s][3][:n]
                        if rows_s:
                            stack_idx[s, :len(rows_s)] = rows_s
                        budget[s] = min(int(budget[s]),
                                        len(walks[s][0]))
                else:
                    stack = np.ones((B, n, V), dtype=bool)
                    for s in masked_slots:
                        w_masks, w_forced, w_boundary, _ = walks[s]
                        for i, row in enumerate(w_masks[:n]):
                            stack[s, i] = row
                        budget[s] = min(int(budget[s]), len(w_masks))
            plan = StepPlan("chunk", k=n, sync=sync,
                            mask_stack=stack,
                            mask_stack_idx=stack_idx, budget=budget,
                            rows=n, mask_s=mask_s)
        else:
            mask = None
            mask_idx = None
            if masked_slots:
                V = self.engine.cfg.vocab_size
                if self._gcache is not None and all(
                        (not walks[s][0]) or walks[s][3][0] is not None
                        for s in masked_slots):
                    mask_idx = np.zeros(B, dtype=np.int32)
                    for s in masked_slots:
                        if walks[s][0]:
                            mask_idx[s] = walks[s][3][0]
                else:
                    mask = np.ones((B, V), dtype=bool)
                    for s in masked_slots:
                        w_masks, w_forced, w_boundary, _ = walks[s]
                        if w_masks:
                            mask[s] = w_masks[0]
            plan = StepPlan("decode", sync=sync, mask=mask,
                            mask_idx=mask_idx, mask_s=mask_s)
        self._predict_step(plan, walks, n)
        return plan

    def _walk_maskers(self, masked_slots, horizon: int,
                      walks: Dict[int, tuple]) -> bool:
        """Walk every masked slot's automaton ahead into `walks`;
        True when one of them cannot be walked (its masker does not
        copy, or its continuation is unknown)."""
        for s in masked_slots:
            m = self.slots[s].masker
            if (self._planned_tail[s] is None
                    or not callable(getattr(m, "copy", None))):
                return True
            try:
                walks[s] = self._walk_masker(s, horizon)
            except AttributeError:
                # the masker copies but its automaton cannot
                return True
        return False

    def _walk_masker(self, slot: int, horizon: int):
        """Advance a COPY of the slot's grammar ahead of its
        committed stream: feed the predicted in-flight tail, then
        walk up to `horizon` future positions, collecting the
        allowed-token mask at each and jumping through forced tokens
        (positions where the grammar allows exactly one — closing
        braces, fixed keys, separators). Returns (masks, forced,
        boundary, rows): one [V] mask per walked position, the
        forced tokens (always a prefix of the walk), whether the
        walk stopped at a boundary — a position whose token only the
        device can decide — and one device mask-table row index per
        position (None where the state is uncacheable or the table
        is exhausted; plans fall back to dense masks around Nones).
        Raises AttributeError when the underlying automaton cannot
        be copied (the caller falls back to one synchronous masked
        step)."""
        req = self.slots[slot]
        walker = req.masker.copy()
        tail = self._planned_tail[slot] or []
        for tok in tail:
            walker.feed(tok)
        V = self.engine.cfg.vocab_size
        masks: list = []
        forced: list = []
        rows: list = []
        boundary = False
        produced = len(req.output_ids) + len(tail)
        for i in range(horizon):
            if walker.done():
                break
            remaining = req.max_new_tokens - produced - i
            if remaining <= 0:
                break
            closing = remaining <= walker.closing_distance() + 4
            row, ridx = self._lookup_mask(walker, V, closing,
                                          remaining)
            masks.append(row)
            rows.append(ridx)
            allowed = np.flatnonzero(row)
            if allowed.size == 1:
                tok = int(allowed[0])
                forced.append(tok)
                walker.feed(tok)
            else:
                boundary = True
                break
        return masks, forced, boundary, rows

    def _lookup_mask(self, walker, V: int, closing: bool,
                     remaining: Optional[int]):
        """One walked position's allowed-token mask, served through
        the device-resident row cache when the automaton state is
        cacheable. A cached entry holds the state's BUDGET-FREE mask
        plus its recorded slack — the worst closing-distance growth
        any accepted token causes — and substitutes for the budgeted
        dense mask exactly when `remaining - 1 >= closing_distance +
        slack` (past that horizon the budget provably bans nothing).
        Everything else — closing masks, tight budgets, automatons
        without a signature, a table exhausted by pinned rows —
        computes the dense mask host-side. Returns (bits, device row
        index or None)."""
        gc = self._gcache
        if gc is not None and not closing:
            key_fn = getattr(walker, "cache_key", None)
            key = key_fn() if key_fn is not None else None
            if key is not None:
                ent = gc.get(key)
                if ent is None:
                    # compile + install the budget-free mask; its
                    # slack is only known after compiling, so even a
                    # position whose budget ends up too tight to use
                    # it installs the entry for future positions
                    bits, slack = walker.mask_with_slack(V)
                    ent = gc.insert(key, bits, slack)
                    self._g_gmask_resident.set(len(gc))
                if ent is not None:
                    bits, ridx, slack = ent
                    if remaining is None or remaining - 1 \
                            >= walker.closing_distance() + slack:
                        return bits, ridx
        return walker.mask(V, closing=closing,
                           remaining=remaining), None

    def _draft_masked(self, slot: int, walk, k: int):
        """Spec through the grammar (docs/structured-outputs.md):
        build a masked slot's draft from its walk. The forced run
        drafts verbatim — the masked target distribution puts
        probability 1 on each forced token at any temperature, so
        those drafts are accepted with certainty. Past a free
        boundary the n-gram drafter proposes and every proposal is
        filtered through the automaton walk: a proposal the grammar
        rejects truncates the draft (a rejected draft, never an
        invalid emission). Returns (rows, draft tokens, bonus_free):
        device mask rows for positions 0..len(drafts) — the verify
        program masks every position so rejection resampling stays
        in-grammar — and whether the position after the draft is a
        free sample (which makes the plan sync). None when position
        0 itself has no resident row."""
        w_masks, w_forced, w_boundary, w_rows = walk
        npos = len(w_masks)
        if npos == 0 or w_rows[0] is None:
            return None
        # longest forced prefix whose positions 0..d all have rows
        d = min(k, len(w_forced), npos - 1)
        while d > 0 and any(w_rows[j] is None for j in range(d + 1)):
            d -= 1
        rows = [w_rows[j] for j in range(d + 1)]
        toks = [int(t) for t in w_forced[:d]]
        bonus_free = d >= len(w_forced) and w_boundary
        if bonus_free and d < k:
            req = self.slots[slot]
            walker = req.masker.copy()
            tail = self._planned_tail[slot] or []
            for t in tail:
                walker.feed(t)
            for t in toks:
                walker.feed(t)
            produced = len(req.output_ids) + len(tail)
            stream = (list(req.prompt_ids)
                      + list(req.output_ids[int(self._base_out[slot]):])
                      + tail + toks)
            V = self.engine.cfg.vocab_size
            state = {"bits": w_masks[d], "d": d}

            def accept(t: int) -> bool:
                if not state["bits"][t]:
                    return False  # proposal exits the grammar
                walker.feed(t)
                rem = (req.max_new_tokens - produced
                       - (state["d"] + 1))
                if rem <= 0 or walker.done():
                    return False
                closing = rem <= walker.closing_distance() + 4
                nbits, nrow = self._lookup_mask(walker, V, closing,
                                                rem)
                if nrow is None:
                    return False  # next position not resident
                toks.append(t)
                rows.append(nrow)
                state["bits"] = nbits
                state["d"] += 1
                return True

            spec_drafter.grammar_prefix(
                spec_drafter.propose(stream, k - d), accept)
        return rows, toks, bonus_free

    def _predict_step(self, plan: StepPlan, walks: Dict[int, tuple],
                      n: int) -> None:
        """Extend each slot's predicted tail with what this
        decode/chunk plan will deterministically emit: forced grammar
        tokens are exact; a freely sampled position makes the slot's
        continuation unknown until the step drains. Sync plans drain
        immediately, so their tails re-anchor at the next plan."""
        if plan.sync:
            return
        for s, r in enumerate(self.slots):
            if r is None:
                continue
            tail = self._planned_tail[s]
            if tail is None:
                continue
            if s in walks:
                self._planned_tail[s] = tail + walks[s][1][:n]
            else:
                self._planned_tail[s] = None

    def _predict_verify(self, plan: StepPlan,
                        walks: Dict[int, tuple]) -> None:
        """Predict each slot's continuation through a verify plan:
        the optimistic outcome is every draft accepted plus the
        drafter's own guess at the bonus token. Wrong predictions
        never emit a wrong byte — the drain reconciles against what
        the device actually produced and the next plan flushes if it
        needs an alignment the prediction lost."""
        if plan.sync:
            return
        for s, r in enumerate(self.slots):
            if r is None:
                continue
            tail = self._planned_tail[s]
            if tail is None:
                continue
            if s in walks:
                # a masked slot advances its forced-run draft plus
                # the bonus: drafted forced tokens are accepted with
                # certainty (the masked target distribution forces
                # them) and a non-sync plan's bonus position is
                # forced too — free-bonus plans are sync and never
                # reach here
                d = int(plan.dlen[s]) if plan.dlen is not None else 0
                self._planned_tail[s] = tail + walks[s][1][:d + 1]
                continue
            d = int(plan.dlen[s])
            if d == 0:
                # a free position-0 sample: unknown until drained
                self._planned_tail[s] = None
                continue
            drafted = [int(t) for t in plan.drafts[s, :d]]
            stream = (list(r.prompt_ids)
                      + list(r.output_ids[int(self._base_out[s]):])
                      + tail + drafted)
            bonus = spec_drafter.propose(stream, 1)
            if bonus.size:
                self._planned_tail[s] = (tail + drafted
                                         + [int(bonus[0])])
            else:
                self._planned_tail[s] = None

    def _execute(self, plan: StepPlan) -> bool:
        """Dispatch one StepPlan, feed the lag queue, drain. Every
        plan takes the same path: one compiled-program call keyed on
        plan.kind, one lag-queue append, one windowed drain — the
        generation-counter discard rules do the rest. The executor
        never decides composition; it only honors plan.sync by
        running this step's window at depth 0."""
        sampling = self._sampling()
        n_steps = plan.k if plan.kind == "chunk" else 1
        self._step_seq += 1
        # multi-step chunks attribute their whole on-device loop to
        # `device_loop` (K tokens per observation), not `dispatch`
        with self._phase("device_loop" if n_steps > 1 else "dispatch",
                         step=self._step_seq, kind=plan.kind) as sent:
            t0 = sent.t0
            gap_s = None
            if self._dispatch_end is not None:
                gap_s = t0 - self._dispatch_end
                self._h_step_gap.observe(gap_s)
            toks = self._dispatch(plan, sampling, t0)
        self._dispatch_end = sent.t1
        # per-STEP time of the DISPATCH — what the jitted call took to
        # return, an enqueue under pipelining — feeds the queue-wait
        # estimator as it always has (a K-chunk amortizes over K
        # steps); the step histogram reads completions (_step_done)
        dt_step = sent.dt / n_steps
        # a program's FIRST dispatch includes its compilation — tens
        # of seconds on the chip, not a service time. One such sample
        # held the queue-wait estimate over the admission cap and an
        # idle, freshly started server answered 429 (first chip run)
        led = getattr(self.engine, "ledger", None)
        cost = led.last_dispatch() if led is not None else None
        if cost is None or cost["dispatches"] > 1:
            self._ewma_step_s = dt_step if self._ewma_step_s is None \
                else 0.9 * self._ewma_step_s + 0.1 * dt_step
        self._inc("decode_steps_total", n_steps)
        self._c_sample_tier[self._sample_tier].inc(n_steps)
        if plan.kind == "verify":
            self._inc("spec_steps_total")
            self._inc("spec_proposed_tokens_total",
                      int(plan.dlen.sum()))
        self._inflight.append(
            (toks, list(self.slots), list(self._slot_gen),
             _Dispatched(self._step_seq, t0, n_steps, gap_s,
                         plan.mask_s, cost)))
        depth = 0 if plan.sync else self.pipeline_depth
        # emit steps older than the pipeline window — with the next
        # step now dispatched, reading them costs no dispatch overlap
        self._drain_inflight(keep=max(depth, 1))
        # paged-KV pool pressure may have evicted sequences BEFORE the
        # step above ran — the token it samples for them is garbage
        # (their new KV row went to the trash block), so requeue
        # without emitting: the generation bump makes the lag queue
        # discard their pending token, and generated-so-far tokens
        # ride along as prompt for the re-prefill (vLLM recompute
        # preemption). Their PREVIOUS step's token was valid and was
        # emitted by the drain above, before output_ids was folded in.
        take = getattr(self.engine, "take_preempted", None)
        for slot in (take() if take is not None else ()):
            req = self.slots[slot]
            if req is None:
                continue
            self.slots[slot] = None
            self._slot_changed(slot)
            self._temp[slot] = 0.0
            # fold only the tokens generated SINCE this admission:
            # outputs[:base_out] were folded by a previous preemption
            # and already sit inside prompt_ids — re-adding them would
            # corrupt the resume prompt the second time a request is
            # preempted
            req.prompt_ids = list(req.prompt_ids) + list(
                req.output_ids[int(self._base_out[slot]):])
            self._flush_decode_chunk(req, final=True)
            self._flight_event("preempt_fold", slot=slot,
                               request=req.id,
                               folded=len(req.output_ids)
                               - int(self._base_out[slot]))
            self._requeue.appendleft(req)
            self._inc("preemptions_total")
            self._c_class_preempt[self._class_of(req)].inc()
            if self.overlap:
                self._free_slots.release()
        if depth == 0:
            self._drain_inflight()
        return True

    def _dispatch(self, plan: StepPlan, sampling, t0: float):
        """The one compiled-program call of a plan, keyed on
        plan.kind; returns the lag-queue payload."""
        if plan.kind == "verify":
            kw = {}
            if getattr(self.engine, "kv_block", 0):
                # paged pre-allocation must cover this plan AND every
                # plan still in flight (their commits have not
                # advanced the host length mirror yet)
                kw["lookahead_rows"] = self._inflight_rows() + plan.rows
            if plan.mask_idx is not None:
                kw["mask_idx"] = plan.mask_idx
            elif plan.mask is not None:
                kw["mask"] = plan.mask
            self.state, out, acc = self.engine.verify(
                self.state, plan.drafts, plan.dlen, *sampling, **kw)
            toks = _SpecStep(out, acc, plan.dlen, t0)
        elif plan.kind == "chunk":
            kw = {}
            if plan.mask_stack_idx is not None:
                kw["mask_idx"] = plan.mask_stack_idx
            elif plan.mask_stack is not None:
                kw["mask"] = plan.mask_stack
            self.state, out, adv = self.engine.decode_multi(
                self.state, *sampling, steps=plan.k,
                budget=plan.budget, stop_ids=self._stop_table(),
                lookahead_rows=self._inflight_rows() + plan.rows,
                **kw)
            led = getattr(self.engine, "ledger", None)
            toks = _MultiStep(
                out, adv, plan.k, t0,
                cost=led.last_dispatch() if led is not None else None)
        elif plan.mask_idx is not None:
            self.state, toks = self.engine.decode(
                self.state, *sampling, mask_idx=plan.mask_idx)
        elif plan.mask is not None:
            self.state, toks = self.engine.decode(
                self.state, *sampling, mask=plan.mask)
        else:  # engine wrappers/fakes need no mask kwarg in their API
            self.state, toks = self.engine.decode(
                self.state, *sampling)
        return toks

    def _step_done(self, sent: _Dispatched, t_fetched: float) -> None:
        """The host just learned that step `sent` ended. Its step
        time is a COMPLETION time: from the later of its own dispatch
        and the previous step's fetch to this fetch, per decode
        iteration for a chunk. Under load that is the device's step
        plus whatever the device ran in between (a prefill); on an
        idle server it is dispatch to result. Feeds
        ome_engine_decode_step_seconds and the slow-step outlier
        detector (docs/perf-attribution.md)."""
        dt_step = (t_fetched - max(self._last_fetched, sent.t_dispatch)
                   ) / sent.k_steps
        self._last_fetched = t_fetched
        self._h_decode_step.observe(dt_step)
        entry = sent.entry
        # slow-step detector: compare against the rolling median of
        # recent per-step times, not a fixed threshold — "slow" means
        # slow relative to THIS batch shape on THIS device. Warm-up
        # (first few steps, compiles) is excluded by requiring a
        # half-full window before judging.
        win = self._step_window
        if len(win) >= win.maxlen // 2:
            med = sorted(win)[len(win) // 2]
            if med > 0 and dt_step > self.slow_step_factor * med:
                self._c_slow_steps.inc()
                fields = dict(
                    step_ms=round(dt_step * 1e3, 3),
                    median_ms=round(med * 1e3, 3),
                    ratio=round(dt_step / med, 2),
                    k_steps=sent.k_steps,
                    mask_ms=round(sent.mask_s * 1e3, 3),
                    gap_ms=round((sent.gap_s or 0.0) * 1e3, 3))
                if entry is not None:
                    fields["program"] = entry["program"]
                    fields["expected_ms"] = round(
                        entry["expected_ms"], 3)
                self._flight_event("slow_step", **fields)
        win.append(dt_step)

    def _spec_headroom(self, k: int) -> bool:
        """True when every active slot has cache headroom for the k+1
        speculative KV rows a verify step writes — plus the exact
        rows every plan still in flight may commit. A near-capacity
        slot makes the whole step fall back to plain decode (it
        finishes with reason=length within a step or two anyway);
        without this, a clamped multi-row cache write would corrupt
        earlier rows."""
        need = self._inflight_rows() + (k + 1)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            used = (int(self._true_len[slot]) + len(req.output_ids)
                    - int(self._base_out[slot]))
            if used + need > self.engine.max_seq:
                return False
        return True

    def _build_drafts(self, k: int):
        """Per-slot n-gram drafts from each request's host-visible
        committed stream (prompt + emitted output) EXTENDED by its
        predicted in-flight tail, so drafts align with where the
        device will be when the verify runs — the precondition that
        lets verify steps pipeline. A slot whose tail is unknown
        drafts from the committed stream alone (the planner flushes
        before dispatching if that draft would be misaligned).
        Masked slots never draft: their continuation belongs to the
        grammar walk, not the n-gram cache. Returns ([B, k] int32
        drafts, [B] int32 draft lengths); a slot with no match
        drafts 0 tokens and degenerates to plain decode inside the
        verify."""
        B = self.engine.max_slots
        drafts = np.zeros((B, k), np.int32)
        dlen = np.zeros((B,), np.int32)
        for slot, req in enumerate(self.slots):
            if req is None or req.masker is not None:
                continue
            # outputs[:base_out] of a resumed request are already
            # folded into prompt_ids — slicing keeps the drafter's
            # view of the stream free of duplicated spans
            d = spec_drafter.propose(
                list(req.prompt_ids)
                + list(req.output_ids[int(self._base_out[slot]):])
                + (self._planned_tail[slot] or []), k)
            if d.size:
                drafts[slot, :d.size] = d
                dlen[slot] = d.size
        return drafts, dlen

    def _fits_pool(self, req: Request) -> bool:
        """Paged KV only: a request whose worst-case footprint exceeds
        the whole pool can never finish — preempting it would livelock
        (it is always its own cheapest victim), so reject upfront.
        A preempted request's generated tokens already moved into
        prompt_ids, so the remaining-output term shrinks by what was
        produced (no double count)."""
        kvb = getattr(self.engine, "kv_block", 0)
        if not kvb:
            return True
        usable = (self.engine.kv_blocks - 1) * kvb
        remaining = max(req.max_new_tokens - len(req.output_ids), 0)
        worst = min(min(len(req.prompt_ids), self.engine.max_seq)
                    + remaining + 1, self.engine.max_seq)
        return worst <= usable

    def _pool_ready(self, req: Request) -> bool:
        """Cheap pre-prefill check: enough free blocks for this
        request's PROMPT — avoids re-running a full prefill forward on
        every retry while the pool is saturated (the insert would just
        bounce with KVPoolExhausted again)."""
        kvb = getattr(self.engine, "kv_block", 0)
        if not kvb:
            return True
        need = self.engine.blocks_needed(
            min(len(req.prompt_ids), self.engine.max_seq))
        stats = self.engine.kv_pool_stats
        return stats["kv_blocks_free"] >= need

    def _peer_prefill(self, req: Request, peer: str):
        """Try fetching this prompt's prefix KV from the peer replica
        the router's prefix directory named (X-OME-Prefix-Peer) —
        engine.prefill-shaped result or None, in which case the
        caller computes the prefill locally (the recompute fallback).
        A successful fetch seeds the LOCAL prefix cache so the next
        same-prefix request hits on device without any peer."""
        if self._peer_client is None:
            from .peering import PrefixPeerClient
            self._peer_client = PrefixPeerClient(
                registry=self.registry)
        res = self._peer_client.fetch(
            peer, req.prompt_ids, temperature=req.temperature,
            top_k=req.top_k, top_p=req.top_p, deadline=req.deadline,
            priority=req.priority, trace=req.trace)
        if res is None:
            if self.flight is not None:
                self.flight.record("prefix_peer_fallback", peer=peer,
                                   request_id=req.id)
            return None
        token, (k, v), true_len, bucket = res
        import jax.numpy as jnp
        k = jnp.asarray(k)
        v = jnp.asarray(v)
        pc = getattr(self.engine, "prefix_cache", None)
        put = getattr(pc, "put", None)
        if callable(put):
            put(list(req.prompt_ids)[-true_len:], k, v, true_len,
                bucket)
        if self.flight is not None:
            self.flight.record("prefix_peer_fetch", peer=peer,
                               request_id=req.id,
                               prefix_len=true_len)
        return token, (k, v), true_len, bucket

    def _timed_prefill(self, req: Request, span: Optional[Span]):
        """`_prefill_req` under an `admit.prefill` profiler span
        (request id, prompt length and bucket: no token list). Its
        host-observed duration feeds ome_engine_prefill_seconds and,
        for the request's first prefill, the request log's
        `prefill_s`."""
        with jax.profiler.TraceAnnotation(
                ADMIT_PREFILL, request=req.id,
                prompt_tokens=len(req.prompt_ids)) as ann:
            t0 = time.monotonic()
            out = self._prefill_req(req, span=span)
            dt = time.monotonic() - t0
            ann.set_metadata(bucket=out[3])
        self._h_prefill.observe(dt)
        if req.prefill_s is None:
            req.prefill_s = dt
        return out

    def _prefill_req(self, req: Request, span: Optional[Span] = None):
        """Engine prefill for one request; constrained requests pass
        the grammar mask for their FIRST sampled token."""
        # cross-replica prefix reuse: fetch the prefix KV from the
        # directory-named peer when this request is eligible (base
        # model, unconstrained, non-PD engine); any failure falls
        # through to the ordinary local prefill below
        peer = getattr(req, "prefix_peer", None)
        if (peer and req.adapter is None and req.masker is None
                and not getattr(self.engine, "pd_request_context",
                                False)):
            fetched = self._peer_prefill(req, peer)
            if fetched is not None:
                return fetched
        kw = {}
        if req.adapter is not None:
            kw["adapter"] = req.adapter
        if req.masker is not None:
            kw["first_mask"] = req.masker.mask(
                self.engine.cfg.vocab_size,
                remaining=req.max_new_tokens)
        if getattr(self.engine, "pd_request_context", False):
            # PD decode nodes cap each remote-fetch attempt at the
            # request's own deadline and stamp its traceparent on the
            # wire (engine/pd.py); the priority class rides along so
            # prefill-pool logs attribute work to the right tenant
            kw["deadline"] = req.deadline
            kw["priority"] = req.priority
            trace = req.trace
            if span is not None:
                # hand PD the PREFILL span as the context, so its
                # per-peer attempt spans (and the peer's own engine
                # span, via the forwarded header) nest under the
                # prefill phase rather than the whole request
                trace = SpanContext(trace_id=span.trace_id,
                                    span_id=span.span_id)
            kw["trace"] = trace
        return self.engine.prefill(req.prompt_ids, req.temperature,
                                   req.top_k, req.top_p, **kw)

    def _build_mask(self):
        """[B, V] allowed-token mask when any slot is constrained
        (structured outputs); None otherwise so the maskless compiled
        program keeps running."""
        if not any(r is not None and r.masker is not None
                   for r in self.slots):
            return None
        V = self.engine.cfg.vocab_size
        mask = np.ones((self.engine.max_slots, V), dtype=bool)
        for slot, r in enumerate(self.slots):
            if r is not None and r.masker is not None:
                remaining = r.max_new_tokens - len(r.output_ids)
                # switch to close-out masks before the budget can
                # strand an open string/container (valid JSON even at
                # finish_reason=length); `remaining` additionally bans
                # tokens whose completion cost overshoots the budget
                closing = remaining <= r.masker.closing_distance() + 4
                mask[slot] = r.masker.mask(V, closing=closing,
                                           remaining=remaining)
        return mask

    def _maybe_finish(self, slot: int, tok: int):
        req = self.slots[slot]
        if req.masker is not None:
            req.masker.feed(tok)
        if req.masker is not None and req.masker.done():
            reason = "stop"  # the grammar accepted a complete value
        elif tok in req.stop_ids:
            reason = "stop"
        elif req.expired():
            # deadline passed mid-decode: partial output is returned
            # with the honest finish reason
            reason = "timeout"
        elif len(req.output_ids) >= req.max_new_tokens:
            reason = "length"
        elif (int(self._true_len[slot])
              + len(req.output_ids) - int(self._base_out[slot])
              >= self.engine.max_seq):
            # cache capacity: the slot was admitted with the (possibly
            # truncated) true_len rows, +1 row per token generated
            # SINCE admission (a resumed request's earlier outputs are
            # already inside true_len)
            reason = "length"
        else:
            return
        self.slots[slot] = None
        self._slot_changed(slot)
        self._temp[slot] = 0.0
        free = getattr(self.engine, "free_slot", None)
        if free is not None:  # paged engines reclaim the KV blocks
            free(slot)
        if reason == "timeout":
            self._inc("timeouts_total")
        n = max(len(req.output_ids), 1)
        self._ewma_req_steps = float(n) if self._ewma_req_steps is None \
            else 0.8 * self._ewma_req_steps + 0.2 * n
        req.finish(reason)
        if self.overlap:
            self._free_slots.release()

    # -- crash recovery ------------------------------------------------

    def _fail_batch(self, reason: str):
        """Fail the in-flight batch ONLY: occupied slots are freed and
        their requests finished; queued work (pending, _requeue, and
        prefilled-awaiting-insert _ready items, whose KV is
        independent of the decode state) survives the restart."""
        # drop dispatched-but-unread steps WITHOUT fetching: reading
        # tokens of a faulted step would re-raise (or deadlock on) the
        # failed computation, and the failed batch's lagged tokens
        # must not be emitted anyway
        self._inflight.clear()
        self._dispatch_end = None
        for slot, r in enumerate(self.slots):
            if r is None:
                continue
            self.slots[slot] = None
            self._slot_changed(slot)
            self._temp[slot] = 0.0
            free = getattr(self.engine, "free_slot", None)
            if free is not None:
                try:
                    free(slot)
                except Exception:  # noqa: BLE001 — allocator state is
                    pass  # rebuilt wholesale below anyway
            r.finish(reason)
            if self.overlap:
                self._free_slots.release()

    def _go_dead(self) -> bool:
        self._flight_event("dead", restarts=self._restarts)
        self._flight_autodump("dead")
        with self._lock:
            self._status = "dead"
        # `engine_fault` (vs `shutdown`): the replica crashed out from
        # under the work — the router may retry it elsewhere, and a
        # journal keeps these entries live for the replacement process
        # to resume (status is already `dead` when _fail_all finishes
        # them, which is what _request_finished keys on)
        self._fail_all("engine_fault")
        return False

    def _recover(self, err: BaseException) -> bool:
        """Engine-step fault path: fail the in-flight batch, rebuild
        the decode state after an exponential-backoff pause, resume
        admitting. Returns False when the restart budget is exhausted
        (scheduler dead) or the state rebuild itself fails."""
        import logging
        log = logging.getLogger("ome.engine")
        self._inc("engine_faults_total")
        # narrate the fault into the ring, then persist the ring: the
        # dump carries every event that LED INTO this fault even if
        # the process never recovers far enough to serve /debug/events
        self._flight_event("crash_recovery",
                           restart=self._restarts + 1,
                           error=str(err)[:160])
        self._flight_autodump("engine_fault")
        with self._lock:
            self._status = "degraded"
        self._restarts += 1
        if self._restarts > self.max_restarts:
            # budget exhausted: go dead BEFORE failing the batch, so
            # the in-flight requests finish under dead status (their
            # journal entries stay live for the replacement process —
            # this crash kills the pod, not just the batch)
            log.error("engine fault (%s); %d consecutive restarts "
                      "exhausted the budget — scheduler dead", err,
                      self._restarts - 1)
            return self._go_dead()
        self._fail_batch("engine_fault")
        delay = min(self.restart_backoff * (2 ** (self._restarts - 1)),
                    5.0)
        log.warning("engine fault (%s); restart %d/%d in %.3fs", err,
                    self._restarts, self.max_restarts, delay)
        if self._stop.wait(delay):
            return True  # shutting down; stop() drains the queues
        try:
            self.state = self.engine.new_state()
        except Exception:  # noqa: BLE001
            log.exception("decode-state rebuild failed; scheduler dead")
            return self._go_dead()
        self._fault_event.clear()
        with self._lock:
            self._status = "ok"
            self._inc_locked("restarts_total")
        return True

    def _run(self):
        while not self._stop.is_set():
            try:
                if self._status == "dead":
                    # no recovery left; fail waiters fast (this is a
                    # crash, not a drain — hence engine_fault)
                    self._fail_all("engine_fault")
                    return
                if self._fault_event.is_set():
                    raise RuntimeError(
                        "admission-thread engine fault")
                did = self.step()
                if did and self._status == "ok":
                    self._restarts = 0  # a good step resets the budget
                else:
                    if not did:
                        time.sleep(0.001)
            except Exception as e:  # noqa: BLE001 — a dead loop must
                # not leave waiters hanging or /health lying
                import logging
                logging.getLogger("ome.engine").exception(
                    "scheduler step failed; failing in-flight batch")
                if not self._recover(e):
                    return
