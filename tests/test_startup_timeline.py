"""Start-up and compilation on the program's own clock (PR 39):
`startup.*` phases from process creation to ready (telemetry/startup.py,
engine/serve.py: main), compile and cache-load seconds by stage and by
program (perf/ledger.py), and where each is published: /metrics,
/health, /debug/programs, the flight ring, the span log.

One tiny `--random-weights` server is started for the module on a
fresh compile cache (a second process on the same cache shows the
hits); every wait on a port carries its own time limit."""

import json
import logging
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from ome_tpu.perf import ledger as ledger_mod
from ome_tpu.perf.ledger import OTHER_PROGRAM, ProgramLedger
from ome_tpu.telemetry import Registry, SpanLog
from ome_tpu.telemetry import export as trace_export
from ome_tpu.telemetry import startup as startup_mod
from ome_tpu.telemetry.flight import FlightRecorder
from ome_tpu.telemetry.scopes import (COMPILE_OUTCOMES, COMPILE_STAGES,
                                      COMPILE_WHEN, PROGRAM_COMPILED,
                                      STARTUP_PHASES)
from ome_tpu.telemetry.startup import StartupTimeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
START_LIMIT_S = 180.0

_SAMPLE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})? ([0-9.eE+-]+)$")
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url, body=None, timeout=60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
    except urllib.error.HTTPError as e:
        with e:
            raw = e.read()
    try:
        return json.loads(raw)
    except ValueError:
        return raw.decode()


def _family(url, name):
    """[(labels, value)] of one family of a /metrics scrape."""
    out = []
    for line in _http(url + "/metrics").splitlines():
        m = _SAMPLE.match(line)
        if m and m.group(1) == name:
            out.append((dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def _compile_seconds(url) -> float:
    return sum(v for _, v in _family(url, "ome_engine_compile_seconds_total"))


class _Child:
    """A child process that is killed at the end, and whose waits on
    its port end at a deadline."""

    def __init__(self, argv, env, log_path, port):
        self.url = f"http://127.0.0.1:{port}"
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(argv, env=env, cwd=REPO,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def wait_for(self, ready, limit_s=START_LIMIT_S):
        deadline = time.monotonic() + limit_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                break
            try:
                got = ready()
                if got:
                    return got
            except (urllib.error.URLError, OSError, ValueError):
                pass
            time.sleep(0.2)
        with open(self.log_path, errors="replace") as f:
            tail = f.read()[-3000:]
        raise AssertionError(
            f"child not ready (rc={self.proc.poll()}):\n{tail}")

    def stop(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(30)
        self._log.close()


def _serve(tmp, cache_dir, name, *extra):
    model = tmp / "model"
    model.mkdir(exist_ok=True)
    (model / "config.json").write_text(json.dumps({
        "architectures": ["LlamaForCausalLM"], "vocab_size": 300,
        "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 8, "intermediate_size": 64,
        "max_position_embeddings": 64, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False}))
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)     # one CPU device is enough
    child = _Child(
        [sys.executable, "-m", "ome_tpu.engine.serve", "--model-dir",
         str(model), "--random-weights", "--max-slots", "2", "--max-seq",
         "64", "--host", "127.0.0.1", "--port", str(port),
         "--debug-endpoints", "--prefix-cache-mb", "0", *extra],
        env, str(tmp / f"{name}.log"), port)

    def ready():
        health = _http(child.url + "/health", timeout=2.0)
        return health if health["startup"]["ready_s"] is not None else None

    try:
        child.health = child.wait_for(ready)
    except BaseException:
        child.stop()
        raise
    return child


def _complete(url):
    out = _http(url + "/v1/completions",
                {"prompt": "hello", "max_tokens": 4, "temperature": 0.0},
                timeout=START_LIMIT_S)
    assert out["usage"]["completion_tokens"] == 4, out
    return out


@pytest.fixture(scope="module")
def cold(tmp_path_factory):
    """The first process on an empty compile cache, `--ledger-mode
    full` (which the CPU's `auto` is not), with a span log."""
    tmp = tmp_path_factory.mktemp("startup")
    child = _serve(tmp, tmp / "cache", "cold", "--ledger-mode", "full",
                   "--span-log", str(tmp / "spans.jsonl"))
    child.tmp = tmp
    yield child
    child.stop()


# -- the phases (one server, in file order) ----------------------------


def test_phases_tile_creation_to_ready_in_the_fixed_order(cold):
    block = cold.health["startup"]
    phases = block["phases"]
    assert [p["name"] for p in phases] == list(STARTUP_PHASES)
    assert phases[0]["start_s"] == 0.0
    for before, after in zip(phases, phases[1:]):
        assert after["start_s"] == before["end_s"]      # no hole, no overlap
        assert after["end_s"] >= after["start_s"]
    assert block["ready_s"] == phases[-1]["end_s"]
    gauges = {labels["phase"]: v for labels, v in _family(
        cold.url, "ome_engine_startup_phase_seconds")}
    assert list(gauges) == list(STARTUP_PHASES)
    total = _family(cold.url, "ome_engine_startup_seconds")[0][1]
    assert sum(gauges.values()) == pytest.approx(total, rel=0.01)
    assert total == pytest.approx(block["ready_s"], abs=1e-3)
    # Python and the imports are seconds, not the rounding of a tick
    assert gauges["interpreter"] > 0.2
    with open(cold.log_path, errors="replace") as f:
        assert "after process creation: interpreter" in f.read()


def test_health_marks_the_first_admitted_request(cold):
    assert cold.health["startup"]["first_request_s"] is None
    assert _compile_seconds(cold.url) > 0        # the random init
    _complete(cold.url)
    block = _http(cold.url + "/health")["startup"]
    first = block["first_request_s"]
    assert first is not None and first > block["ready_s"]
    _complete(cold.url)
    assert _http(cold.url + "/health")["startup"][
        "first_request_s"] == first


def test_a_programs_first_call_is_booked_on_its_entry_and_no_later_one(
        cold):
    body = _http(cold.url + "/debug/programs")
    by_name = {p["name"]: p for p in body["programs"]}
    assert {"prefill", "decode"} <= set(by_name)
    for p in by_name.values():
        took = p["compile_s"]
        assert list(took) == list(COMPILE_STAGES)
        # its one trace, lowering and compile, reported from inside
        # the ledger's `_build_entry` ...
        assert took["trace"] > 0 and took["lower"] > 0
        assert took["backend_compile"] > 0 and took["cache_load"] == 0
        # ... and what the ledger itself added on top, under `full`
        assert took["introspect"] > 0
        assert p["cache"] == "miss"
    serving = {labels["stage"]: v for labels, v in _family(
        cold.url, "ome_engine_compile_seconds_total")
        if labels["when"] == "serving"}
    assert list(serving) == list(COMPILE_STAGES)
    for stage in COMPILE_STAGES:
        booked = sum(p["compile_s"][stage] for p in body["programs"])
        assert booked <= serving[stage] + 1e-6
    # the same request again: every program is compiled, nothing moves
    before = _compile_seconds(cold.url)
    events = _family(cold.url, "ome_engine_compile_events_total")
    _complete(cold.url)
    assert _compile_seconds(cold.url) == before
    assert _family(cold.url, "ome_engine_compile_events_total") == events
    again = {p["name"]: p for p in _http(
        cold.url + "/debug/programs")["programs"]}
    for name, p in by_name.items():
        assert again[name]["compile_s"] == p["compile_s"]
        assert again[name]["dispatches"] > p["dispatches"]
    listeners = body["compile"]["listeners"]
    assert listeners["calls"] > 0 and listeners["seconds"] < 0.5


def test_a_compile_after_ready_leaves_a_flight_event(cold):
    events = [e for e in _http(cold.url + "/debug/events?n=2048")["events"]
              if e["event"] == PROGRAM_COMPILED]
    decode = [e for e in events if e["program"] == "decode"]
    assert {e["stage"] for e in decode} == set(COMPILE_STAGES) - {
        "cache_load"}
    for e in decode:
        assert e["seconds"] >= 0 and e["cache"] in (None, "miss")
    assert [e["cache"] for e in decode
            if e["stage"] == "backend_compile"] == ["miss"]
    # after ready: later than the listen phase's end, on one clock
    assert all(e["stage"] in COMPILE_STAGES for e in events)


def test_span_log_holds_the_start_as_one_span_with_six_children(cold):
    spans = trace_export.load_spans([str(cold.tmp / "spans.jsonl")])
    root = [s for s in spans if s["name"] == "engine.startup"]
    assert len(root) == 1
    kids = [s for s in spans if s["parent_id"] == root[0]["span_id"]]
    assert [s["name"] for s in kids] == [
        "engine.startup." + p for p in STARTUP_PHASES]
    assert sum(s["dur_s"] for s in kids) == pytest.approx(
        root[0]["dur_s"], rel=0.01)
    for a, b in zip(kids, kids[1:]):
        assert b["t_start"] == pytest.approx(a["t_start"] + a["dur_s"],
                                             abs=1e-3)
    doc = trace_export.build_trace(spans)
    drawn = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "engine.startup" in drawn and "engine.request" in drawn
    assert "engine.startup.tokenizer" in drawn


def test_first_process_counts_misses_and_a_second_one_hits(cold):
    events = {labels["outcome"]: v for labels, v in _family(
        cold.url, "ome_engine_compile_events_total")}
    assert list(events) == list(COMPILE_OUTCOMES)
    assert events["cache_miss"] > 0 and events["cache_hit"] == 0
    warm = _serve(cold.tmp, cold.tmp / "cache", "warm",
                  "--ledger-mode", "full")
    try:
        _complete(warm.url)
        events = {labels["outcome"]: v for labels, v in _family(
            warm.url, "ome_engine_compile_events_total")}
        assert events["cache_hit"] > 0 and events["cache_miss"] == 0
        body = _http(warm.url + "/debug/programs")
        for p in body["programs"]:
            assert p["cache"] == "hit" and p["compile_s"]["cache_load"] > 0
        other = body["compile"]["other"]
        assert other["program"] == OTHER_PROGRAM and other["cache"] == "hit"
        assert other["compile_s"]["cache_load"] > 0   # the random init
    finally:
        warm.stop()


def test_router_publishes_its_own_start(tmp_path):
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=REPO)
    child = _Child(
        [sys.executable, "-m", "ome_tpu.router", "--backend",
         f"http://127.0.0.1:{_free_port()}", "--port", str(port),
         "--bind", "127.0.0.1"], env, str(tmp_path / "router.log"), port)
    try:
        child.wait_for(lambda: _family(
            child.url, "ome_router_startup_seconds"), 60.0)
        phases = {labels["phase"]: v for labels, v in _family(
            child.url, "ome_router_startup_phase_seconds")}
        assert list(phases) == ["interpreter", "listen"]
        total = _family(child.url, "ome_router_startup_seconds")[0][1]
        assert sum(phases.values()) == pytest.approx(total, rel=0.01)
    finally:
        child.stop()


# -- the timeline alone -------------------------------------------------


def test_a_phase_closed_inside_a_block_takes_its_part_out_of_it():
    tl = StartupTimeline()
    with tl.phase("engine"):
        with tl.phase("weights"):
            time.sleep(0.02)
        time.sleep(0.01)
    tl.ready()
    names = [name for name, _, _ in tl.phases]
    assert names == ["interpreter", "weights", "engine"]
    for (_, _, end), (_, start, _) in zip(tl.phases, tl.phases[1:]):
        assert start == end
    took = tl.seconds()
    assert took["weights"] >= 0.02 and 0.01 <= took["engine"] < 0.02 + 0.5
    assert tl.health()["ready_s"] == pytest.approx(sum(took.values()),
                                                   abs=1e-5)


def test_without_proc_there_is_no_interpreter_phase(monkeypatch):
    monkeypatch.setattr(startup_mod, "process_created_mono",
                        lambda now=None: None)
    tl = StartupTimeline()
    with tl.phase("listen"):
        pass
    tl.ready()
    assert [name for name, _, _ in tl.phases] == ["listen"]
    reg = Registry()
    tl.publish(reg.gauge("ome_router_startup_phase_seconds", "",
                         labelnames=("phase",)),
               reg.gauge("ome_router_startup_seconds", ""))
    assert reg.get("ome_router_startup_seconds") == pytest.approx(
        reg.get("ome_router_startup_phase_seconds", phase="listen"))
    assert tl.health()["phases"][0]["start_s"] == 0.0


def test_process_creation_lies_before_now_and_after_boot():
    now = time.monotonic()
    created = startup_mod.process_created_mono(now)
    assert created is not None          # the tests run where /proc is
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    assert 0.0 <= now - created <= uptime + 1.0


def test_spans_need_a_log_and_keep_the_caller_s_trace(tmp_path):
    tl = StartupTimeline()
    with tl.phase("listen"):
        pass
    tl.ready()
    tl.write_spans(None)                            # nothing to do
    tl.write_spans(SpanLog(None))                   # a log that is off
    from ome_tpu.telemetry import new_trace
    ctx = new_trace()
    log = SpanLog(str(tmp_path / "s.jsonl"), component="engine")
    tl.write_spans(log, ctx)
    log.close()
    spans = trace_export.load_spans([str(tmp_path / "s.jsonl")])
    assert {s["trace_id"] for s in spans} == {ctx.trace_id}
    assert spans[0]["parent_id"] == ctx.span_id
    assert [s["name"] for s in spans] == [
        "engine.startup", "engine.startup.interpreter",
        "engine.startup.listen"]


# -- the ledger alone, in this process ----------------------------------


@pytest.fixture
def listening():
    """A full-mode ledger that hears this process's compile events,
    bound to a registry and a flight ring; unhooked afterwards."""
    led = ProgramLedger(mode="full")
    reg, flight = Registry(), FlightRecorder(capacity=256)
    led.bind(reg, flight)
    led.listen()
    yield led, reg, flight
    ledger_mod._listening = None


def _program(width):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(x):
        return jnp.tanh(x @ x.T).sum() + width      # a shape of its own
    return fn


def _seconds(reg) -> float:
    return sum(reg.get("ome_engine_compile_seconds_total", stage=stage,
                       when=when)
               for stage in COMPILE_STAGES for when in COMPILE_WHEN)


@pytest.mark.parametrize("mode", ["full", "model"])
def test_stages_are_disjoint_and_add_up_to_less_than_the_wall(
        listening, mode):
    import numpy as np
    led, reg, flight = listening
    led.mode = mode
    fn = _program(17 if mode == "full" else 19)
    x = np.ones((17 if mode == "full" else 19, 8), np.float32)
    t0 = time.monotonic()
    entry = led.capture("probe", f"mode={mode}", fn, (x,), {},
                        {"flops": 1.0, "bytes": 1.0})
    fn(x).block_until_ready()
    wall = time.monotonic() - t0
    took = dict(entry["compile_s"])
    assert took["trace"] > 0 and took["lower"] > 0
    assert took["backend_compile"] + took["cache_load"] > 0
    # what ran inside the ledger's second lowering is under its own
    # stage once, and `introspect` holds none of it
    assert sum(took.values()) <= wall + 1e-3
    assert sum(took.values()) == pytest.approx(_seconds(reg))
    if mode == "full":
        assert entry["source"] == "compiled" and took["introspect"] > 0
    else:
        assert entry["source"] == "model" and took["introspect"] < 0.05
    assert entry["cache"] in ("hit", "miss")
    # the second call of a compiled program reports nothing
    led.capture("probe", f"mode={mode}", fn, (x,), {},
                {"flops": 1.0, "bytes": 1.0})
    fn(x).block_until_ready()
    assert dict(entry["compile_s"]) == took
    assert entry["dispatches"] == 2
    stages = [e["stage"] for e in flight.snapshot()
              if e["event"] == PROGRAM_COMPILED
              and e["program"] == entry["program"]]
    assert sorted(set(stages)) == sorted(
        s for s in COMPILE_STAGES if took[s] > 0 or s == "introspect")
    assert led.mark_serving() is None
    assert led._when == COMPILE_WHEN[1]


def test_a_nested_trace_is_counted_once(listening):
    import jax
    import jax.numpy as jnp
    import numpy as np
    led, reg, _ = listening

    @jax.jit
    def inner(x):
        return jnp.cos(x) * 3.0

    @jax.jit
    def outer(x):
        for _ in range(40):          # forty traces inside one
            x = inner(x + 1.0)
        return x

    x = np.ones((23,), np.float32)
    t0 = time.monotonic()
    entry = led.capture("nest", "", outer, (x,), {}, {})
    outer(x).block_until_ready()
    wall = time.monotonic() - t0
    assert 0 < entry["compile_s"]["trace"] <= wall
    assert sum(entry["compile_s"].values()) <= wall + 1e-3


def test_what_a_thread_compiles_before_its_first_capture_is_others(
        listening):
    import numpy as np
    led, reg, flight = listening
    fn = _program(29)
    x = np.ones((29, 8), np.float32)
    seen = {}

    def work():
        fn(x).block_until_ready()
        seen.update(led.compile_totals()["other"]["compile_s"])

    t = threading.Thread(target=work)
    t.start()
    t.join(120)
    assert not t.is_alive()
    assert seen["trace"] > 0 and seen["lower"] > 0
    assert len(led) == 0
    assert OTHER_PROGRAM in {e["program"] for e in flight.snapshot()}
    totals = led.compile_totals()
    assert sum(totals["events"].values()) >= 1
    assert list(totals["seconds"]) == list(COMPILE_STAGES)


def test_seconds_booked_before_the_bind_are_exported_at_it():
    led = ProgramLedger(mode="model")
    led._book(led._other, "lower", 1.5)
    led._on_outcome("cache_miss")
    led.mark_serving()
    led._book(led._other, "lower", 0.25)
    reg = Registry()
    led.bind(reg)
    assert led.bound
    assert reg.get("ome_engine_compile_seconds_total", stage="lower",
                   when="startup") == 1.5
    assert reg.get("ome_engine_compile_seconds_total", stage="lower",
                   when="serving") == 0.25
    assert reg.get("ome_engine_compile_events_total",
                   outcome="cache_miss") == 1
    # every series exists from the bind on
    text = reg.render()
    for stage in COMPILE_STAGES:
        for when in COMPILE_WHEN:
            assert f'stage="{stage}",when="{when}"' in text
    snap = led.snapshot()
    assert snap == []


# -- the small repair ---------------------------------------------------


def test_a_tokenizer_that_does_not_load_says_so(tmp_path, caplog):
    from ome_tpu.engine.tokenizer import ByteTokenizer, load_tokenizer
    (tmp_path / "tokenizer.json").write_text("{ not a tokenizer")
    with caplog.at_level(logging.WARNING, logger="ome.engine.tokenizer"):
        tok = load_tokenizer(str(tmp_path))
    assert isinstance(tok, ByteTokenizer)
    assert any("did not load" in r.getMessage() for r in caplog.records)
