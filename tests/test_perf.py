"""Device-performance attribution (ISSUE 12): program cost ledger,
live HBM accounting, the step's completion time + slow-step outliers,
and the bench regression gate.

Covers: ledger capture in forced-full mode (AOT cost/memory
introspection works on the CPU backend too) and its off-TPU analytic
fallback (`source: "model"`), the guarded /debug/programs surface,
HBM partition arithmetic against injected allocator stats with the
new-peak watermark event, the slow-step detector on an injected
stall, the profiler response's ledger ride-along, and
scripts/perfgate.py pass/fail/waiver/check-only behavior against the
checked-in BENCH history."""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from ome_tpu import faults
from ome_tpu.engine.scheduler import Request, Scheduler
from ome_tpu.engine.server import EngineServer
from ome_tpu.engine.tokenizer import ByteTokenizer
from ome_tpu.perf import (HBM_TENANTS, HbmAccountant, ProgramLedger,
                          device_spec, roofline_ms)
from ome_tpu.telemetry import Registry
from ome_tpu.telemetry.flight import FlightRecorder

from test_faults import FakeEngine, _get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFGATE = os.path.join(REPO, "scripts", "perfgate.py")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _tiny_engine(ledger=None, **kw):
    from ome_tpu.engine.core import InferenceEngine
    from ome_tpu.models.config import ModelConfig
    from ome_tpu.models.llama import init_params
    cfg = ModelConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2,
                      intermediate_size=64, max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(params, cfg, max_slots=2, max_seq=64,
                           ledger=ledger, **kw)


# -- instruction -> scope path, from the compiled text ----------------


HLO = """HloModule jit__decode_paged, is_scheduled=true

%fused_computation.5 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%param_0.1)
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.7 = f32[8]{0} get-tuple-element(%arg), index=1
  %slice-start.2 = f32[8]{0} copy(%get-tuple-element.7)
  %fusion.9 = f32[8]{0} fusion(%slice-start.2), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_decode_paged)/decode/layers/while/body/closed_call/mlp/dot_general" source_file="x.py" source_line=3}
  %copy.3 = f32[8]{0} copy(%fusion.9)
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%get-tuple-element.7, %copy.3)
}

%cond.3 (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.9 (x.1: f32[8], key.1: u32[2]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %key.1 = u32[2]{0} parameter(1)
  %copy.1 = f32[8]{0} copy(%x.1)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%copy.1, %copy.1)
  %while.4 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond.3, body=%body.2, metadata={op_name="jit(_decode_paged)/decode/layers/while"}
  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%while.4), index=1
  %copy.78 = f32[8]{0} copy(%get-tuple-element.2)
  %fusion.2 = f32[8]{0} fusion(%copy.78), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_decode_paged)/decode/sample/jit(argsort)/sort"}
  %sort.2 = f32[8]{0} sort(%fusion.2), dimensions={0}, to_apply=%cond.3
  %fusion.5 = f32[8]{0} fusion(%sort.2), kind=kLoop, calls=%fused_computation.5, metadata={op_name="reduce_window_sum"}
  ROOT %paged_attention.3 = f32[8]{0} custom-call(%fusion.5, %key.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_paged)/decode/layers/attn/pallas_call"}
}
"""


class TestInstructionPaths:
    """perf/ledger.instruction_paths: what joins a device trace's
    operation names to the program's scopes."""

    @pytest.fixture(scope="class")
    def paths(self):
        from ome_tpu.perf.ledger import instruction_paths
        return instruction_paths(HLO)

    @pytest.mark.parametrize("instr,ends", [
        ("fusion.9", "/mlp/dot_general"),          # its own
        ("fusion.2", "/sample/jit(argsort)/sort"),
        ("while.4", "/decode/layers/while"),
        ("paged_attention.3", "/attn/pallas_call"),
        ("sort.2", "/sample/jit(argsort)/sort"),   # its producer's
        ("fusion.5", "/sample/jit(argsort)/sort"),  # a bare op_name
        ("copy.78", "/decode/layers/while"),       # through the loop's result
        ("copy.3", "/mlp/dot_general"),
        ("slice-start.2", "/decode/layers/while"),  # the body's parameter
    ])
    def test_own_path_or_the_nearest_producers(self, paths, instr, ends):
        assert paths[instr].endswith(ends), paths[instr]

    def test_what_resolves_to_nothing_is_left_out(self, paths):
        assert "copy.1" not in paths              # hangs on an argument

    def test_only_instructions_that_run_on_their_own(self, paths):
        for free in ("x.1", "arg", "get-tuple-element.7", "tuple.4",
                     "neg.1", "lt.1"):
            assert free not in paths

    def test_full_mode_entry_serves_the_paths_of_a_real_program(self):
        import jax.numpy as jnp
        from ome_tpu.telemetry.scopes import scoped

        @jax.jit
        @scoped("decode")
        def step(x):
            with jax.named_scope("sample"):
                return jnp.cumsum(jnp.tanh(x))

        led = ProgramLedger(mode="full")
        entry = led.capture("step", "", step, (jnp.ones((8, 128)),), {},
                            {"flops": 1.0, "bytes": 1.0})
        assert entry["op_names"]
        assert all("/decode/" in p for p in entry["op_names"].values())
        assert any("/decode/sample/" in p
                   for p in entry["op_names"].values())
        # the model-mode entry has none, and says so with None
        model = ProgramLedger(mode="model").capture(
            "step", "", step, (jnp.ones((8, 128)),), {},
            {"flops": 1.0, "bytes": 1.0})
        assert model["op_names"] is None


# -- ledger unit behavior --------------------------------------------


class TestLedger:
    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="ledger mode"):
            ProgramLedger(mode="bogus")

    def test_roofline_is_max_of_memory_and_compute(self):
        # memory-bound: 1 GB at 100 GB/s = 10 ms >> compute term
        assert roofline_ms(1e9, 1e9, 100.0, 100.0) == \
            pytest.approx(10.0)
        # compute-bound: 1 TFLOP at 1 TFLOP/s = 1000 ms
        assert roofline_ms(1e12, 1e3, 100.0, 1.0) == \
            pytest.approx(1000.0)

    def test_device_spec_off_tpu(self):
        spec = device_spec()
        assert spec["platform"] == "cpu"
        assert spec["hbm_gbps"] > 0 and spec["peak_tflops"] > 0

    def test_unknown_accelerator_is_an_error(self):
        # a chip that is not in the table is never priced as a v5e
        class Dev:
            platform, device_kind = "tpu", "TPU v9 imaginary"
        with pytest.raises(LookupError, match="no published peaks"):
            device_spec(Dev())

        class V5e:
            platform, device_kind = "tpu", "TPU v5 lite"
        spec = device_spec(V5e())
        assert (spec["hbm_gbps"], spec["peak_tflops"]) == (819.0, 197.0)

    def test_full_entry_names_platform_kernels_and_declines(self):
        import jax.numpy as jnp

        from ome_tpu.ops import note_decline

        @jax.jit
        def f(a):
            note_decline("some_kernel", "shape not covered")
            return a + 1

        led = ProgramLedger(mode="full")
        entry = led.capture("p", "", f, (jnp.ones((8,)),), {},
                            {"flops": 1.0, "bytes": 1.0})
        # priced from the CPU row: says so, and holds no Mosaic call
        assert entry["platform"] == "cpu"
        assert entry["mosaic_calls"] == 0
        assert entry["kernel_declines"] == [
            "some_kernel: shape not covered"]

    def test_capture_model_fallback_off_tpu(self):
        # mode "auto" resolves to the analytic model off-TPU — the
        # acceptance path for TPU-less CI: no second compile, no crash
        led = ProgramLedger(mode="auto")
        entry = led.capture("decode", "", None, (), {},
                            {"flops": 2e9, "bytes": 1e8})
        assert entry["source"] == "model"
        assert entry["flops"] == 2e9 and entry["bytes"] == 1e8
        assert entry["expected_ms"] > 0
        assert len(led) == 1

    def test_capture_full_introspects_compiled_program(self):
        import jax.numpy as jnp

        @jax.jit
        def f(a, b):
            return a @ b

        x = jnp.ones((64, 64), jnp.float32)
        led = ProgramLedger(mode="full")
        entry = led.capture("matmul", "", f, (x, x), {},
                            {"flops": 1.0, "bytes": 1.0})
        # the compiler's numbers replace the analytic seed
        assert entry["source"] in ("compiled", "lowered")
        assert entry["flops"] >= 2 * 64 * 64 * 64 * 0.9
        assert entry["bytes"] > 0
        assert entry["argument_bytes"] == 2 * 64 * 64 * 4
        assert entry["output_bytes"] == 64 * 64 * 4
        # repeat dispatch: same entry, bumped count, no re-lowering
        again = led.capture("matmul", "", f, (x, x), {},
                            {"flops": 1.0, "bytes": 1.0})
        assert again is entry and entry["dispatches"] == 2
        assert led.last_dispatch() is entry

    def test_static_desc_splits_entries(self):
        led = ProgramLedger(mode="model")
        led.capture("decode_multi", "n=4", None, (), {},
                    {"flops": 1.0, "bytes": 1.0})
        led.capture("decode_multi", "n=8", None, (), {},
                    {"flops": 2.0, "bytes": 2.0})
        assert [e["program"] for e in led.snapshot()] == \
            ["decode_multi[n=4]", "decode_multi[n=8]"]

    def test_off_mode_captures_nothing(self):
        led = ProgramLedger(mode="off")
        assert led.capture("decode", "", None, (), {},
                           {"flops": 1.0, "bytes": 1.0}) is None
        assert len(led) == 0

    def test_bind_exports_retroactively(self):
        led = ProgramLedger(mode="model")
        led.capture("decode", "", None, (), {},
                    {"flops": 5.0, "bytes": 7.0})
        reg = Registry()
        fl = FlightRecorder()
        led.bind(reg, fl)
        assert reg.get("ome_engine_program_flops",
                       program="decode") == 5.0
        assert reg.get("ome_engine_program_bytes",
                       program="decode") == 7.0
        # post-bind captures flow through gauges AND the flight ring
        led.capture("prefill", "bucket=64", None, (), {},
                    {"flops": 3.0, "bytes": 4.0})
        assert reg.get("ome_engine_program_flops",
                       program="prefill[bucket=64]") == 3.0
        assert "program_captured" in \
            [e["event"] for e in fl.snapshot(10)]

    def test_summary_shape(self):
        led = ProgramLedger(mode="model")
        led.capture("decode", "", None, (), {},
                    {"flops": 1.0, "bytes": 1.0})
        (row,) = led.summary()
        assert set(row) == {"program", "expected_ms", "source"}


# -- engine integration ----------------------------------------------


class TestEngineLedger:
    def test_real_engine_model_mode_entries(self):
        led = ProgramLedger(mode="model")
        eng = _tiny_engine(ledger=led)
        state = eng.new_state()
        tok, kv, tl, bucket = eng.prefill([1, 2, 3])
        state = eng.insert(state, kv, 0, tl, tok, bucket)
        state, _ = eng.decode(state, [0.0, 0.0], [0, 0], [1.0, 1.0])
        programs = {e["program"]: e for e in led.snapshot()}
        assert "prefill[bucket=64]" in programs
        assert "decode" in programs
        for e in programs.values():
            # off-TPU degradation: analytic numbers, flagged as such
            assert e["source"] == "model"
            assert e["flops"] > 0 and e["bytes"] > 0
            assert e["expected_ms"] > 0

    def test_engine_builds_default_ledger(self):
        eng = _tiny_engine()
        assert isinstance(eng.ledger, ProgramLedger)
        assert eng.ledger.mode == "auto"


# -- /debug/programs surface -----------------------------------------


class TestDebugPrograms:
    def test_403_when_disabled(self):
        srv = EngineServer(Scheduler(FakeEngine(max_slots=1)),
                           tokenizer=ByteTokenizer(), model_name="t",
                           port=0)
        srv.start()
        try:
            status, body = _get(
                f"http://127.0.0.1:{srv.port}/debug/programs")
            assert status == 403
            assert "--debug-endpoints" in body["error"]
        finally:
            srv.stop()

    def test_404_without_ledger(self):
        srv = EngineServer(Scheduler(FakeEngine(max_slots=1)),
                           tokenizer=ByteTokenizer(), model_name="t",
                           port=0, debug_endpoints=True)
        srv.start()
        try:
            status, body = _get(
                f"http://127.0.0.1:{srv.port}/debug/programs")
            assert status == 404
        finally:
            srv.stop()

    def test_schema_when_enabled(self):
        eng = FakeEngine(max_slots=1)
        eng.ledger = ProgramLedger(mode="model")
        sched = Scheduler(eng)  # binds the ledger to its registry
        eng.ledger.capture("decode", "", None, (), {},
                           {"flops": 2e9, "bytes": 1e8})
        srv = EngineServer(sched, tokenizer=ByteTokenizer(),
                           model_name="t", port=0,
                           debug_endpoints=True)
        srv.start()
        try:
            status, doc = _get(
                f"http://127.0.0.1:{srv.port}/debug/programs")
            assert status == 200
            assert doc["mode"] == "model"
            assert doc["count"] == 1
            assert doc["device"]["platform"] == "cpu"
            (entry,) = doc["programs"]
            assert entry["program"] == "decode"
            for field in ("flops", "bytes", "expected_ms", "source",
                          "dispatches"):
                assert field in entry
        finally:
            srv.stop()


# -- HBM accounting --------------------------------------------------


class TestHbm:
    def test_partition_arithmetic(self):
        reg = Registry()
        acc = HbmAccountant(
            reg, weight_bytes=1000, flight=None,
            stats_fn=lambda: {"bytes_in_use": 5000,
                              "bytes_limit": 16000,
                              "peak_bytes_in_use": 6000})
        part = acc.update(engine=None)  # no engine: kv/prefix are 0
        assert part["weights"] == 1000
        assert part["kv_cache"] == 0 and part["prefix_cache"] == 0
        assert part["workspace"] == 4000  # residual
        assert part["bytes_in_use"] == 5000
        assert reg.get("ome_engine_hbm_bytes_in_use") == 5000
        assert reg.get("ome_engine_hbm_bytes_limit") == 16000
        assert reg.get("ome_engine_hbm_peak_bytes") == 6000
        assert reg.get("ome_engine_hbm_tenant_bytes",
                       tenant="workspace") == 4000
        for t in HBM_TENANTS:  # every tenant pre-created, no gaps
            assert reg.get("ome_engine_hbm_tenant_bytes",
                           tenant=t) is not None

    def test_no_stats_falls_back_to_tenant_model(self):
        reg = Registry()
        acc = HbmAccountant(reg, weight_bytes=1234,
                            stats_fn=lambda: None)
        part = acc.update(engine=None)
        assert part["bytes_in_use"] == 1234
        assert part["workspace"] == 0

    def test_peak_watermark_event(self):
        fl = FlightRecorder()
        stats = {"bytes_in_use": 100, "peak_bytes_in_use": 100}
        acc = HbmAccountant(Registry(), weight_bytes=10, flight=fl,
                            stats_fn=lambda: dict(stats))
        acc.update()  # first observation seeds the watermark silently
        acc.update()  # flat: no event
        assert not [e for e in fl.snapshot(10)
                    if e["event"] == "hbm_peak"]
        stats["peak_bytes_in_use"] = 150
        stats["bytes_in_use"] = 150
        acc.update()
        (ev,) = [e for e in fl.snapshot(10)
                 if e["event"] == "hbm_peak"]
        assert ev["peak_bytes"] == 150
        assert ev["weights"] == 10
        assert ev["workspace"] == 140

    def test_for_engine_rejects_fakes(self):
        assert HbmAccountant.for_engine(FakeEngine(), Registry()) \
            is None

    def test_for_engine_real_engine_partitions_kv(self):
        eng = _tiny_engine()
        reg = Registry()
        acc = HbmAccountant.for_engine(eng, reg)
        assert acc is not None
        part = acc.update(eng)
        # dense slab: L * B * S * heads * (kd + vd) * itemsize
        cfg = eng.cfg
        import jax.numpy as jnp
        expect_kv = (cfg.num_layers * eng.max_slots * eng.max_seq
                     * cfg.kv_cache_heads
                     * (cfg.kv_cache_k_dim + cfg.kv_cache_v_dim)
                     * jnp.dtype(cfg.dtype).itemsize)
        assert part["kv_cache"] == expect_kv
        assert part["weights"] > 0


# -- slow-step detector ----------------------------------------------


class StallEngine(FakeEngine):
    """FakeEngine whose decode stalls when an armed `fake_decode`
    fault rule says so (faults.py grammar, e.g.
    ``fake_decode.slow=0.08@40``)."""

    def decode(self, state, t, k, p):
        faults.fire("fake_decode")
        return state, np.full(self.max_slots, 3, np.int32)


class TestSlowStep:
    def test_injected_stall_records_flight_event(self):
        # the detector needs a half-full rolling window (32 steps)
        # before judging; stall step 40 at ~100x the fake median
        faults.install("fake_decode.slow=0.08@40")
        sched = Scheduler(StallEngine(max_slots=1))
        req = Request(id="r1", prompt_ids=[1, 2], max_new_tokens=50)
        sched.submit(req)
        deadline = time.monotonic() + 30
        while not req.done.is_set() and time.monotonic() < deadline:
            sched.step()
        assert req.done.is_set()
        events = [e for e in sched.flight.snapshot(256)
                  if e["event"] == "slow_step"]
        assert events, "stalled step never flagged"
        # a µs-scale fake median may flag ambient jitter too; the
        # INJECTED stall must be among the flagged steps
        ev = max(events, key=lambda e: e["step_ms"])
        # phase breakdown rides along for the post-mortem
        for field in ("step_ms", "median_ms", "ratio", "k_steps",
                      "mask_ms", "gap_ms"):
            assert field in ev
        assert ev["ratio"] > 4.0
        assert ev["step_ms"] >= 80.0
        assert sched.registry.get(
            "ome_engine_slow_steps_total") >= 1

    def test_steady_state_stays_quiet(self):
        # a stable ~5 ms step keeps the median well away from OS
        # jitter; nothing here should ever trip the 4x threshold
        sched = Scheduler(FakeEngine(max_slots=1, decode_s=0.005))
        req = Request(id="r1", prompt_ids=[1], max_new_tokens=50)
        sched.submit(req)
        deadline = time.monotonic() + 30
        while not req.done.is_set() and time.monotonic() < deadline:
            sched.step()
        assert not [e for e in sched.flight.snapshot(256)
                    if e["event"] == "slow_step"]


# -- a step time that is a step ----------------------------------------


class _Lagged:
    """Device tokens whose host fetch blocks: np.asarray() on it is
    the scheduler's lag-queue read."""

    def __init__(self, toks, fetch_s):
        self.toks, self.fetch_s = toks, fetch_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.fetch_s)
        return self.toks


class LaggedEngine(FakeEngine):
    """Dispatch returns at once; the result takes `fetch_s` to reach
    the host — a pipelined device step seen from the host."""

    def __init__(self, fetch_s=0.02, **kw):
        super().__init__(**kw)
        self.fetch_s = fetch_s

    def decode(self, state, t, k, p):
        return state, _Lagged(np.full(self.max_slots, 3, np.int32),
                              self.fetch_s)


def _run_to_done(sched, req, timeout=60):
    sched.submit(req)
    deadline = time.monotonic() + timeout
    while not req.done.is_set() and time.monotonic() < deadline:
        sched.step()
    assert req.done.is_set()


class TestStepTime:
    REMOVED = ("ome_engine_roofline_efficiency",
               "ome_engine_step_achieved_gbps",
               "ome_engine_roofline_step_efficiency")

    @pytest.fixture(scope="class")
    def lagged(self):
        sched = Scheduler(LaggedEngine(max_slots=1, fetch_s=0.02))
        _run_to_done(sched, Request(id="r1", prompt_ids=[1, 2],
                                    max_new_tokens=12))
        return sched

    def test_step_seconds_reads_the_completion_not_the_enqueue(
            self, lagged):
        h = lagged._h_decode_step
        assert h.count >= 10
        mean = h.sum / h.count
        # parent: ~0 (the fake dispatch returns at once)
        assert 0.015 < mean < 0.06, mean

    def test_queue_wait_estimator_still_fed_the_dispatch(self, lagged):
        """`_ewma_step_s` keeps its input: what the dispatch took to
        return, not the completion (a behaviour change for a PR of
        its own — PERF.md section 7)."""
        assert lagged._ewma_step_s is not None
        assert lagged._ewma_step_s < 0.005

    def test_phase_helper_moves_its_histogram_child(self, lagged):
        child = lagged._ph["host_sample"]
        before = (child.count, child.sum, lagged._ewma_step_s)
        with lagged._phase("host_sample", step=99) as ph:
            time.sleep(0.01)
        assert child.count == before[0] + 1
        assert child.sum - before[1] == pytest.approx(ph.dt)
        assert ph.dt >= 0.01
        assert lagged._ewma_step_s == before[2]

    @pytest.mark.parametrize("phase,at_least", [
        ("plan", 10), ("insert", 1), ("dispatch", 10),
        ("device_wait", 10), ("host_sample", 10)])
    def test_phases_observed(self, lagged, phase, at_least):
        assert lagged._ph[phase].count >= at_least
        text = lagged.registry.render()
        assert f'ome_engine_step_phase_seconds_count{{phase="{phase}"}}' \
            in text

    def test_device_wait_holds_the_fetch_not_the_dispatch(self, lagged):
        wait, sent = lagged._ph["device_wait"], lagged._ph["dispatch"]
        assert wait.sum / wait.count > 0.015
        assert sent.sum / sent.count < 0.005

    def test_idle_server_reads_dispatch_to_result(self):
        """After a pause the step starts at its own dispatch, not at
        the previous step's fetch."""
        sched = Scheduler(LaggedEngine(max_slots=1, fetch_s=0.01))
        _run_to_done(sched, Request(id="a", prompt_ids=[1],
                                    max_new_tokens=3))
        sched.step()   # _run would: reads out the step left in flight
        time.sleep(0.3)
        h = sched._h_decode_step
        before = (h.count, h.sum)
        _run_to_done(sched, Request(id="b", prompt_ids=[1],
                                    max_new_tokens=3))
        steps = h.count - before[0]
        assert steps >= 2
        assert (h.sum - before[1]) / steps < 0.1

    @pytest.mark.parametrize("name", REMOVED)
    def test_roofline_series_are_gone_from_metrics(self, lagged, name):
        assert name not in lagged.registry.render()

    @pytest.mark.parametrize("name", REMOVED)
    def test_roofline_series_are_gone_from_the_catalog(self, name):
        for rel in ("docs/observability.md", "docs/perf-attribution.md",
                    "ome_tpu/engine/scheduler.py"):
            with open(os.path.join(REPO, rel)) as f:
                assert name not in f.read(), rel

    def test_real_engine_still_exports_hbm_and_program_gauges(self):
        eng = _tiny_engine(ledger=ProgramLedger(mode="model"))
        sched = Scheduler(eng)
        _run_to_done(sched, Request(id="r1", prompt_ids=[1, 2, 3],
                                    max_new_tokens=8), timeout=120)
        assert sched._h_decode_step.count >= 7
        # HBM gauges refresh on the scrape path
        sched.update_gauges()
        assert sched.registry.get("ome_engine_hbm_bytes_in_use") > 0
        assert "ome_engine_program_bytes" in sched.registry.render()


class TestRooflineOnline:
    def test_compile_dispatch_stays_out_of_the_queue_wait_estimate(
            self):
        """A program's first dispatch includes its compilation. On the
        chip that one sample (tens of seconds) held the estimate over
        the admission cap and an idle server answered 429; the
        estimator takes a program's step times from its second
        dispatch on."""
        class SlowFirstLedger:
            mode = "model"
            entry = {"dispatches": 0, "bytes": 1.0, "expected_ms": 1.0,
                     "program": "decode"}

            def last_dispatch(self):
                return self.entry

        eng = FakeEngine(max_slots=1, decode_s=0.001)
        eng.ledger = SlowFirstLedger()
        decode = eng.decode

        def compile_then_decode(*a, **kw):
            eng.ledger.entry["dispatches"] += 1
            if eng.ledger.entry["dispatches"] == 1:
                time.sleep(0.3)          # the "compilation"
            return decode(*a, **kw)

        eng.decode = compile_then_decode
        sched = Scheduler(eng)
        req = Request(id="r1", prompt_ids=[1], max_new_tokens=6)
        sched.submit(req)
        deadline = time.monotonic() + 30
        while not req.done.is_set() and time.monotonic() < deadline:
            sched.step()
        assert req.done.is_set()
        assert sched._ewma_step_s is not None
        assert sched._ewma_step_s < 0.1


# -- profiler ride-along ---------------------------------------------


class TestProfilerLedger:
    def test_off_tpu_response_carries_programs(self):
        from ome_tpu.telemetry import profiler
        led = ProgramLedger(mode="model")
        led.capture("decode", "", None, (), {},
                    {"flops": 1.0, "bytes": 1.0})
        result = profiler.capture("/tmp/unused", 0.1, ledger=led)
        assert result["captured"] is False
        assert result["programs"][0]["program"] == "decode"


# -- perfgate --------------------------------------------------------


# the shape of one bench.py result in perfgate's wrapper — a fixture
# for the gate's arithmetic, not a record of any run
HISTORY = os.path.join(REPO, "tests", "data", "bench_history",
                       "BENCH_r*.json")
HISTORY_R05 = HISTORY.replace("*", "05")


def _gate(*args):
    if "--history" not in args:
        args = ("--history", HISTORY, *args)
    return subprocess.run(
        [sys.executable, PERFGATE, *args],
        capture_output=True, text=True, cwd=REPO, timeout=120)


class TestPerfgate:
    def test_check_only_smoke_against_committed_history(self):
        r = _gate("--check-only")
        assert r.returncode == 0, r.stderr
        assert "check-only OK" in r.stdout

    def test_identical_rerun_passes(self, tmp_path):
        base = json.load(open(HISTORY_R05))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(base))
        r = _gate("--bench-json", str(fresh))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "perfgate: pass" in r.stdout

    def test_decode_regression_fails(self, tmp_path):
        base = json.load(open(HISTORY_R05))
        base["parsed"]["value"] *= 0.9  # synthetic 10% decode loss
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(base))
        r = _gate("--bench-json", str(fresh))
        assert r.returncode == 1
        assert "REGRESSION" in r.stdout and "value" in r.stdout

    def test_waiver_downgrades_to_warning(self, tmp_path):
        base = json.load(open(HISTORY_R05))
        base["parsed"]["value"] *= 0.9
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(base))
        waivers = tmp_path / "waivers.json"
        waivers.write_text(json.dumps(
            [{"metric": "value", "reason": "accepted for ISSUE-12"}]))
        r = _gate("--bench-json", str(fresh),
                  "--waivers", str(waivers))
        assert r.returncode == 0, r.stdout
        assert "WAIVED: accepted for ISSUE-12" in r.stdout

    def test_improvement_never_fails(self, tmp_path):
        base = json.load(open(HISTORY_R05))
        base["parsed"]["value"] *= 1.5
        base["parsed"]["prefill_ms_batch32x128"] *= 0.5
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(base))
        r = _gate("--bench-json", str(fresh))
        assert r.returncode == 0
        assert "improved" in r.stdout

    def test_cost_table_artifact(self, tmp_path):
        out = tmp_path / "costs.json"
        r = _gate("--check-only", "--cost-table", str(out))
        assert r.returncode == 0
        table = json.loads(out.read_text())
        assert "decode_bf16" in table["programs"]
        assert table["programs"]["decode_bf16"]["step_ms"] > 0
        assert "prefill_b32x128" in table["programs"]

    def test_composition_cells_gate_and_export(self, tmp_path):
        """bench.py composition cells (docs/step-plan.md) gate under
        the ^composition. bands and export to the cost table: a cell
        losing throughput regresses; its fitted cost ships to the
        fleet simulator as a composed_* program."""
        base = json.load(open(HISTORY_R05))
        base["parsed"]["composition"] = {
            "cells": {"spec4_k4_d1": {
                "tokens_per_sec": 5000.0, "accept_rate": 0.8,
                "spec": 4, "k": 4, "depth": 1, "degraded_steps": 0}},
            "best_single_tokens_per_sec": 4200.0,
            "best_composed_tokens_per_sec": 5000.0,
            "composed_vs_best_single": 1.19}
        hist = tmp_path / "BENCH_r90.json"
        hist.write_text(json.dumps(base))
        fresh = json.loads(json.dumps(base))
        cell = fresh["parsed"]["composition"]["cells"]["spec4_k4_d1"]
        cell["tokens_per_sec"] = 4000.0  # -20%: outside the 8% band
        fj = tmp_path / "fresh.json"
        fj.write_text(json.dumps(fresh))
        r = _gate("--history", str(tmp_path / "BENCH_r*.json"),
                  "--bench-json", str(fj))
        assert r.returncode == 1
        assert "composition.cells.spec4_k4_d1.tokens_per_sec" \
            in r.stdout
        out = tmp_path / "costs.json"
        r = _gate("--history", str(tmp_path / "BENCH_r*.json"),
                  "--check-only", "--cost-table", str(out))
        assert r.returncode == 0, r.stdout + r.stderr
        table = json.loads(out.read_text())
        assert table["programs"]["composed_spec4_k4_d1"] == {
            "tokens_per_sec": 5000.0, "accept_rate": 0.8}

    def test_missing_baseline_is_usage_error(self, tmp_path):
        r = _gate("--history", str(tmp_path / "nope_*.json"),
                  "--check-only")
        assert r.returncode == 2
