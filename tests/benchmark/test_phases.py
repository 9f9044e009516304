"""The reduction that reads the program's own names (benchmark/
phases.py) and the per-layer metrics built on it, on the CPU: the pure
reduction on a hand-built capture, every reader on that context and on
one with no capture, and a real small capture made here to pin the
host-plane format the reducer relies on."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import phases  # noqa: E402
import run as bench  # noqa: E402

DEC, PRE = "jit__decode_paged(11)", "jit__prefill(22)"
P = "jit(_decode_paged)/decode/"


def _op(instr, start, dur, path="", opcode="fusion"):
    hlo = f"%{instr} = f32[16,8]{{1,0}} {opcode}(f32[16,8]{{1,0}} %p.1)"
    return (hlo, start, dur, path)


def _hand_built():
    """Two device planes. Plane 0: two decode modules, one prefill,
    one helper; operations with scope paths (one nested in the layer
    scan's while), one kernel-named, one with no path inside a decode
    module, a container. Gaps: 0.010-0.020 (covered by
    sched.device_wait), 0.032-0.050 (covered by nothing), 0.080-0.090
    (plan and, inside it, mask_apply)."""
    ops0 = [
        _op("while.4", 0.000, 0.010, P + "layers/while", "while"),
        _op("fusion.1", 0.000, 0.002, P + "embed/gather"),
        _op("fusion.7", 0.002, 0.003,
            P + "layers/while/body/closed_call/mlp/dot_general"),
        _op("paged_attention.3", 0.005, 0.001,
            P + "layers/while/body/closed_call/attn/paged_attention/"
            "pallas_call", "custom-call"),
        _op("fusion.9", 0.006, 0.001,
            P + "layers/while/body/dynamic_update_slice"),
        _op("fusion.2", 0.007, 0.002, P + "sample/jit(argsort)/sort"),
        _op("fusion.5", 0.009, 0.001),                 # no path
        # second decode module
        _op("fusion.7", 0.020, 0.004,
            P + "layers/while/body/closed_call/mlp/dot_general"),
        _op("fusion.13", 0.024, 0.002,
            P + "layers/while/body/closed_call/kv_write/scatter"),
        _op("fusion.2", 0.026, 0.004, P + "sample/jit(argsort)/sort"),
        _op("copy.78", 0.030, 0.002, "", "copy"),      # no path
        # prefill, a helper program with no scope at all, decode-less
        _op("flash_prefill.2", 0.050, 0.020,
            "jit(_prefill)/prefill/layers/while/body/closed_call/attn/"
            "flash_prefill/pallas_call", "custom-call"),
        _op("fusion.3", 0.070, 0.010,
            "jit(_prefill)/prefill/sample/jit(argsort)/sort"),
        _op("convert.1", 0.090, 0.010, "", "convert"),
    ]
    mods0 = [(DEC, 0.000, 0.010), (DEC, 0.020, 0.012),
             (PRE, 0.050, 0.030),
             ("jit_convert_element_type(3)", 0.090, 0.010)]
    ops1 = [_op("fusion.7", 0.000, 0.050,
                P + "layers/while/body/closed_call/mlp/dot_general")]
    mods1 = [(DEC, 0.000, 0.050)]
    spans = [("sched.dispatch", 0.000, 0.001),
             ("sched.device_wait", 0.008, 0.013),
             ("sched.plan", 0.078, 0.014),
             ("sched.mask_apply", 0.079, 0.012),
             ("admit.prefill", 0.030, 0.060)]
    return {"/device:TPU:0": {"modules": mods0, "ops": ops0},
            "/device:TPU:1": {"modules": mods1, "ops": ops1}}, spans


def test_scope_of_takes_the_family_and_the_deepest_phase():
    assert phases.scope_of(
        P + "layers/while/body/closed_call/mlp/dot_general") == \
        ("decode", "mlp")
    assert phases.scope_of(P + "layers/while/body/add") == \
        ("decode", "layers")
    assert phases.scope_of(P + "sample/jit(cumsum)/x") == \
        ("decode", "sample")
    assert phases.scope_of("jit(_decode_paged)/decode/mul") == \
        ("decode", None)
    assert phases.scope_of("reduce_window_sum") == (None, None)
    # a family name after a phase is an operation's name, not a family
    assert phases.scope_of("jit(f)/sample/insert") == (None, "sample")


def test_op_path_where_the_hlo_line_carries_it():
    line = ('%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(f)/decode/sample/sort" '
            'source_file="x.py"}')
    assert phases.op_path(line) == "jit(f)/decode/sample/sort"
    assert phases.op_path("%fusion.2 = f32[8]{0} fusion(...)") == ""


def test_kernel_and_opcode_from_the_instruction():
    hlo = _op("paged_attention.3", 0, 1, opcode="custom-call")[0]
    assert phases.kernel_of(hlo) == "paged_attention"
    assert phases.kernel_of(_op("closed_call.9", 0, 1)[0]) is None
    assert phases.kernel_of(_op("flash_prefill", 0, 1)[0]) == \
        "flash_prefill"
    assert phases.opcode_of(hlo) == "custom-call"


def test_reduction_on_a_hand_built_capture():
    devices, spans = _hand_built()
    out = phases.reduce(devices, spans)
    p0 = out["planes"]["/device:TPU:0"]
    # the container is skipped; a pathless operation takes the family
    # of the module that encloses it; a module with no scope is other
    assert p0["module_family"] == {DEC: "decode", PRE: "prefill"}
    assert p0["families_s"]["decode"] == pytest.approx(0.022)
    assert p0["families_s"]["prefill"] == pytest.approx(0.030)
    assert p0["families_s"]["other"] == pytest.approx(0.010)
    assert p0["decode_steps"] == 2
    ph = p0["decode_phases_s"]
    assert ph["mlp"] == pytest.approx(0.007)
    assert ph["layers"] == pytest.approx(0.001)      # self time
    assert ph["kv_write"] == pytest.approx(0.002)
    assert ph["sample"] == pytest.approx(0.006)
    assert ph["unscoped"] == pytest.approx(0.003)
    assert sum(ph.values()) == pytest.approx(p0["families_s"]["decode"])
    assert p0["kernels_s"]["decode"]["paged_attention"] == \
        pytest.approx(0.001)
    assert p0["kernels_s"]["prefill"]["flash_prefill"] == \
        pytest.approx(0.020)
    assert set(p0["decode_unscoped_ops_s"]) == {
        "%fusion.5 fusion f32[16,8]", "%copy.78 copy f32[16,8]"}
    assert p0["busy_s"] == pytest.approx(0.062)
    # gaps by the span that covers most; the innermost where two nest
    assert p0["idle_s"]["device_wait"] == pytest.approx(0.010)
    assert p0["idle_s"]["none"] == pytest.approx(0.018)
    assert p0["idle_s"]["mask_apply"] == pytest.approx(0.010)
    assert "plan" not in p0["idle_s"] and "prefill" not in p0["idle_s"]
    # the sum over planes
    t = out["total"]
    assert t["decode_steps"] == 3
    assert t["families_s"]["decode"] == pytest.approx(0.072)
    assert t["busy_s"] == pytest.approx(0.112)
    assert out["sched_spans"] == 4
    line = phases.decode_line(t)
    assert line["steps"] == 3
    assert line["step_ms"] == pytest.approx(24.0)
    assert sum(line["scopes_share"].values()) == pytest.approx(100.0)
    assert line["kernels_ms"]["paged_attention"] == pytest.approx(1 / 3)
    assert [n for n, _ in line["unscoped_ops_ms"]] == [
        "%copy.78 copy f32[16,8]", "%fusion.5 fusion f32[16,8]"]


def test_paths_joined_by_instruction_name_from_the_programs_ledger():
    """What a v5e capture gives: operations named by instruction and
    nothing else; the path comes from `/debug/programs`' `op_names`
    of the program the enclosing module ran."""
    devices, spans = _hand_built()
    want = phases.reduce(devices, spans)["total"]
    programs = {"count": 3, "programs": [
        {"program": "decode_paged", "name": "decode_paged", "op_names": {
            phases.instruction_of(h): path
            for h, s, _, path in devices["/device:TPU:0"]["ops"]
            if path and s < 0.050}},
        {"program": "prefill[bucket=64]", "name": "prefill",
         "op_names": {"fusion.3":
                      "jit(_prefill)/prefill/sample/jit(argsort)/sort"}},
        {"program": "prefill[bucket=128]", "name": "prefill",
         "op_names": {"flash_prefill.2":
                      "jit(_prefill)/prefill/layers/while/body/attn/x"}},
        {"program": "insert_paged[bucket=64]", "name": "insert_paged",
         "op_names": None}]}
    names = phases.program_names(programs)
    assert set(names) == {"jit__decode_paged", "jit__prefill"}
    assert set(names["jit__prefill"]) == {"fusion.3", "flash_prefill.2"}
    bare = {p: {"modules": ev["modules"],
                "ops": [(h, s, d, "") for h, s, d, _ in ev["ops"]]}
            for p, ev in devices.items()}
    got = phases.reduce(bare, spans, names)["total"]
    assert got["families_s"] == pytest.approx(want["families_s"])
    assert got["decode_phases_s"] == pytest.approx(want["decode_phases_s"])
    assert got["kernels_s"]["decode"] == want["kernels_s"]["decode"]
    assert got["decode_steps"] == want["decode_steps"]
    assert phases.program_names(None) == {}
    assert phases.program_names({"programs": [{"name": "x"}]}) == {}


def test_a_program_that_names_nothing_reduces_to_other():
    devices, _ = _hand_built()
    bare = {p: {"modules": ev["modules"],
                "ops": [(h, s, d, "") for h, s, d, _ in ev["ops"]]}
            for p, ev in devices.items()}
    out = phases.reduce(bare, [])
    assert set(out["total"]["families_s"]) == {"other"}
    assert out["total"]["decode_steps"] == 0
    assert out["total"]["idle_s"] == {"none": pytest.approx(0.038)}
    assert phases.decode_line(out["total"]) is None


TRACED = ["decode_sample_share", "decode_kv_pool_share",
          "decode_attention_kernel_ms", "decode_unscoped_share",
          "prefill_busy_share", "idle_unattributed_share"]


def _ctx(total):
    class A:
        def __init__(self, rid):
            self.request_id = rid
    return {
        "phases": total,
        "answers": [A("cmpl-1"), A("cmpl-2"), A("cmpl-3"), A(None)],
        "request_log": [
            {"request_id": 1, "prefill_s": 0.050},
            {"request_id": 2, "prefill_s": 0.070},
            {"request_id": 3, "prefill_s": None},
            {"request_id": 9, "prefill_s": 9.0}],      # warm-up's
        "metrics_before": {"ome_engine_decode_step_seconds_sum": 1.0,
                           "ome_engine_decode_step_seconds_count": 10.0},
        "metrics_after": {"ome_engine_decode_step_seconds_sum": 5.5,
                          "ome_engine_decode_step_seconds_count": 60.0},
    }


def test_every_new_reader_on_the_hand_built_capture():
    devices, spans = _hand_built()
    out = phases.reduce(devices, spans)
    ctx = _ctx(dict(out["total"], sched_spans=out["sched_spans"]))
    got = {n: bench.load_reader("layer_metrics", n)(ctx)
           for n in TRACED + ["step_time_mean_ms", "prefill_host_p50_ms"]}
    assert got["decode_sample_share"] == pytest.approx(100 * 6 / 72)
    assert got["decode_kv_pool_share"] == pytest.approx(100 * 3 / 72)
    assert got["decode_unscoped_share"] == pytest.approx(100 * 3 / 72)
    assert got["decode_attention_kernel_ms"] == pytest.approx(1 / 3)
    assert got["prefill_busy_share"] == pytest.approx(100 * 30 / 112)
    assert got["idle_unattributed_share"] == pytest.approx(100 * 18 / 38)
    assert got["step_time_mean_ms"] == pytest.approx(90.0)
    assert got["prefill_host_p50_ms"] == pytest.approx(60.0)
    assert all(0 <= got[n] <= 100 for n in got if n.endswith("_share"))


@pytest.mark.parametrize("name", TRACED)
@pytest.mark.parametrize("why", ["no_capture", "no_names"])
def test_a_traced_reader_returns_nothing_without_names(name, why):
    """A run with no capture (`profile` has no `dir`: the CPU answers
    `captured: false`), and a capture of a program that writes no
    names, as the parent of PR 24: None, and no exception."""
    if why == "no_capture":
        ctx = {"profile": {"captured": False, "status": 200}}
    else:
        devices, _ = _hand_built()
        bare = {p: {"modules": ev["modules"],
                    "ops": [(h, s, d, "") for h, s, d, _ in ev["ops"]]}
                for p, ev in devices.items()}
        out = phases.reduce(bare, [])
        ctx = {"phases": dict(out["total"], sched_spans=0)}
    assert bench.load_reader("layer_metrics", name)(ctx) is None
    assert "phases" in ctx      # looked for once, kept


def test_counter_and_log_readers_return_nothing_on_a_parent():
    ctx = _ctx(None)
    ctx["metrics_after"] = dict(ctx["metrics_before"])
    ctx["request_log"] = [{"request_id": 1}, {"request_id": 2}]
    assert bench.load_reader("layer_metrics", "step_time_mean_ms")(ctx) \
        is None
    assert bench.load_reader("layer_metrics",
                             "prefill_host_p50_ms")(ctx) is None


def test_the_benchmarks_vocabulary_is_the_programs():
    """The benchmark keeps its own copy and never imports the
    program's; this is the one place the two are laid side by side."""
    from ome_tpu.telemetry import scopes
    assert phases.FAMILIES == scopes.FAMILIES
    assert phases.PHASES == scopes.PHASES
    assert phases.KERNELS == scopes.KERNELS
    assert phases.SCHED_PREFIX == scopes.SCHED_PREFIX
    with open(os.path.join(BENCH, "phases.py")) as f:
        assert "ome_tpu" not in f.read().replace(
            "ome_tpu/telemetry/scopes.py", "")


def test_a_real_capture_on_the_cpu_pins_the_host_plane_format(tmp_path):
    """A tiny jitted function under the scheduler's `_phase` helper,
    captured as telemetry/profiler.py captures (Python tracer off),
    read back by phases.read_capture: the `sched.*` spans are events
    of a `/host:CPU` plane named as written, and their attributes are
    event stats."""
    import jax
    import jax.numpy as jnp
    from ome_tpu.engine.scheduler import Scheduler
    from ome_tpu.telemetry.scopes import scoped

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_faults import FakeEngine
    sched = Scheduler(FakeEngine(max_slots=1))

    @jax.jit
    @scoped("decode")
    def step(x):
        with jax.named_scope("sample"):
            return jnp.cumsum(jnp.tanh(x))

    x = jnp.ones((64,))
    step(x).block_until_ready()             # compiled before the capture
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 2
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for n in range(3):
            with sched._phase("dispatch", step=n, kind="decode"):
                y = step(x)
            with sched._phase("device_wait", step=n):
                y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = phases.xtrace.find_xplane(str(tmp_path))
    devices, spans = phases.read_capture(path)
    assert devices == {}                    # no TPU plane on the CPU
    names = [n for n, _, _ in spans]
    assert names.count("sched.dispatch") == 3
    assert names.count("sched.device_wait") == 3
    starts = [s for _, s, _ in spans]
    assert all(d > 0 for _, _, d in spans) and max(starts) < 60.0
    # the histogram moved with the spans, by the same helper
    assert sched._ph["dispatch"].count == 3
    listing = phases.read_capture(path, dump=True)
    host = [ln for ln in listing if ln["plane"].startswith("/host:CPU")]
    first = next(ev for ln in host for ev in ln["first"]
                 if ev["name"] == "sched.dispatch")
    assert first["stats"] == {"step": "0", "kind": "decode"}
    # no Python-tracer events: the capture holds no frame of this file
    assert not any("test_phases" in ev["name"]
                   for ln in listing for ev in ln["first"])
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                  "*", "*.xplane.pb"))
