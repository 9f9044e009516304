"""The hybrid family in the harness, without the chip: a whole run on
the CPU at a toy size from a fixture tree of its own
(`fixture_hybrid/`, files and entries only, beside the benchmark's
`fixture/`), the finer reduction of a capture (`subphases.py`), the
family's cost functions, and the new readers on a program that writes
none of what they read."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixture_hybrid")
sys.path.insert(0, BENCH)

import cost_hybrid  # noqa: E402
import run as bench  # noqa: E402
import subphases  # noqa: E402

CELL = "tiny-qwen3-next.tiny-batch"
NEW_READERS = ("decode_moe_share", "decode_linear_attn_share",
               "moe_experts_hit_share", "decode_moe_roofline",
               "decode_linear_attn_roofline")


def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "qwen3-next-80b-a3b-ep4.json")) as f:
        return json.load(f)


def test_a_sound_run_of_the_hybrid_fixture_is_correct(capsys):
    """Server and router children, warm-up of every bucket, a closed-
    loop window, the check child against the plain reference: the
    served tokens are the reference's, and the server's and the
    reference's parameter counts are the file's."""
    r = bench.run(CELL, 2 ** 31 + 27, 5.0, False, require_tpu=False,
                  env_extra={"JAX_PLATFORMS": "cpu"}, bench_root=FIXTURE)
    out = capsys.readouterr().out
    assert r["correct"] is True, out[-3000:]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["check"]["tokens"] > 30 and r["check"]["finite"]
    for name in ("itl_p95_ms", "out_tokens_per_s", "setup_s"):
        assert r["metrics"][name]["value"] > 0
    compared = {json.loads(ln)["number"]: json.loads(ln)
                for ln in out.splitlines() if '"phase": "compare"' in ln}
    # the server logs its count to 0.01 M, of 0.88 M here
    assert compared["params_served_vs_published"]["value"] < 0.005
    assert compared["params_reference_vs_published"]["value"] < 0.005


def test_the_fixture_and_the_cell_resolve_to_files_and_readers():
    for root, cell in ((FIXTURE, CELL),
                       (ROOT, "qwen3-next-80b-a3b-ep4.long-batch")):
        c = bench.load_cell(cell, root)
        assert c["config"]["benchmark"]["reference"] == "hybrid_gdn_moe"
        reported = {m["name"] for m in c["end_to_end"]}
        assert {"setup_s", "itl_p95_ms"} <= reported
        names = {m["name"] for m in c["per_layer"]}
        assert set(NEW_READERS) <= names
        for m in c["per_layer"]:
            assert callable(bench.load_reader("layer_metrics", m["name"]))
            assert m["moves"] in reported
    # the dense formula's roofline and the paged kernel's time are not
    # read in the new cell; every accepted cell keeps them. Tokens per
    # second is not judged there: twelve windows on the chip spread by
    # 2.1 % (PERF.md, PR 27), four times what a new cell is admitted at
    c = bench.load_cell("qwen3-next-80b-a3b-ep4.long-batch")
    assert {m["name"] for m in c["end_to_end"]} == {"itl_p95_ms",
                                                    "setup_s"}
    names = {m["name"] for m in c["per_layer"]}
    assert not names & {"decode_step_roofline",
                        "decode_attention_kernel_ms"}
    for cell in ("qwen3-4b.chat-steady", "qwen3-4b.batch-offline"):
        names = {m["name"] for m in bench.load_cell(cell)["per_layer"]}
        assert {"decode_step_roofline",
                "decode_attention_kernel_ms"} <= names
        assert not names & set(NEW_READERS)


def test_the_cells_traffic_is_what_the_issue_gives():
    import traffic
    c = bench.load_cell("qwen3-next-80b-a3b-ep4.long-batch")
    plan = traffic.plan(c["traffic"], 2 ** 31 + 5, 51.0)
    assert len(plan) == 64 * 40 and {p.client for p in plan} == set(range(64))
    assert min(p.prompt_tokens for p in plan) >= 1024
    assert max(p.prompt_tokens for p in plan) <= 3584
    assert 128 <= min(p.max_tokens for p in plan) \
        and max(p.max_tokens for p in plan) <= 256      # check.ROWS
    first = plan[:64]
    assert sum(p.temperature == 0.0 for p in first) == 13
    assert "schedule_seed" not in c["traffic"]
    # every bucket the traffic reaches is in the file's list, and the
    # longest request fits --max-seq
    args = c["config"]["benchmark"]["serve_args"]
    assert 3584 + 256 <= args[args.index("--max-seq") + 1]
    assert c["config"]["benchmark"]["prefill_buckets"][-1] == 4096


def test_cost_functions_count_the_issues_arithmetic():
    cfg = _real_config()
    assert cost_hybrid.expert_params(cfg) == 3_145_728
    assert cost_hybrid.mixer_params(cfg) == 33_718_464
    assert cost_hybrid.attention_params(cfg) == 27_263_488
    assert cost_hybrid.moe_fixed_params(cfg) + 2 * 2048 == 4_200_448
    assert cost_hybrid.param_count(cfg) == 5_423_084_736 \
        == cfg["benchmark"]["published_params"]
    # a slot's state a layer: 2 MiB of float32 and the conv's 3 rows
    assert cost_hybrid.state_bytes_per_slot(cfg) == (2 << 20) + 3 * 8192 * 2
    # no expert hit: router, shared expert and gate of 12 layers
    assert cost_hybrid.moe_step_bytes(cfg, 0) == 2 * 12 * (
        2048 * 512 + 3 * 2048 * 512 + 2048)
    assert cost_hybrid.moe_step_bytes(cfg, 128) \
        - cost_hybrid.moe_step_bytes(cfg, 0) == 2 * 12 * 128 * 3_145_728
    empty = cost_hybrid.linear_attn_step_bytes(cfg, 0)
    assert empty == 2 * 9 * 33_718_464
    assert cost_hybrid.linear_attn_step_bytes(cfg, 32) - empty == \
        9 * 2 * 32 * cost_hybrid.state_bytes_per_slot(cfg)


def _plane():
    """Two decode steps and a prefill, named as a v5e capture names
    them: paths come from the ledger's map."""
    names = {"jit__decode": {
        "fusion.1": "jit(_decode)/decode/layers/while/body/gdn_mixer/qkv/dot",
        "fusion.2": "jit(_decode)/decode/layers/while/body/gdn_mixer/attn/"
                    "kv_write/gdn_state/mul",
        "fusion.3": "jit(_decode)/decode/layers/while/body/mlp/"
                    "moe_experts/ragged_dot",
        "fusion.4": "jit(_decode)/decode/layers/while/body/mlp/"
                    "moe_shared/dot",
        "fusion.5": "jit(_decode)/decode/layers/while/body/mlp/"
                    "moe_router/dot",
        "fusion.6": "jit(_decode)/decode/lm_head/dot",
    }, "jit__prefill": {
        "fusion.1": "jit(_prefill)/prefill/layers/while/body/mlp/"
                    "moe_experts/ragged_dot"}}
    modules = [("jit__decode(1)", 0.0, 0.010), ("jit__decode(1)", 0.010, 0.010),
               ("jit__prefill(2)", 0.020, 0.005)]
    ops = []
    for base in (0.0, 0.010):
        t = base
        for i, d in enumerate((0.001, 0.002, 0.003, 0.0005, 0.0005, 0.001)):
            ops.append((f"%fusion.{i + 1} = f32[8]{{0}} fusion(%p)", t, d, ""))
            t += d
        ops.append(("%while.1 = (f32[8]) while(%t)", base, 0.009, ""))
    ops.append(("%fusion.1 = f32[8]{0} fusion(%p)", 0.020, 0.005, ""))
    return modules, ops, names


def test_subphases_books_time_on_every_name_of_the_path():
    modules, ops, names = _plane()
    total = subphases.reduce({"/device:TPU:0": {"modules": modules,
                                                "ops": ops}}, names)
    assert total["decode_steps"] == 2
    assert total["decode_s"] == pytest.approx(0.016)
    assert total["sub_s"]["gdn_mixer"] == pytest.approx(0.006)
    assert total["sub_s"]["gdn_state"] == pytest.approx(0.004)
    # nested names are one operation's time, counted once in a group
    assert subphases.under(total, subphases.LINEAR_ATTN) == \
        pytest.approx(0.006)
    assert subphases.under(total, subphases.MOE) == pytest.approx(0.008)
    # the prefill's expert time is not the decode family's
    assert total["sub_s"]["moe_experts"] == pytest.approx(0.006)
    # the accepted reduction reads the same capture by the phase around
    import phases
    accepted = phases.reduce_plane(modules, ops, [], names)
    assert accepted["decode_phases_s"]["mlp"] == pytest.approx(0.008)
    assert accepted["decode_phases_s"]["kv_write"] == pytest.approx(0.004)
    assert accepted["decode_phases_s"]["qkv"] == pytest.approx(0.002)
    assert "unscoped" not in accepted["decode_phases_s"]


def _ctx(total, moved=True):
    before = {"ome_engine_moe_layer_steps_total": 100.0,
              "ome_engine_moe_experts_hit_total": 4000.0}
    after = {"ome_engine_moe_layer_steps_total": 100.0 + 12 * 50,
             "ome_engine_moe_experts_hit_total": 4000.0 + 12 * 50 * 64}
    return {"subphases": total, "metrics_before": before,
            "metrics_after": after if moved else dict(before),
            "gauge_samples": [{"ome_engine_batch_occupancy_ratio": 0.5},
                              {"ome_engine_batch_occupancy_ratio": 1.0}],
            "config": _real_config(),
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_the_new_readers_read_scopes_and_counters():
    total = {"decode_s": 1.0, "decode_steps": 40,
             "sub_s": {"moe_experts": 0.4}, "sets_s": {
                 "moe_experts": 0.4, "moe_shared": 0.05, "moe_router": 0.05,
                 "gdn_mixer": 0.1, "gdn_mixer+gdn_state": 0.1}}
    ctx = _ctx(total)
    read = {n: bench.load_reader("layer_metrics", n)(ctx)
            for n in NEW_READERS}
    assert read["decode_moe_share"] == pytest.approx(50.0)
    assert read["decode_linear_attn_share"] == pytest.approx(20.0)
    assert read["moe_experts_hit_share"] == pytest.approx(50.0)
    cfg = _real_config()
    least = cost_hybrid.moe_step_bytes(cfg, 64) / 819e9
    assert read["decode_moe_roofline"] == pytest.approx(
        100 * least / (0.5 / 40))
    least = cost_hybrid.linear_attn_step_bytes(cfg, 24) / 819e9
    assert read["decode_linear_attn_roofline"] == pytest.approx(
        100 * least / (0.2 / 40))


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_on_a_parent(name):
    """A program that writes no such scope and has no such counter (the
    parent commit, on which a traced run must still end): nothing, and
    no exception."""
    ctx = _ctx(None, moved=False)
    assert bench.load_reader("layer_metrics", name)(ctx) is None
    ctx = _ctx(None, moved=False)
    del ctx["subphases"]
    ctx["profile"] = {}
    assert bench.load_reader("layer_metrics", name)(ctx) is None
