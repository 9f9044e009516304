"""The latent-attention sparse family (one cached row a token read by
kernels of their own, sandwich norms, a plain sigmoid router over a
share of the experts) in the harness, without the chip: a whole run on
the CPU at a toy size from a fixture tree of its own
(`fixture_latent/`, files and entries only), the cell as the issue
gives it, the configuration's file against the catalog's row, the
family's cost functions, and the new readers on a program that writes
none of what they read."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixture_latent")
sys.path.insert(0, BENCH)

import cost_latent_moe as cost  # noqa: E402
import latent_kinds  # noqa: E402
import run as bench  # noqa: E402

CELL = "tiny-pangu.tiny-doc"
NAME = "openpangu-ultra-moe-718b-ep16"
REAL = NAME + ".long-doc"
NEW_READERS = ("decode_latent_attn_share", "decode_latent_attn_roofline",
               "prefill_latent_attn_share", "prefill_latent_attn_roofline",
               "decode_latent_moe_roofline", "latent_experts_hit_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _real_config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "openPangu-Ultra-MoE-718B")


def test_a_sound_run_of_the_latent_fixture_is_correct(capsys):
    """Server and router children, warm-up of every bucket, a closed-
    loop window of mixed prompt lengths decoded through the latent
    slab, the check child against the plain reference (which absorbs
    nothing and keeps no cache): the served tokens are the
    reference's, and the server's and the reference's parameter
    counts are the file's."""
    r = bench.run(CELL, 2 ** 31 + 46, 5.0, False, require_tpu=False,
                  env_extra={"JAX_PLATFORMS": "cpu"}, bench_root=FIXTURE)
    out = capsys.readouterr().out
    assert r["correct"] is True, out[-3000:]
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["check"]["tokens"] > 30 and r["check"]["finite"]
    for name in ("itl_p95_ms", "out_tokens_per_s", "setup_s"):
        assert r["metrics"][name]["value"] > 0
    compared = {json.loads(ln)["number"]: json.loads(ln)
                for ln in out.splitlines() if '"phase": "compare"' in ln}
    assert compared["params_served_vs_published"]["value"] < 0.005
    assert compared["params_reference_vs_published"]["value"] == 0


def test_the_fixture_and_the_cell_resolve_to_files_and_readers():
    for root, cell in ((FIXTURE, CELL), (ROOT, REAL)):
        c = bench.load_cell(cell, root)
        assert c["config"]["benchmark"]["reference"] == "latent_moe"
        reported = {m["name"] for m in c["end_to_end"]}
        assert {"setup_s", "itl_p95_ms"} <= reported
        names = {m["name"] for m in c["per_layer"]}
        assert set(NEW_READERS) | {"decode_moe_share"} <= names
        for m in c["per_layer"]:
            assert callable(bench.load_reader("layer_metrics", m["name"]))
            assert m["moves"] in reported
    # judged on the gap's tail and set-up alone; the other families'
    # rooflines and hit shares read keys this config.json does not have
    c = bench.load_cell(REAL)
    assert {m["name"] for m in c["end_to_end"]} == {"itl_p95_ms", "setup_s"}
    names = {m["name"] for m in c["per_layer"]}
    assert not names & {
        "decode_step_roofline", "decode_moe_roofline",
        "decode_window_moe_roofline", "decode_attn_cache_roofline",
        "prefill_attn_roofline", "moe_experts_hit_share",
        "preroute_experts_hit_share", "decode_window_attn_share",
        "decode_global_attn_share", "prefill_attn_share"}
    for cell in ("qwen3-4b.chat-steady", "qwen3-4b.batch-offline",
                 "qwen3-next-80b-a3b-ep4.long-batch",
                 "trinity-mini-ep4.long-doc",
                 "smallthinker-21b-a3b-ep4.long-decode"):
        assert not {m["name"] for m in
                    bench.load_cell(cell)["per_layer"]} & set(NEW_READERS)


def test_the_cells_traffic_is_what_the_issue_gives():
    import traffic
    c = bench.load_cell(REAL)
    assert c["cell"]["chips"] == 1 and c["cell"]["traffic"] == "long-doc"
    # the mix is trinity-mini-ep4.long-doc's, the file unchanged
    assert c["traffic"] == bench.load_cell(
        "trinity-mini-ep4.long-doc")["traffic"]
    plan = traffic.plan(c["traffic"], 2 ** 31 + 5, 51.0)
    assert len(plan) == 24 * 40 and {p.client for p in plan} == set(range(24))
    assert min(p.prompt_tokens for p in plan) >= 4096
    assert max(p.prompt_tokens for p in plan) <= 15872
    assert 64 <= min(p.max_tokens for p in plan) \
        and max(p.max_tokens for p in plan) <= 192
    assert {p.temperature for p in plan} == {0.0, 0.8}
    args = c["config"]["benchmark"]["serve_args"]
    assert 15872 + 192 <= args[args.index("--max-seq") + 1] == 16384
    assert args[args.index("--max-slots") + 1] == c["traffic"]["clients"] == 24
    assert "--kv-block" not in args
    buckets = c["config"]["benchmark"]["prefill_buckets"]
    lo, hi = traffic.prefill_lengths(c["traffic"])
    assert [b for b in buckets if b >= lo][:1] == [4096] and hi <= buckets[-1]
    assert c["config"]["benchmark"]["kernels"] == {"decode": 1}
    assert set(c["config"]["benchmark"]["kernels_if_compiled"]) == {
        "prefill[bucket=8192]", "prefill[bucket=16384]"}


def test_the_configurations_file_keeps_every_published_number():
    """The catalog row's numbers under the same keys, the cuts listed
    (in the file and in BENCHMARK.json alike), and the held count the
    issue reckons."""
    file = _real_config()
    reduced = ["first_k_dense_replace", "n_routed_experts",
               "num_hidden_layers", "vocab_size"]
    assert sorted(file["reduced"]) == reduced
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == NAME)
    assert sorted(entry["reduced"]) == reduced
    assert entry["source"] == file["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert (file["num_hidden_layers"], file["first_k_dense_replace"],
            file["n_routed_experts"], file["vocab_size"],
            file["ep_num_experts_total"], file["ep_expert_offset"]) == (
                5, 1, 16, 19200, 256, 0)
    for key in ("published", "deployment", "assumed"):
        assert file[key]
    assert cost.param_count(file) == 4_919_139_840 \
        == file["benchmark"]["published_params"]
    limits = file["benchmark"]["check"]
    assert 0 < limits["gap_mean_limit"] < limits["gap_max_limit"]
    row = _catalog_row()
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in file["reduced"]:
            assert file[key] == value, key
    # the floors: an eighth of the vocabulary, 8 routed experts, four
    # layers behind the leading dense ones (which count once)
    assert file["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert file["n_routed_experts"] >= 8
    assert file["num_hidden_layers"] - file["first_k_dense_replace"] >= 4


def test_cost_functions_count_the_issues_arithmetic():
    cfg = _real_config()
    assert cost.attention_params(cfg) == 196_577_280
    assert cost.expert_params(cfg) == 47_185_920
    assert cost.dense_layer_params(cfg) == 621_281_280
    assert cost.expert_layer_params(cfg) == 1_000_734_720
    # the PUBLISHED keys: the 718B of the name
    published = _catalog_row()["config"]
    assert cost.param_count(published) == 719_093_767_680
    assert cost.expert_layer_params(published) == \
        245_760_000 + 256 * 47_185_920
    # a cached row is 576 numbers a layer: 5760 B a token over 5 layers
    assert cost.latent_row_bytes(cfg) == 1152
    nbytes, flops = cost.latent_decode_step(cfg, [10_000] * 24)
    assert nbytes == 24 * 10_000 * 5 * 1152 == 1_382_400_000
    # every head scores 576 lanes and weighs 512: 278 528 operations
    # a row, 242 a byte, where a v5e's ridge is 197e12 / 819e9 = 240
    assert flops / (24 * 10_000 * 5) == 278_528
    assert 241 < flops / nbytes < 243
    assert cost.latent_decode_step(cfg, []) == (0.0, 0.0)
    # a 16 384-token prompt's triangle: 55 TFLOP over five layers
    assert cost.seen_pairs(4) == 10
    assert cost.latent_prefill_flops(cfg, 16384) == pytest.approx(
        54.98e12, rel=1e-3)
    assert cost.latent_prefill_flops(cfg, 1) == 5 * 2 * 128 * 320
    # 4 expert layers, 8.5 of 16 held experts hit: 3.2 GB
    assert cost.moe_step_bytes(cfg, 8.5) == 4 * 8.5 * 2 * 47_185_920
    # the expected hit share of 24 tokens x top-8 of 256: 53 %
    assert 100 * (1 - (248 / 256) ** 24) == pytest.approx(53.3, abs=0.1)


def _answer(prompt, arrivals, done=True):
    return types.SimpleNamespace(prompt_ids=[0] * prompt, arrivals=arrivals,
                                 done=done)


# the program's expert counters over a window: 4 expert layers a step,
# 50 steps, 9 of the 16 held experts hit a layer-step
MOE_BEFORE = {"ome_engine_moe_layer_steps_total": 8.0,
              "ome_engine_moe_experts_hit_total": 70.0}
MOE_AFTER = {"ome_engine_moe_layer_steps_total": 8.0 + 4 * 50,
             "ome_engine_moe_experts_hit_total": 70.0 + 4 * 50 * 9}


def _ctx(kinds, sub=None):
    return {"latent_kinds": kinds, "config": _real_config(),
            "subphases": sub, "metrics_before": MOE_BEFORE,
            "metrics_after": MOE_AFTER if sub else MOE_BEFORE,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "t0": 100.0, "seconds": 10.0, "trace": {"window_s": 2.0},
            "answers": [_answer(8000, [103.0, 104.0, 105.5, 106.0]),
                        _answer(3000, [104.5, 104.9, 105.2], done=False),
                        _answer(4000, [101.0, 102.0]),          # ended
                        _answer(4000, [106.0, 107.0])]}         # not begun


def test_the_new_readers_read_scope_kernels_lengths_and_the_counter(capsys):
    # 50 decode steps of 12 ms: 3 ms under the scope, 2.5 of them the
    # kernel; two whole prefills, one of which a span names
    kinds = {"modules": {"decode": 50, "prefill": 3},
             "family_s": {"decode": 0.6, "prefill": 2.4},
             "scope_s": {"decode": 0.15, "prefill": 1.2},
             "kernel_s": {"decode": {"latent_decode": 0.125},
                          "prefill": {"latent_prefill": 1.1}},
             "prefills": [
                 {"dur_s": 1.0, "busy_s": 1.0, "attn_s": 0.5,
                  "kernel_s": 0.45, "prompt_tokens": 16000},
                 {"dur_s": 0.6, "busy_s": 0.6, "attn_s": 0.3,
                  "kernel_s": 0.28, "prompt_tokens": None}]}
    sub = {"decode_s": 0.6, "decode_steps": 50,
           "sub_s": {"moe_experts": 0.3},
           "sets_s": {"moe_experts": 0.3, "moe_router": 0.025}}
    ctx = _ctx(kinds, sub)
    read = {n: bench.load_reader("layer_metrics", n)(ctx)
            for n in NEW_READERS}
    cfg = _real_config()
    # in flight at 105.0: 8000 + 2 tokens, and 3000 + 2
    assert latent_kinds.live_lengths(ctx) == [8002, 3002]
    nbytes, flops = cost.latent_decode_step(cfg, [8002, 3002])
    by_bytes, by_flops = nbytes / 819e9, flops / 197e12
    assert read["decode_latent_attn_roofline"] == pytest.approx(
        100 * max(by_bytes, by_flops) / 0.0025)
    assert read["decode_latent_attn_roofline"] < 100
    # both of its sides are printed beside it
    line = next(json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                if "latent_decode_roofline" in ln)
    assert line["bytes_ms"] == pytest.approx(1e3 * by_bytes)
    assert line["flops_ms"] == pytest.approx(1e3 * by_flops)
    assert line["kernel_ms"] == pytest.approx(2.5)
    assert line["bound"] == "compute"       # 242 operations a byte
    assert read["decode_latent_attn_share"] == pytest.approx(25.0)
    assert read["prefill_latent_attn_share"] == pytest.approx(50.0)
    # the one prefill a span names, at its TRUE length
    assert read["prefill_latent_attn_roofline"] == pytest.approx(
        100 * cost.latent_prefill_flops(cfg, 16000) / 197e12 / 0.5)
    assert read["prefill_latent_attn_roofline"] < 100
    assert read["decode_latent_moe_roofline"] == pytest.approx(
        100 * cost.moe_step_bytes(cfg, 9) / 819e9 / (0.3 / 50))
    assert read["decode_latent_moe_roofline"] < 100
    assert read["latent_experts_hit_share"] == pytest.approx(100 * 9 / 16)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_on_a_parent(name):
    """A program that writes neither the scope, the kernels nor the
    counters (the parent commit refuses this architecture; a traced
    run of any other cell on it must still end): nothing, and no
    exception."""
    ctx = _ctx(None)
    assert bench.load_reader("layer_metrics", name)(ctx) is None
    ctx = _ctx(None)
    del ctx["latent_kinds"], ctx["subphases"]
    ctx["profile"] = {}
    assert bench.load_reader("layer_metrics", name)(ctx) is None
    # counters that never moved, a scope and kernels that hold no time
    ctx = _ctx({"modules": {"decode": 10, "prefill": 0},
                "family_s": {"decode": 0.1, "prefill": 0.0},
                "scope_s": {"decode": 0.0, "prefill": 0.0},
                "kernel_s": {"decode": {}, "prefill": {}}, "prefills": []},
               None)
    assert bench.load_reader("layer_metrics", name)(ctx) is None


def test_latent_kinds_reduces_a_plane_by_scope_and_kernel():
    """One decode module and one whole prefill module: the scope by
    the op_name path (event's own or the ledger's map), the kernels by
    their instructions' names, the prefill's length by the host span
    that covers it."""
    modules = [("jit__decode(1)", 0.0, 1.0), ("jit__prefill(2)", 2.0, 3.0)]
    d = "jit(_decode)/decode/layers/while/body/closed_call/"
    p = "jit(_prefill)/prefill/layers/while/body/closed_call/"
    ops = [
        ("%latent_decode.3 = bf16[24,128,512] custom-call(...)", 0.1, 0.3,
         d + "attn_latent/attn/latent_decode/pallas_call"),
        ("%fusion.1 = bf16[5,24,16384,640] fusion(...)", 0.4, 0.1,
         d + "attn_latent/kv_write/scatter"),
        ("%fusion.2 = bf16[24,7680] fusion(...)", 0.5, 0.4, d + "mlp/dot"),
        ("%while.1 = (s32[]) while(...)", 0.0, 1.0, ""),     # a container
        ("%latent_prefill.9 = bf16[1,32,16384,128] custom-call(...)", 2.2,
         1.5, ""),
        ("%fusion.7 = bf16[16384,7680] fusion(...)", 3.8, 1.2,
         p + "mlp/dot"),
    ]
    names = {"jit__prefill": {
        "latent_prefill.9": p + "while/body/attn_latent/attn/x/pallas_call"}}
    got = latent_kinds.reduce_plane(modules, ops, names,
                                    admits=[(1.9, 3.2, 15000)])
    assert got["modules"] == {"decode": 1, "prefill": 1}
    assert got["family_s"]["decode"] == pytest.approx(0.8)
    assert got["scope_s"]["decode"] == pytest.approx(0.4)
    assert got["kernel_s"]["decode"] == {"latent_decode": pytest.approx(0.3)}
    assert got["scope_s"]["prefill"] == pytest.approx(1.5)
    assert got["kernel_s"]["prefill"] == {
        "latent_prefill": pytest.approx(1.5)}
    assert got["prefills"] == [{
        "dur_s": 3.0, "busy_s": pytest.approx(2.7),
        "attn_s": pytest.approx(1.5), "kernel_s": pytest.approx(1.5),
        "prompt_tokens": 15000}]
    # a program that writes none of the names
    bare = latent_kinds.reduce_plane(
        modules, [(h, s, t, path.replace("attn_latent/", ""))
                  for h, s, t, path in ops[1:3]])
    assert bare["scope_s"] == {"decode": 0.0, "prefill": 0.0}
