"""The benchmark's own arithmetic, on the CPU: traffic generation,
percentiles and lateness, the synthetic tokenizer, the trace
reduction, the cost functions, and that every name in BENCHMARK.json
resolves to a file (with a fixture cell that was added as files
only)."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixture")
sys.path.insert(0, BENCH)

import client  # noqa: E402
import cost  # noqa: E402
import modeldir  # noqa: E402
import run as bench  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
import xtrace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _traffic(mix):
    """The mix as the first cell that uses it runs it (a cell's own
    file may give the rate)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    name = next(w["name"] for w in cells if w["traffic"] == mix)
    return bench.load_cell(name)["traffic"]


# -- traffic ---------------------------------------------------------


@pytest.mark.parametrize("mix", ["chat-steady", "batch-offline"])
def test_same_seed_same_schedule_other_seed_another(mix):
    spec = _traffic(mix)
    a = traffic.plan(spec, 3000000001, 45)
    b = traffic.plan(spec, 3000000001, 45)
    c = traffic.plan(spec, 7, 45)
    assert a == b
    assert a != c
    assert [p.prompt_seed for p in a] != [p.prompt_seed for p in c]
    free = {k: v for k, v in spec.items() if k != "schedule_seed"}
    assert [p.prompt_tokens for p in traffic.plan(free, 1, 45)] != \
        [p.prompt_tokens for p in traffic.plan(free, 2, 45)]


def test_a_schedule_seed_pins_the_order_and_leaves_the_words_to_the_seed():
    spec = dict(_traffic("chat-steady"), schedule_seed=0)
    a, b = traffic.plan(spec, 1, 45), traffic.plan(spec, 2, 45)
    same = lambda p: (p.due_s, p.prompt_tokens, p.max_tokens,  # noqa: E731
                      p.temperature)
    assert [same(x) for x in a] == [same(x) for x in b]
    assert [x.prompt_seed for x in a] != [x.prompt_seed for x in b]


def test_every_seed_sends_the_same_work_in_another_order():
    spec = _traffic("chat-steady")
    plans = [traffic.plan(spec, s, 45) for s in (1, 2, 3, 2 ** 31 + 5)]
    def work(p):
        return (len(p), sorted(x.prompt_tokens for x in p),
                sorted(x.max_tokens for x in p),
                sum(x.temperature == 0 for x in p),
                round(p[-1].due_s, 9))
    assert len({str(work(p)) for p in plans}) == 1      # the same work
    assert len(plans[0]) == int(spec["rate_rps"] * 45)
    free = {k: v for k, v in spec.items() if k != "schedule_seed"}
    assert [x.prompt_tokens for x in traffic.plan(free, 1, 45)] != \
        [x.prompt_tokens for x in traffic.plan(free, 2, 45)]  # other order
    greedy = sum(x.temperature == 0 for x in plans[0]) / len(plans[0])
    assert 0.17 < greedy < 0.23
    lo, hi = traffic.prefill_lengths(spec)
    assert all(lo <= x.prompt_tokens <= hi for p in plans for x in p)
    assert all(0 < x.due_s < 45 for p in plans for x in p)
    assert all(a.due_s <= b.due_s for p in plans for a, b in zip(p, p[1:]))


def test_closed_loop_plan_fills_every_client():
    spec = _traffic("batch-offline")
    plan = traffic.plan(spec, 5, 45)
    assert {p.client for p in plan} == set(range(spec["clients"]))
    assert all(p.due_s is None for p in plan)
    assert all(128 <= p.prompt_tokens <= 512 and 128 <= p.max_tokens <= 256
               for p in plan)


def test_unknown_distribution_is_refused():
    spec = dict(_traffic("chat-steady"),
                prompt_tokens={"dist": "zipf", "min": 1, "max": 2})
    with pytest.raises(ValueError):
        traffic.plan(spec, 1, 10)


# -- arithmetic -------------------------------------------------------


def test_percentile_on_a_hand_made_sample():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([], 50) is None
    assert stats.percentile([7], 95) == 7


def test_lateness_and_gaps_on_a_hand_made_sample():
    late = stats.lateness([0.0, 1.0, 2.0], [0.001, 1.0, 2.01])
    assert late["max_ms"] == pytest.approx(10.0)
    assert late["mean_ms"] == pytest.approx(11 / 3)
    assert stats.gaps([1.0, 1.5, 2.5, 4.5]) == [0.5, 1.0, 2.0]
    assert stats.gaps([1.0, 1.5, 2.5, 4.5], until=3.0) == [0.5, 1.0]


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics
    xs = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 102.5)


# -- tokenizer --------------------------------------------------------


def test_synthetic_tokenizer_loads_as_hf_and_has_no_eos(tmp_path):
    from ome_tpu.engine.tokenizer import HFTokenizer, load_tokenizer
    vocab = 5000
    modeldir.write(str(tmp_path), {"vocab_size": vocab, "source": "x",
                                   "benchmark": {}})
    with open(tmp_path / "config.json") as f:
        assert json.load(f) == {"vocab_size": vocab}
    tok = load_tokenizer(str(tmp_path))
    assert isinstance(tok, HFTokenizer)
    assert tok.eos_id is None and tok.bos_id is None
    assert tok.vocab_size == vocab
    import random
    ids = modeldir.prompt_ids(random.Random(3), 300, vocab)
    assert tok.encode(modeldir.prompt_text(ids)) == ids
    pieces = [tok.decode([i]) for i in range(vocab)]
    assert all(pieces) and len(set(pieces)) == vocab
    assert [modeldir.token_id(p) for p in pieces] == list(range(vocab))
    # the streaming path sends decode(ids[:n]) minus what it sent: one
    # non-empty delta a token
    sent = ""
    for n in range(1, 20):
        full = tok.decode(ids[:n])
        delta, sent = full[len(sent):], full
        assert modeldir.token_id(delta) == ids[n - 1]


def test_answer_that_ended_early_is_answered_not_failed():
    a = client.Answer(index=0, status=200, done=True, max_tokens=64,
                      arrivals=[1.0, 1.1], words=[" t5", " t9"],
                      finish_reason="stop", usage_tokens=2)
    assert not a.failed
    assert a.token_ids() == [5, 9]
    refused = client.Answer(index=1, status=429)
    broken = client.Answer(index=2, status=200, arrivals=[1.0], words=["t1"])
    silent = client.Answer(index=3, status=200, done=True)
    assert refused.failed and broken.failed and silent.failed
    odd = client.Answer(index=4, status=200, done=True, arrivals=[1.0],
                        words=[" t5 t6"], usage_tokens=2)
    assert odd.token_ids() is None       # never sampled for the check


def test_verdict_reads_no_token_count_and_no_finish_reason():
    """Hazards 1 and 3 by construction: what `correct` is computed
    from has no field that a short answer or a 429 could move."""
    import inspect
    src = inspect.getsource(bench.verdict)
    for name in ("finish_reason", "usage_tokens", "failed", "arrivals",
                 "status", "lateness"):
        assert name not in src


# -- trace reduction --------------------------------------------------


def test_trace_reduction_on_a_hand_built_trace():
    dev = {
        "/device:TPU:0": {
            "modules": [("jit_decode(1)", 0.0, 0.010),
                        ("jit_decode(1)", 0.020, 0.012),
                        ("jit_prefill(2)", 0.050, 0.030),
                        ("jit_decode(1)", 0.090, 0.014)],
            "ops": [("fusion.1", 0.0, 0.006), ("fusion.2", 0.004, 0.006),
                    ("fusion.1", 0.020, 0.012), ("conv", 0.050, 0.030),
                    ("fusion.1", 0.090, 0.010)]},
        "/device:TPU:1": {
            "modules": [("jit_decode(1)", 0.0, 0.050)],
            "ops": [("fusion.1", 0.0, 0.050)]},
    }
    r = xtrace.reduce_events(dev, (0.0, 0.1))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_by_device"][0] == pytest.approx(0.062)   # overlap merged
    assert r["busy_by_device"][1] == pytest.approx(0.050)
    assert r["busy_s"] == pytest.approx(0.056)
    assert r["modules"]["jit_decode(1)"]["count"] == 3
    assert r["modules"]["jit_decode(1)"]["median_s"] == pytest.approx(0.012)
    assert r["device_ops"][0][0] == "conv"
    assert r["idle_gaps"][0][0] == "unattributed:before:jit_prefill"
    assert r["idle_gaps"][0][1] == pytest.approx(0.018)
    assert xtrace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_device_idle_share_takes_the_worst_device():
    read = bench.load_reader("layer_metrics", "device_idle_share")
    ctx = {"trace": {"window_s": 2.0, "busy_by_device": [1.5, 1.0]}}
    assert read(ctx) == pytest.approx(50.0)
    assert read({"trace": None}) is None


# -- cost -------------------------------------------------------------


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bytes_of_a_decode_step_from_shapes():
    cfg = _config("qwen3-4b")
    assert cost.param_count(cfg) == 4022468096
    # the embedding table is the head (tied), so a step reads all of it
    assert cost.decode_weight_bytes(cfg) == 2 * (4022468096 - 36 * 256)
    assert cost.kv_bytes_per_token(cfg) == 2 * 36 * 8 * 128 * 2
    assert cost.decode_step_bytes(cfg, 1000) == (
        cost.decode_weight_bytes(cfg) + 1000 * cost.kv_bytes_per_token(cfg))


def test_pool_in_the_configuration_fits_the_chip():
    cfg = _config("qwen3-4b")
    args = cfg["benchmark"]["serve_args"]
    get = lambda flag: int(args[args.index(flag) + 1])  # noqa: E731
    fits = cost.size_pool(cfg, get("--max-slots"), get("--max-seq"),
                          get("--kv-block"))
    assert 2 <= get("--kv-blocks") <= fits


# -- BENCHMARK.json ---------------------------------------------------


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units_have_only_the_allowed_characters():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    names += [w[k] for w in b["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in b[k]]
        assert len(ns) == len(set(ns))
    metrics = b["end_to_end"] + b["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(0.01 <= m["bound"] <= 0.1 for m in b["end_to_end"])
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in b["end_to_end"])
    assert all(len(w["why"]) <= 200 for w in b["workloads"] + b["configs"])
    assert 1 <= b["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(len(b["workloads"]) // 4, 1)


@pytest.mark.parametrize("root", [ROOT, FIXTURE])
def test_every_cell_resolves_to_files_and_readers(root):
    """The fixture tree is a configuration, a traffic mix and a cell
    that were added as files and entries only."""
    b = _bench(root)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        cell = bench.load_cell(w["name"], root)
        assert cell["config"]["benchmark"]["chips"] == w["chips"]
        ref = cell["config"]["benchmark"]["reference"]
        assert os.path.exists(os.path.join(BENCH, "reference", ref + ".py"))
        assert traffic.plan(cell["traffic"], 1, 5.0)
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        for m in cell["end_to_end"]:
            assert callable(bench.load_reader("end_to_end", m["name"]))
        for m in cell["per_layer"]:
            assert callable(bench.load_reader("layer_metrics", m["name"]))
            assert m["moves"] in reported, (w["name"], m["name"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for c in b["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))
        assert any(w["config"] == c["name"] for w in b["workloads"])


def test_a_cells_own_file_overrides_single_keys_of_its_traffic():
    cell = bench.load_cell("tiny-qwen3.tiny-chat", FIXTURE)
    assert cell["traffic"]["rate_rps"] == 4.0
    assert cell["traffic"]["loop"] == "open"


def test_harness_holds_no_cells_and_no_models_name():
    b = _bench()
    taboo = [w["name"] for w in b["workloads"]] + \
        [c["name"] for c in b["configs"]] + ["qwen", "mistral"]
    for fname in ("run.py", "traffic.py", "client.py", "xtrace.py",
                  "cost.py", "check.py", "stats.py", "procs.py"):
        with open(os.path.join(BENCH, fname)) as f:
            text = f.read().lower()
        for name in taboo:
            if fname == "cost.py" and name == "qwen":
                continue        # model_type == "qwen3" has q/k norms
            assert name.lower() not in text, (fname, name)


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    """BENCHMARK.json and the files under `paths`, nothing else: exit
    code other than 0 and no result line."""
    import shutil
    import subprocess
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         _bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout


# -- the verdict ------------------------------------------------------


def _verdict(programs, group=None):
    import types
    cfg = {"published_params": 1000, "check": {"gap_mean_limit": 0.007,
                                                "gap_max_limit": 0.45},
           "kernels": {"decode_paged": 1},
           "kernels_if_compiled": {"prefill[bucket=2048]": 1}}
    served = types.SimpleNamespace(bench_cfg=cfg, require_tpu=True,
                                   served_params=lambda: 1000.0)
    group = group or {"tokens": 500, "gap_mean": 0.002, "gap_max": 0.1,
                      "finite": True}
    return bench.verdict(served, group, 1000, {"programs": programs})


def _prog(name, calls=1, declines=()):
    return {"program": name, "mosaic_calls": calls,
            "kernel_declines": list(declines)}


def test_verdict_holds_kernels_only_in_programs_the_traffic_compiled():
    assert _verdict([_prog("decode_paged")])      # bucket never reached
    assert _verdict([_prog("decode_paged"), _prog("prefill[bucket=2048]")])
    assert not _verdict([_prog("prefill[bucket=2048]")])   # no paged decode
    assert not _verdict([_prog("decode_paged", calls=0)])
    assert not _verdict([_prog("decode_paged"),
                         _prog("prefill[bucket=2048]", declines=["x"])])


@pytest.mark.parametrize("field,value", [("gap_mean", 0.02),
                                         ("gap_max", 2.7),
                                         ("finite", False), ("tokens", 0)])
def test_verdict_fails_on_one_number_outside_its_limit(field, value):
    group = {"tokens": 500, "gap_mean": 0.002, "gap_max": 0.1,
             "finite": True, field: value}
    assert not _verdict([_prog("decode_paged")], group)
