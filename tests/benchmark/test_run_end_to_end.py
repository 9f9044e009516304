"""A whole run of the harness on the CPU at a toy size, through the
same code as a run on the chip (server and router children, warm-up,
window, check child), with only the look for a TPU skipped: sound, it
ends `correct: true`; with the timed path broken underneath (every
served token altered where it is produced) it ends `correct: false`;
and with the look for a TPU left on it gives no result at all."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixture")
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench  # noqa: E402

CELL = "tiny-qwen3.tiny-chat"
CPU = dict(env_extra={"JAX_PLATFORMS": "cpu"}, bench_root=FIXTURE)


def test_sound_run_is_correct_and_reports_the_cells_metrics(capsys):
    r = bench.run(CELL, 2 ** 31 + 11, 5.0, False, require_tpu=False, **CPU)
    assert r["correct"] is True
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["check"]["tokens"] > 30 and r["check"]["finite"]
    for name in ("ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms", "setup_s"):
        assert r["metrics"][name]["value"] > 0
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    out = capsys.readouterr().out
    compared = [json.loads(ln) for ln in out.splitlines()
                if '"phase": "compare"' in ln]
    # every number compared is printed beside its limit
    assert {c["number"] for c in compared} >= {"gap_mean", "gap_max"}
    assert all("limit" in c and "value" in c for c in compared)


def test_a_token_altered_where_it_is_produced_is_not_correct():
    r = bench.run(CELL, 12345, 4.0, False, require_tpu=False,
                  serve_module="tests.benchmark.broken_serve", **CPU)
    assert r["attempted"] > 3 and r["failed"] == 0   # it serves, wrongly
    assert r["check"]["gap_mean"] > 10 * 0.05
    assert r["correct"] is False


def test_without_a_tpu_there_is_no_result_and_no_fallback(capsys):
    rc = bench.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--trace", "0"], **CPU)
    captured = capsys.readouterr()
    assert rc != 0
    assert '"correct"' not in captured.out
    assert "TPU" in captured.err
