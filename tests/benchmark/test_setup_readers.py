"""The seven readers that split `setup_s` (PR 39), on hand-built
contexts: a value, a program that publishes no such gauge or counter
(the parent commit) -> None, a run that measured no set-up -> None."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import procs  # noqa: E402
import run as bench  # noqa: E402

# a scrape as the server prints it, parsed as the benchmark parses it:
# the compile family's keys carry two labels, in the registry's order
BEFORE = procs.parse_metrics("""
ome_engine_startup_phase_seconds{phase="interpreter"} 6.5
ome_engine_startup_phase_seconds{phase="device"} 1.5
ome_engine_startup_phase_seconds{phase="weights"} 2.25
ome_engine_startup_phase_seconds{phase="engine"} 0.75
ome_engine_startup_phase_seconds{phase="tokenizer"} 17
ome_engine_startup_phase_seconds{phase="listen"} 1
ome_engine_startup_seconds 29
ome_engine_compile_seconds_total{stage="trace",when="startup"} 0.5
ome_engine_compile_seconds_total{stage="trace",when="serving"} 4
ome_engine_compile_seconds_total{stage="lower",when="serving"} 3
ome_engine_compile_seconds_total{stage="cache_load",when="serving"} 2
ome_engine_compile_seconds_total{stage="introspect",when="serving"} 2
ome_engine_compile_events_total{outcome="cache_hit"} 12
ome_engine_compile_events_total{outcome="cache_miss"} 3
ome_engine_decode_steps_total 7
""")
AFTER = dict(BEFORE, **{
    'ome_engine_compile_seconds_total{stage="trace",when="serving"}': 4.25,
    'ome_engine_compile_seconds_total{stage="lower",when="serving"}': 3.5})
# what the parent commit's server prints
BARE = {"ome_engine_decode_steps_total": 7.0}


def ctx(setup_s=50.0, before=BEFORE, after=AFTER):
    return {"setup_s": setup_s, "metrics_before": before,
            "metrics_after": after}


CASES = [
    ("setup_interpreter_s", ctx(), 8.0),
    ("setup_interpreter_s", ctx(before=BARE), None),
    ("setup_interpreter_s", ctx(setup_s=0.0), None),
    ("setup_weights_s", ctx(), 3.0),
    ("setup_weights_s", ctx(before=BARE), None),
    ("setup_weights_s", ctx(setup_s=0.0), None),
    ("setup_tokenizer_s", ctx(), 18.0),
    ("setup_tokenizer_s", ctx(before=BARE), None),
    ("setup_tokenizer_s", ctx(setup_s=0.0), None),
    ("setup_programs_s", ctx(), 11.5),
    ("setup_programs_s", ctx(before=BARE), None),
    ("setup_programs_s", ctx(setup_s=0.0), None),
    ("setup_cache_misses", ctx(), 3.0),
    ("setup_cache_misses", ctx(before=BARE), None),
    ("setup_cache_misses", ctx(setup_s=0.0), None),
    # 29 s to ready + 11 s of compile work while serving, of 50 s
    ("setup_unattributed_share", ctx(), 20.0),
    ("setup_unattributed_share", ctx(before=BARE), None),
    ("setup_unattributed_share", ctx(setup_s=0.0), None),
    # more on the program's clock than the benchmark's: not under 0
    ("setup_unattributed_share", ctx(setup_s=30.0), 0.0),
    ("compile_seconds_in_window", ctx(), 0.75),
    ("compile_seconds_in_window", ctx(after=BEFORE), 0.0),
    ("compile_seconds_in_window", ctx(before=BARE, after=BARE), None),
]


@pytest.mark.parametrize(
    "name,context,expected", CASES,
    ids=[f"{i}-{n}" for i, (n, _, _) in enumerate(CASES)])
def test_setup_reader(name, context, expected):
    got = bench.load_reader("layer_metrics", name)(context)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_the_seven_are_in_every_cell_and_all_but_one_move_setup_s():
    names = {c[0] for c in CASES}
    assert len(names) == 7
    b = bench.load_json(ROOT, "BENCHMARK.json")
    mine = [m for m in b["per_layer"] if m["name"] in names]
    assert {m["name"] for m in mine} == names
    for m in mine:
        assert "workloads" not in m and m["layer"] == "Engine"
        assert m["moves"] == ("itl_p95_ms" if m["name"]
                              == "compile_seconds_in_window" else "setup_s")
