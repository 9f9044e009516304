"""CPU rehearsal of chip_smoke.py.

`--rehearse-cpu` is a switch of the script, not of the engine: a toy
config on the CPU backend drives every phase of the real control flow
(router + server children, request mix, /debug/programs evidence, the
reference-logit child, the int4 + int8-KV pass), and the verdict still
demands a TPU. So the run must end `"ok": false` with a non-zero exit
on the device check while every earlier phase passes — which proves
both that the control flow works and that the script cannot pass
without a chip.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script, *args, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    return proc.returncode, lines, proc.stderr


def test_rehearsal_passes_every_phase_and_fails_the_device_check():
    rc, lines, err = _run(SMOKE, "--rehearse-cpu")
    assert rc != 0, "the smoke passed without a chip"
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}, (lines, err)
    served = {ln["phase"]: ln for ln in lines
              if ln.get("event") == "served"}
    assert set(served) == {"serve-bf16", "serve-int4-kvint8"}
    # every request answered in full, continuous batching exercised,
    # nothing compiled inside the served window
    assert served["serve-bf16"]["requests"] == 10
    assert served["serve-bf16"]["tokens_per_decode_step"] > 1
    assert served["serve-bf16"]["programs_compiled_in_window"] == 0
    results = {ln["phase"]: ln for ln in lines
               if ln.get("event") == "result"}
    assert set(results) == {"reference-bf16", "reference-int4-kvint8"}
    for res in results.values():
        assert res["ok"] and set(res["checks"]) >= {"decode"}
    # the one and only failure is the device check, and it is last
    failed = [ln for ln in lines if ln.get("phase") == "failed"]
    assert len(failed) == 1 and failed[0] is lines[-2]
    assert "the smoke needs" in failed[0]["error"]


def test_alone_in_a_directory_it_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the serving child cannot start, and the script says so with
    ok=false instead of hanging or passing."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    rc, lines, err = _run(str(tmp_path / "chip_smoke.py"),
                          "--rehearse-cpu", cwd=str(tmp_path))
    assert rc != 0
    assert lines[-1] == {"ok": False, "device": None}, (lines, err)
    assert "No module named" in lines[-2]["error"]


def test_size_pool_counts_the_pool_once():
    """The decode program carries the donated pool and keeps no second
    one, so the sizing counts it once: at Qwen3-4B's size the dense
    equivalent of 16 slots of 2048 fits, what it adds up stays inside
    the compiler's HBM, and a second pool would not."""
    import chip_smoke
    cfg = chip_smoke.QWEN3_4B
    sizes = chip_smoke.size_pool(cfg, 16, 2048, 128)
    assert sizes["kv_blocks"] == sizes["dense_equivalent_blocks"] + 1 \
        == 257
    row = (cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
           * 2 * cfg["head_dim"] * 2)
    pool = sizes["kv_blocks"] * 128 * row
    held = (2 * sizes["expected_params"] + pool + 2 * 2048 * row
            + (256 << 20) + (512 << 20))
    assert held <= chip_smoke.V5E_HBM_BYTES < held + pool
