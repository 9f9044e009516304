"""Sharding-efficiency proxy (round-2 review weak #4): the compiled
tp-sharded decode step's collectives must stay ACTIVATION-sized. CPU
correctness tests can't see layout regressions — a sharding mistake
that makes GSPMD all-gather a weight (or the KV cache) per step would
still produce right answers, just 10-100x slower on a real slice. The
compiled HLO's collective shapes catch it.
"""

import re

import jax
import numpy as np
import pytest

from ome_tpu.engine.sharded import ShardedInferenceEngine
from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test

# HLO line: %name = f32[4,1,128]{2,1,0} all-reduce(...), or a tuple
# result (s32[...], s32[...]) all-to-all(...)
_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "f16": 2,
          "pred": 1, "s64": 8, "u8": 1}


def _collectives(hlo_text):
    out = []
    for line in hlo_text.splitlines():
        op = next((o for o in _OPS if f" {o}(" in line), None)
        if op is None or "=" not in line:
            continue
        result = line.split("=", 1)[1].split(f" {op}(", 1)[0]
        nbytes = 0
        for dtype, dims in _SHAPE.findall(result):
            n = int(np.prod([int(d) for d in dims.split(",") if d])) \
                if dims else 1
            nbytes += n * _BYTES.get(dtype, 4)
        out.append((op, result.strip(), nbytes))
    return out


@pytest.fixture(scope="module")
def decode_hlo():
    cfg = tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ShardedInferenceEngine(params, cfg, tp=2, max_slots=4,
                                 max_seq=64)
    state = eng.new_state()
    import jax.numpy as jnp
    lowered = eng.programs["decode"].lower(
        eng.params, state, np.zeros(4, np.float32),
        np.zeros(4, np.int32), np.ones(4, np.float32),
        jax.random.PRNGKey(0))
    return lowered.compile().as_text(), cfg, eng


def test_decode_collectives_are_activation_sized(decode_hlo):
    """No per-step collective may move more than a few activations'
    worth of bytes: weights are ~L*D*F*4 and the KV cache ~L*B*S*K*Dh*4
    — if either shows up in a collective, the tp layout regressed."""
    hlo, cfg, eng = decode_hlo
    colls = _collectives(hlo)
    assert colls, "tp=2 decode must have cross-device reductions"
    # generous activation budget: batch x hidden x 32 (covers fused
    # variants + vocab-dim logit reductions), far below any weight
    act_budget = eng.max_slots * cfg.vocab_size * 4 * 8
    weight_bytes = (cfg.num_layers * cfg.hidden_size
                    * cfg.intermediate_size * 4)
    assert act_budget < weight_bytes  # the test must be able to fail
    for op, shape, nbytes in colls:
        assert nbytes <= act_budget, (
            f"{op} of {nbytes} bytes ({shape}) in the decode step — "
            f"weight- or cache-sized collective, tp layout regressed")


def test_decode_has_no_weight_allgather(decode_hlo):
    """The Megatron layout needs only psum-style reductions after
    o-proj / down-proj; a weight all-gather means a param lost its
    sharding annotation."""
    hlo, cfg, eng = decode_hlo
    gathers = [c for c in _collectives(hlo) if c[0] == "all-gather"]
    per_layer_w = cfg.hidden_size * cfg.intermediate_size * 4
    for op, shape, nbytes in gathers:
        assert nbytes < per_layer_w / 2, (
            f"all-gather of {nbytes} bytes ({shape}) looks weight-sized")
