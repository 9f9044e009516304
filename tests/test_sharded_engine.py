"""Sharded serving: tp engine on the CPU test mesh matches single-device.

The VERDICT's acceptance test for sharded serving: batched decode on
an 8-CPU mesh with tp=2 must match the single-device engine
token-for-token (greedy), through the real prefill -> insert -> decode
slot machinery.
"""

import jax
import numpy as np
import pytest

from ome_tpu.engine.core import InferenceEngine
from ome_tpu.engine.sharded import ShardedInferenceEngine
from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test


def _greedy_run(engine, prompts, steps=12):
    state = engine.new_state()
    outs = []
    for slot, prompt in enumerate(prompts):
        tok, kv, true_len, bucket = engine.prefill(prompt)
        state = engine.insert(state, kv, slot, true_len, tok, bucket)
        outs.append([tok])
    B = engine.max_slots
    temp = np.zeros(B, np.float32)
    top_k = np.zeros(B, np.int32)
    top_p = np.ones(B, np.float32)
    for _ in range(steps):
        state, toks = engine.decode(state, temp, top_k, top_p)
        toks = np.asarray(toks)
        for slot in range(len(prompts)):
            outs[slot].append(int(toks[slot]))
    return outs


def test_tp2_decode_matches_single_device():
    # fp32: bf16 logit margins on random tiny weights are thinner than
    # the tp reduction-order jitter, which flips greedy argmax ties
    import jax.numpy as jnp
    cfg = tiny_test().replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13, 14, 15, 16, 17]]

    single = InferenceEngine(params, cfg, max_slots=4, max_seq=64)
    ref = _greedy_run(single, prompts)

    sharded = ShardedInferenceEngine(params, cfg, tp=2, max_slots=4,
                                     max_seq=64)
    got = _greedy_run(sharded, prompts)
    assert got == ref


def test_tp2_moe_logits_match_single_device():
    # MoE in bf16 flips greedy ties on reduction order; assert logits
    # equivalence in f32 instead (experts sharded on the tp/ep axis)
    import jax.numpy as jnp
    from ome_tpu.parallel.mesh import MeshConfig, build_mesh
    from ome_tpu.parallel.sharding import shard_params

    cfg = tiny_test(moe=True).replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    ref, _ = jax.jit(lambda p, t: llama.forward(p, cfg, t))(params, tok)
    sharded = shard_params(params, build_mesh(MeshConfig(tp=2)))
    got, _ = jax.jit(lambda p, t: llama.forward(p, cfg, t))(sharded, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_tp_requires_divisible_heads():
    cfg = tiny_test()  # 8 heads, 4 kv heads
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="num_kv_heads"):
        ShardedInferenceEngine(params, cfg, tp=3)


def test_tp4_kv_head_sharding_layout():
    cfg = tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ShardedInferenceEngine(params, cfg, tp=4, max_slots=2, max_seq=32)
    state = eng.new_state()
    # KV cache must actually be laid out split over tp on the head dim:
    # the merged [.., K * Dh] axis, a chip's heads contiguous lanes of it
    shard_shapes = {s.data.shape for s in state.k.addressable_shards}
    K = cfg.num_kv_heads
    assert state.k.shape[3:] == (K * cfg.head_dim,)
    assert all(sh[3:] == (K // 4 * cfg.head_dim,) for sh in shard_shapes)


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_shards_the_out_major_projections_on_their_heads(tp):
    """`wq` / `wk` / `wv` lie [L, heads, Dh, D] (llama._proj) and `tp`
    stays on the heads, now their first dim behind the layers: a chip
    holds whole heads' rows, the sharded forward gives the single
    device's logits, and those are the parent form's (the same leaves
    put back [L, D, heads, Dh] and contracted in-major)."""
    import jax.numpy as jnp
    from _parent_proj import parent_form
    from ome_tpu.parallel.mesh import MeshConfig, build_mesh
    from ome_tpu.parallel.sharding import shard_params

    cfg = tiny_test().replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    sharded = shard_params(params, build_mesh(MeshConfig(tp=tp)))
    for name, heads in (("wq", cfg.num_heads), ("wk", cfg.num_kv_heads),
                        ("wv", cfg.num_kv_heads)):
        leaf = sharded["layers"][name]
        assert leaf.shape[1:] == (heads, cfg.head_dim, cfg.hidden_size)
        assert {s.data.shape[1:] for s in leaf.addressable_shards} \
            == {(heads // tp, cfg.head_dim, cfg.hidden_size)}
    tok = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    ref, _ = jax.jit(lambda p, t: llama.forward(p, cfg, t))(params, tok)
    got, _ = jax.jit(lambda p, t: llama.forward(p, cfg, t))(sharded, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    with parent_form():
        want, _ = jax.jit(lambda p, t: llama.forward(p, cfg, t))(
            sharded, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
