"""The latent-attention sparse family (openPangu-Ultra-MoE,
`pangu_ultra_moe`) on the CPU at a small size, seeded weights: the
program (latent rows in the slab, the absorbed decode, a share of the
experts) against the benchmark's plain reference
(`benchmark/reference/latent_moe.py`, which absorbs nothing and keeps
no cache), the two kernels in interpret mode against the einsum path,
the shares of the experts against the uncut layer, and what the
engine and the configuration do with the model."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine.core import InferenceEngine
from ome_tpu.models import llama, mla
from ome_tpu.models.checkpoint import (SUPPORTED_ARCHITECTURES,
                                       unsupported_architectures)
from ome_tpu.models.config import ModelConfig
from ome_tpu.ops import attention as ops
from ome_tpu.ops import flash

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import latent_moe as ref  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the family at toy widths: a cached row of 128 + 64 numbers (padded to
# 256 lanes), 8 heads, one leading dense layer and two expert layers
# that hold experts 2..5 of 8, top-3
HF = dict(
    architectures=["PanguUltraMoEForCausalLM"], model_type="pangu_ultra_moe",
    hidden_size=64, intermediate_size=160, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=8, num_key_value_heads=8,
    q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=2.5, sandwich_norm=True,
    num_nextn_predict_layers=1, rms_norm_eps=1e-5, rope_theta=25600000,
    vocab_size=512, max_position_embeddings=512, tie_word_embeddings=False,
    ep_num_experts_total=8, ep_expert_offset=2)

# float32 at "highest" precision on both sides: what is left is the
# order of float32 sums (the program scores a key through the absorbed
# query, the reference through a materialised key; a grouped matmul
# against a masked sum over every held expert). Read 5e-7 on logits of
# 0.6 at most; 2e-5 leaves forty times that and is a hundredth of what
# a latent row rounded to int8 moves a logit by (read 3e-3)
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF).replace(
        moe_impl="ragged", dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, ref.init_weights(HF, dtype=jnp.float32)


def _reference(w, seq):
    return np.asarray(ref.logits(w, HF, np.asarray(seq, np.int32), 0,
                                 len(seq)))


def _slab(cfg, batch, rows, merged=True):
    cache = llama.KVCache.create(cfg, batch, rows, merged=merged)
    return dataclasses.replace(cache, index=jnp.zeros((batch,), jnp.int32),
                               stats=jnp.zeros((3,), jnp.uint32))


def _through_the_slab(cfg, params, seqs, lens, steps, rows=64,
                      merged=True, spoil=None):
    """Prompts `seqs[b][:lens[b]]` prefilled in ONE padded batch, then
    `steps` decode steps a slot, each slot at its own length; returns
    for every slot its logits at the prompt's last position and at
    each step. `spoil` rewrites the slab between the two."""
    B, S = len(seqs), max(lens)
    toks = np.zeros((B, S), np.int32)
    for b, (seq, n) in enumerate(zip(seqs, lens)):
        toks[b, :n] = seq[:n]
    with jax.default_matmul_precision("highest"):
        lg, cache = llama.forward(params, cfg, jnp.asarray(toks),
                                  cache=_slab(cfg, B, rows, merged))
        out = [[np.asarray(lg[b, n - 1])] for b, n in enumerate(lens)]
        # a slot's length is its prompt's: rows behind it are padding,
        # overwritten before any query sees them
        cache = dataclasses.replace(cache, index=jnp.asarray(lens, jnp.int32))
        if spoil is not None:
            cache = dataclasses.replace(cache, k=spoil(cache.k))
        for t in range(steps):
            step = np.asarray([[seq[n + t]] for seq, n in zip(seqs, lens)],
                              np.int32)
            lg, cache = llama.forward(params, cfg, jnp.asarray(step),
                                      cache=cache)
            for b in range(B):
                out[b].append(np.asarray(lg[b, 0]))
    return [np.stack(o) for o in out], cache


def _seqs(n, length, seed=0):
    return np.random.RandomState(seed).randint(0, HF["vocab_size"],
                                               (n, length)).tolist()


# -- the program against the reference ------------------------------------


def test_a_full_pass_is_the_references(model):
    cfg, params, w = model
    seq = _seqs(1, 48)[0]
    with jax.default_matmul_precision("highest"):
        lg, _ = llama.forward(params, cfg, jnp.asarray([seq], jnp.int32))
    want = _reference(w, seq)
    assert float(np.std(want)) > 0.05
    np.testing.assert_allclose(np.asarray(lg[0]), want, atol=TOL)


@pytest.mark.parametrize("merged", [True, False])
def test_prefill_then_decode_through_the_slab_is_the_references(model,
                                                                merged):
    """A prompt's logits, then sixteen steps of the absorbed decode
    path over the rows the prompt left in the slab, against the
    reference's ONE full pass over the same tokens; rows merged (the
    engine's slab) and with the one latent head apart."""
    cfg, params, w = model
    seq = _seqs(1, 48, seed=1)[0]
    (got,), cache = _through_the_slab(cfg, params, [seq], [32], 16,
                                      merged=merged)
    np.testing.assert_allclose(got, _reference(w, seq)[31:], atol=TOL)
    assert cache.k.shape[3:] == ((256,) if merged else (1, 256))
    assert cache.v.shape[-1] == 0
    assert [int(x) for x in cache.index] == [48]


def test_slots_at_mixed_lengths_decode_in_one_batch(model):
    """Three slots whose prompts end at 32, 17 and 5 rows, prefilled in
    one padded batch and decoded together, every slot at its own
    length: each is its own sequence's reference."""
    cfg, params, w = model
    seqs, lens = _seqs(3, 44, seed=2), [32, 17, 5]
    got, cache = _through_the_slab(cfg, params, seqs, lens, 12)
    for b, n in enumerate(lens):
        want = _reference(w, seqs[b][:n + 12])[n - 1:]
        np.testing.assert_allclose(got[b], want, atol=TOL, err_msg=str(b))
    assert [int(x) for x in cache.index] == [44, 29, 17]
    # 2 expert layers x 12 steps; the prefill's slab counted one pass
    assert int(cache.stats[0]) == 2 * 13


def test_latent_rows_held_in_int8_fail_the_tolerance(model):
    """The comparison is tight enough to tell the rows' precision: the
    same decode over a slab whose rows were rounded to int8 (one scale
    a row) misses `TOL` by two orders."""
    cfg, params, w = model
    seq = _seqs(1, 48, seed=1)[0]

    def int8_rows(k):
        scale = jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(k / scale).clip(-127, 127) * scale

    (got,), _ = _through_the_slab(cfg, params, [seq], [32], 16,
                                  spoil=int8_rows)
    err = np.abs(got - _reference(w, seq)[31:]).max()
    assert err > 50 * TOL, err


def test_the_row_is_padded_to_whole_lane_tiles(model):
    cfg, _, _ = model
    assert cfg.kv_cache_k_dim == 256 and cfg.kv_cache_v_dim == 0
    real = cfg.replace(kv_lora_rank=512, qk_rope_head_dim=64)
    assert real.kv_cache_k_dim == 640
    # a row under one tile stays as it is (tests/test_mla.py's 32 + 8)
    assert cfg.replace(kv_lora_rank=32,
                       qk_rope_head_dim=8).kv_cache_k_dim == 40


def test_the_padding_lanes_stay_zero(model):
    cfg, params, _ = model
    seq = _seqs(1, 40, seed=3)[0]
    _, cache = _through_the_slab(cfg, params, [seq], [32], 8)
    k = np.asarray(cache.k)
    assert np.abs(k[..., :192]).max() > 0
    assert not k[..., 192:].any()


# -- the kernels against the einsum path ----------------------------------


def _rand(*shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


@pytest.mark.parametrize("lo,hi,what", [
    ([0, 0, 0], [1, 130, 256], "one row, off the block size, a full slab"),
    ([0, 100, 200], [37, 230, 256], "lo > 0"),
    ([0, 128, 5], [0, 256, 6], "an empty slot, whole blocks, one row"),
])
@pytest.mark.parametrize("lanes", [192, 256])
def test_latent_decode_kernel_is_the_einsum_path(lo, hi, what, lanes):
    B, H, rank, rope, S, L = 3, 8, 128, 64, 256, 3
    q_lat, q_pe = _rand(B, H, rank), _rand(B, H, rope, seed=1)
    slab = _rand(L, B, S, lanes, seed=2)
    lo, hi = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)
    for layer in (0, 2):
        got = flash.latent_decode(q_lat, q_pe, slab, lo, hi, scale=0.07,
                                  layer=jnp.asarray(layer, jnp.int32),
                                  interpret=True)
        want = ops.xla_latent_decode(q_lat, q_pe, slab[layer], lo, hi, 0.07)
        live = np.asarray(hi > lo)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=2e-5,
                                   err_msg=what)
        assert not np.asarray(got)[~live].any()
    one = flash.latent_decode(q_lat, q_pe, slab[1], lo, hi, scale=0.07,
                              interpret=True)
    np.testing.assert_allclose(
        np.asarray(one)[live],
        np.asarray(ops.xla_latent_decode(q_lat, q_pe, slab[1], lo, hi,
                                         0.07))[live], atol=2e-5)


def test_latent_decode_dispatch_reads_up_to_the_position():
    """`ops.latent_decode`: a slot's rows 0 .. position, whichever path
    runs; the stacked slab by layer index, rows apart or merged."""
    B, H, rank, rope, S, L = 2, 8, 128, 64, 128, 2
    q_lat, q_pe = _rand(B, H, rank), _rand(B, H, rope, seed=1)
    slab = _rand(L, B, S, 1, 256, seed=2)           # the latent head apart
    pos = jnp.asarray([[40], [127]], jnp.int32)
    kw = dict(rank=rank, scale=0.07, layer=jnp.asarray(1, jnp.int32))
    a = ops.latent_decode(q_lat, q_pe, slab, pos, pos[:, 0] + 1,
                          backend="xla", **kw)
    b = ops.latent_decode(q_lat, q_pe, slab, pos, None,
                          backend="pallas_interpret", **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    want = ops.xla_latent_decode(q_lat, q_pe, slab[1, :, :, 0],
                                 jnp.zeros((B,), jnp.int32),
                                 pos[:, 0] + 1, 0.07)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), atol=1e-6)


def test_latent_decode_declines_what_it_does_not_cover():
    ok = dict(scale=1.0, interpret=True)
    lim = jnp.zeros((2,), jnp.int32)
    rows = jnp.zeros((2, 128, 192))
    assert flash.latent_decode(jnp.zeros((2, 8, 128)), jnp.zeros((2, 8, 64)),
                               rows, lim, lim + 1, **ok) is not None
    for q_lat, q_pe, r in (
            (jnp.zeros((2, 6, 128)), jnp.zeros((2, 6, 64)), rows),   # heads
            (jnp.zeros((2, 8, 96)), jnp.zeros((2, 8, 64)), rows),    # rank
            (jnp.zeros((2, 8, 128)), jnp.zeros((2, 8, 64)),
             jnp.zeros((2, 100, 192))),                              # rows
            (jnp.zeros((2, 8, 128)), jnp.zeros((2, 8, 64)),
             jnp.zeros((2, 128, 160)))):                             # lanes
        assert flash.latent_decode(q_lat, q_pe, r, lim, lim + 1,
                                   **ok) is None


@pytest.mark.parametrize("H,Sq,S,base,kv_hi,cached", [
    (8, 128, 128, [0, 0], [128, 100], True),     # a fresh prompt
    (8, 64, 256, [0, 150], [64, 214], True),     # a chunk atop cached rows
    (2, 128, 128, [0, 0], None, False),          # no cache, two heads a step
    (1, 48, 48, [0, 0], None, False),            # one head, blocks of 16
])
def test_latent_prefill_kernel_is_the_einsum_path(H, Sq, S, base, kv_hi,
                                                  cached):
    nope, rope, dv = 128, 64, 128
    q_nope, q_pe = _rand(2, H, Sq, nope), _rand(2, H, Sq, rope, seed=1)
    k_nope, k_pe = _rand(2, H, S, nope, seed=2), _rand(2, S, rope, seed=3)
    v = _rand(2, H, S, dv, seed=4)
    positions = jnp.asarray(base, jnp.int32)[:, None] + jnp.arange(Sq)[None]
    kv_len = jnp.asarray(kv_hi, jnp.int32) if cached else None
    args = (q_nope, q_pe, k_nope, k_pe, v, positions, kv_len)
    got = ops.latent_prefill(*args, scale=0.07, backend="pallas_interpret")
    want = ops.latent_prefill(*args, scale=0.07, backend="xla")
    assert got.shape == (2, H, Sq, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_latent_prefill_blocks_and_head_groups():
    # the cell's buckets: blocks of 512 x 512, four heads a grid step
    assert flash._latent_prefill_blocks(16384, 16384, 32) == (512, 512, 4)
    assert flash._latent_prefill_blocks(48, 48, 1) == (16, 16, 1)
    assert flash._latent_prefill_blocks(100, 128, 8) is None
    # heads materialised at a time: keys of a group under 128 MiB
    assert [mla._prefill_head_group(s, 128, 128)
            for s in (16384, 8192, 4096, 64)] == [32, 64, 128, 128]
    assert mla._prefill_head_group(1 << 20, 6, 128) == 3


def test_a_grouped_prompt_is_the_ungrouped_one(model, monkeypatch):
    """A long prompt's heads are materialised a group at a time
    (`lax.map` over groups, the output projection over the stacked
    groups): the same logits as all heads at once."""
    cfg, params, _ = model
    seq = jnp.asarray(_seqs(1, 32, seed=4), jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole, _ = llama.forward(params, cfg, seq)
        monkeypatch.setattr(mla, "_PREFILL_GROUP_BYTES", 32 * 2 * 128 * 2)
        assert mla._prefill_head_group(32, 8, 128) == 2
        grouped, _ = llama.forward(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(whole),
                               atol=TOL)


# -- a share of the experts ------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(model):
    """model-configs guide, section 4: the routed parts that the eight
    shares of one expert each give (the program's expert layer, told
    which expert it holds, routing over all eight), with the shared
    expert counted once, add up to what the uncut reference gives for
    the whole layer."""
    cfg, _, _ = model
    uncut = dict(HF, n_routed_experts=8, ep_expert_offset=0)
    w = ref.init_weights(uncut, dtype=jnp.float32)["moe"]
    u = _rand(1, 24, 64, seed=5)
    with jax.default_matmul_precision("highest"):
        want = ref._moe_ffn(u[0], w, 1, top_k=3, lo=0, route_scale=2.5,
                            int8=False)
        total = llama.dense_mlp(u, {"w_gate": w["ws_gate"][1],
                                    "w_up": w["ws_up"][1],
                                    "w_down": w["ws_down"][1]})
        hit = 0
        for e in range(8):
            share = cfg.replace(num_experts=1, expert_offset=e,
                                num_shared_experts=0)
            p = {"router": w["router"][1],
                 **{n: w[n][1, e:e + 1]
                    for n in ("we_gate", "we_up", "we_down")}}
            part, (experts, pairs) = llama.moe_mlp(u, p, share,
                                                   with_stats=True)
            total = total + part
            hit += int(pairs)
    assert hit == 24 * 3                    # every routed pair, once
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=TOL)


def test_a_share_routes_over_all_and_computes_its_own(model):
    """The held range masks what lands here: with experts 2..5 of 8
    held, only pairs routed to them are computed (the counters say how
    many), and the router stays 8 wide."""
    cfg, params, _ = model
    assert (cfg.num_experts, cfg.router_width, cfg.expert_offset) == (4, 8, 2)
    assert params["layers"]["router"].shape == (2, 64, 8)
    assert params["layers"]["we_gate"].shape == (2, 4, 64, 32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    u = _rand(1, 40, 64, seed=6)
    _, (experts, pairs) = llama.moe_mlp(u, lp, cfg, with_stats=True)
    _, idx = llama._route(u, lp, cfg)
    mine = (np.asarray(idx) >= 2) & (np.asarray(idx) < 6)
    assert int(pairs) == mine.sum() and 0 < int(pairs) < 40 * 3
    assert int(experts) == len(set(np.asarray(idx)[mine].tolist()))


def test_a_long_prompts_pairs_run_in_token_chunks(model, monkeypatch):
    """Past `_MOE_PAIRS_LIMIT` bytes of gathered pairs an expert layer
    runs its tokens in chunks; the same result, and the accepted
    cells' prompts stay whole."""
    cfg, params, _ = model
    # trinity-mini-ep4 / smallthinker / qwen3-next at their largest
    # bucket: under the limit, one chunk; this model's 16 384: four
    assert llama._moe_token_chunks(16384, 8, 2048, 2) == 1
    assert llama._moe_token_chunks(16384, 6, 2560, 2) == 1
    assert llama._moe_token_chunks(4096, 10, 2048, 2) == 1
    assert llama._moe_token_chunks(16384, 8, 7680, 2) == 4
    assert llama._moe_token_chunks(8192, 8, 7680, 2) == 1
    assert llama._moe_token_chunks(24, 8, 7680, 2) == 1
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    u = _rand(1, 64, 64, seed=7)
    with jax.default_matmul_precision("highest"):
        whole, (e0, p0) = llama.moe_mlp(u, lp, cfg, with_stats=True)
        monkeypatch.setattr(llama, "_MOE_PAIRS_LIMIT", 1 << 12)
        monkeypatch.setattr(llama, "_MOE_PAIRS_CHUNK", 1 << 12)
        assert llama._moe_token_chunks(64, 3, 64, 4) == 16
        chunked, (e1, p1) = llama.moe_mlp(u, lp, cfg, with_stats=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               atol=1e-6)
    assert int(p1) == int(p0) and int(e1) >= int(e0)


# -- configuration, engine, names -----------------------------------------


def test_from_hf_reads_the_catalog_rows_config():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    cfg = ModelConfig.from_hf_config(
        dict(row["config"], architectures=["PanguUltraMoEForCausalLM"]))
    assert cfg.mla and cfg.post_block_norms and not cfg.router_bias
    assert (cfg.num_layers, cfg.first_k_dense, cfg.hidden_size,
            cfg.num_heads) == (61, 3, 7680, 128)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts,
            cfg.moe_intermediate_size, cfg.intermediate_size) == (
                256, 8, 1, 2048, 18432)
    assert (cfg.router_scoring, cfg.norm_topk_prob, cfg.n_group,
            cfg.routed_scaling_factor) == ("sigmoid_v3", True, 0, 2.5)
    assert cfg.rope_theta == 25600000 and cfg.rope_scaling is None
    assert cfg.mla_scale == 192 ** -0.5 and cfg.kv_cache_k_dim == 640
    assert cfg.num_experts_total == 0 and cfg.router_width == 256
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 153600


@pytest.mark.parametrize("key,value,what", [
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("n_group", 8, "group-limited"),
    ("scoring_func", "softmax", "scoring_func"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("q_lora_rank", None, "q_lora_rank"),
])
def test_from_hf_refuses_what_is_not_written(key, value, what):
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config(dict(HF, **{key: value}))


def test_the_architecture_is_served_by_name():
    assert "PanguUltraMoEForCausalLM" in SUPPORTED_ARCHITECTURES
    assert unsupported_architectures(HF) == []


def test_the_engine_refuses_the_pool_with_todays_reason(model):
    cfg, params, _ = model
    with pytest.raises(ValueError, match=r"paged KV \(--kv-block\) serves "
                       r"standard rmsnorm GQA models.*latent attention "
                       r"\(MLA\), sparse experts, leading dense layers"):
        InferenceEngine(params, cfg, max_slots=2, max_seq=128, kv_block=128)


def test_the_engine_serves_it_and_counts_its_experts(model):
    """Prefill, insert and decode through the engine's own programs:
    the slab is merged padded rows, greedy tokens are the full pass's,
    and the expert counters move (`llama.counts_experts`: a share
    under the ragged dispatch, whatever the family)."""
    cfg, params, _ = model
    assert llama.counts_experts(cfg)
    assert not llama.counts_experts(cfg.replace(moe_impl="dense"))
    eng = InferenceEngine(params, cfg, max_slots=2, max_seq=64,
                          prefill_buckets=[16, 32, 64])
    assert eng.kv_rows_merged and eng._counts_experts
    prompt = _seqs(1, 20, seed=8)[0]
    tok, kv, n, bucket = eng.prefill(prompt)
    assert kv[0].shape == (3, 1, 32, 256) and kv[1].shape == (3, 1, 32, 0)
    state = eng.new_state()
    assert state.k.shape == (3, 2, 64, 256)
    state = eng.insert(state, kv, 1, n, tok, bucket)
    served = [tok]
    for _ in range(6):
        state, toks = eng.decode(state, np.zeros(2, np.float32),
                                 np.zeros(2, np.int32), np.ones(2, np.float32))
        served.append(int(np.asarray(toks)[1]))
    full, _ = llama.forward(params, cfg,
                            jnp.asarray([prompt + served[:-1]], jnp.int32))
    want = [int(t) for t in np.asarray(full[0]).argmax(-1)[19:]]
    assert served == want
    counts = eng.moe_counters()
    assert counts["layer_steps"] == 2 * 6 and counts["pairs"] > 0
    assert eng.kv_row_bytes() == 3 * 256 * 4        # float32 here


def test_the_names_and_the_tenant():
    from ome_tpu.perf.hbm import HBM_TENANTS
    from ome_tpu.telemetry import scopes
    assert "attn_latent" in scopes.SUBPHASES
    assert scopes.SUBKERNELS == ("latent_decode", "latent_prefill")
    assert not set(scopes.SUBKERNELS) & set(scopes.KERNELS)
    assert "latent_rows" in HBM_TENANTS


def test_a_decode_step_writes_the_names(model):
    """The scope around a latent layer's cache write and attention,
    inside `layers`, and the phases around it, as the traced program
    carries them."""
    cfg, params, _ = model
    cache = _slab(cfg, 2, 64)
    import re
    text = jax.jit(lambda p, t, c: llama.forward(p, cfg, t, cache=c)) \
        .lower(params, jnp.zeros((2, 1), jnp.int32), cache).compile() \
        .as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("/layers/while/body/closed_call/attn_latent/kv_write/",
                  "/layers/while/body/closed_call/attn_latent/attn/",
                  "/layers/while/body/closed_call/qkv/",
                  "/layers/while/body/closed_call/o_proj/",
                  "/mlp/moe_experts/", "/mlp/moe_shared/",
                  "/mlp/moe_router/"):
        assert any(scope in p for p in paths), scope
    # the phases stay the deepest names `phases.py` knows
    assert not any("/attn/attn_latent" in p or "/kv_write/attn_latent" in p
                   for p in paths)


def test_the_hbm_accountant_books_the_slab_as_latent_rows(model):
    from ome_tpu.perf.hbm import HbmAccountant
    from ome_tpu.telemetry import Registry
    cfg, params, _ = model
    eng = InferenceEngine(params, cfg, max_slots=2, max_seq=64,
                          prefill_buckets=[64])
    acct = HbmAccountant(Registry(), weight_bytes=1000,
                         stats_fn=lambda: None)
    part = acct.update(eng)
    assert part["kv_cache"] == 0
    assert part["latent_rows"] == 3 * 2 * 64 * 256 * 4
