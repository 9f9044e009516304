"""PowerInfer SmallThinker on the serve path: a period that OPENS with
its rotary-free global layer, window layers that keep a RING of
`sliding_window_size` KV rows a slot, a router that reads the layer's
INPUT before attention, ReLU-gated experts of which a share is held,
7 query heads a KV head.

Small sizes with the true ratios (window 8, period 4 with the global
layer first, 2 periods, 8 experts top-3 of which 4 are held, 7 heads
on 1 KV head): the program in float32 against the benchmark's plain
reference (benchmark/reference/preroute_moe.py, which imports nothing
of the program and keeps no ring); prefill then decode through slab
and ring against one full pass, with the ring wrapping during prefill
and during DECODE; the period's phase; the four EP-4 shares adding up
to the uncut layer; routing decided ahead of attention against
routing decided behind it; each term of the block shown to matter;
the families that shared the period scan before, to the bit; the
kernels at a group of 7; what start-up refuses.

Tolerances: float32 under "highest" precision on both sides, logits of
standard deviation 0.16: 5e-5 absolute is a few float32 roundings
through 8 layers (readings up to 3e-7), and under a hundredth of what
a bfloat16 pass differs by, so a lower precision fails every one of
them (`test_the_program_in_bfloat16_would_fail_the_tolerance`).
"""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine import core
from ome_tpu.engine.core import InferenceEngine
from ome_tpu.models import llama
from ome_tpu.models.config import ModelConfig, tiny_test

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import preroute_moe as ref  # noqa: E402

from _parent_proj import parent_form, stored  # noqa: E402

W = 8
HF = dict(
    architectures=["SmallThinkerForCausalLM"], model_type="smallthinker",
    hidden_size=64, num_hidden_layers=8, num_attention_heads=7,
    num_key_value_heads=1, head_dim=16, vocab_size=256,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    sliding_window_size=W, sliding_window_layout=[0, 1, 1, 1] * 2,
    rope_layout=[0, 1, 1, 1] * 2, rope_theta=1500000, rope_scaling=None,
    rms_norm_eps=1e-6, tie_word_embeddings=False,
    max_position_embeddings=512)
# the chip's share: experts 2..5 of 8 held, the router 8 wide
CUT = dict(HF, moe_num_primary_experts=4, ep_num_experts_total=8,
           ep_expert_offset=2)
ATOL = 5e-5


def _cfg(hf):
    return ModelConfig.from_hf_config(hf).replace(
        dtype=jnp.float32, moe_impl="ragged")


def _params(cfg):
    return jax.jit(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))()


@pytest.fixture(scope="module")
def cut():
    cfg = _cfg(CUT)
    return cfg, _params(cfg), ref.init_weights(CUT, jnp.float32)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, HF["vocab_size"], n)


def _per_slot(cache):
    return dataclasses.replace(
        cache, index=jnp.zeros((cache.k.shape[1],), jnp.int32))


# -- config.json -------------------------------------------------------


# PowerInfer/SmallThinker-21BA3B-Instruct's published config.json (the
# model-configs catalog's row of it, every key), so that the test runs
# wherever the checkout does
PUBLISHED = dict(
    head_dim=128, hidden_size=2560, max_position_embeddings=16384,
    model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
    moe_num_active_primary_experts=6, moe_num_primary_experts=64,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_attention_heads=28, num_hidden_layers=52, num_key_value_heads=4,
    rms_norm_eps=1e-06, rope_layout=[0, 1, 1, 1] * 13, rope_scaling=None,
    rope_theta=1500000, sliding_window_layout=[0, 1, 1, 1] * 13,
    sliding_window_size=4096, tie_word_embeddings=False,
    vocab_size=151936)


def test_the_published_config_parses_to_the_published_shape():
    """52 layers in 13 periods that OPEN with the global layer, 64
    experts top-6 of 768, window 4096, 28 heads on 4, and every term
    of the block that has a field. Where the catalog is at hand, the
    copy above is held to its row."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["config"] == PUBLISHED
    cfg = ModelConfig.from_hf_config(
        dict(PUBLISHED, architectures=["SmallThinkerForCausalLM"]))
    assert (cfg.num_layers, cfg.num_experts, cfg.experts_per_token,
            cfg.moe_intermediate_size, cfg.sliding_window,
            cfg.sliding_pattern, cfg.global_phase) \
        == (52, 64, 6, 768, 4096, 4, 0)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.max_seq_len) \
        == (2560, 28, 4, 128, 151936, 16384)
    assert cfg.alt_sliding_window and cfg.rope_skip_global
    assert cfg.window_ring and cfg.router_pre_attn
    assert (cfg.router_scoring, cfg.moe_activation) == ("mixtral", "relu")
    assert cfg.rope_theta == 1500000 and cfg.rms_norm_eps == 1e-6
    assert (cfg.window_layers, cfg.kv_cache_layers) == (39, 13)
    assert cfg.attn_layer_windows == ((4096, 39), (None, 13))
    assert not (cfg.qk_norm or cfg.attn_bias or cfg.attn_output_gate
                or cfg.post_block_norms or cfg.embed_scale
                or cfg.router_bias or cfg.num_shared_experts
                or cfg.first_k_dense or cfg.tie_word_embeddings)
    # a global-last layout is the same family at the other phase
    last = ModelConfig.from_hf_config(dict(
        HF, sliding_window_layout=[1, 1, 1, 0] * 2,
        rope_layout=[1, 1, 1, 0] * 2))
    assert (last.sliding_pattern, last.global_phase % 4) == (4, 3)


@pytest.mark.parametrize("change,what", [
    (dict(sliding_window_layout=[0, 1, 1, 1, 0, 1, 1, 0]), "one period"),
    (dict(sliding_window_layout=[1] * 8, rope_layout=[1] * 8),
     "one period"),
    (dict(sliding_window_layout=[0] * 8, rope_layout=[0] * 8),
     "one period"),
    (dict(sliding_window_layout=[0, 1, 1, 1]), "one entry a layer"),
    (dict(sliding_window_layout=None), "one entry a layer"),
    (dict(rope_layout=[1, 1, 1, 1] * 2), "rope_layout"),
    (dict(moe_primary_router_apply_softmax=False), "apply_softmax"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
     "rope_scaling"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(sliding_window_size=None), "sliding_window_size"),
])
def test_an_unimplemented_variant_raises(change, what):
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config(dict(HF, **change))


# -- the phase of the period -------------------------------------------


@pytest.mark.parametrize("P,phase,L", [
    (4, 0, 8), (4, 0, 5), (4, 0, 7), (4, -1, 8), (4, -1, 6), (4, 3, 6),
    (2, -1, 5), (2, 0, 5), (4, 1, 7), (3, 2, 4)])
def test_the_counts_and_places_follow_the_phase(P, phase, L):
    """Global layers counted from the phase, for depths that are and
    are not whole periods; a layer's place among the slabs or the
    rings is the number of its kind before it."""
    cfg = tiny_test().replace(
        num_layers=L, alt_sliding_window=True, sliding_pattern=P,
        global_phase=phase, sliding_window=W, window_ring=True)
    kinds = [i % P == phase % P for i in range(L)]
    assert [cfg.is_global_layer(i) for i in range(L)] == kinds
    assert cfg.kv_cache_layers == sum(kinds)
    assert cfg.window_layers == L - sum(kinds)
    assert cfg.attn_layer_windows == ((W, L - sum(kinds)),
                                      (None, sum(kinds)))
    for i in range(L + 1):
        assert cfg.globals_before(i) == sum(kinds[:i])
        assert int(cfg.globals_before(jnp.asarray(i, jnp.int32))) \
            == sum(kinds[:i])
    # no ring: every layer keeps full-length rows, the counts say so
    plain = cfg.replace(window_ring=False)
    assert (plain.window_layers, plain.kv_cache_layers) == (0, L)
    assert plain.attn_layer_windows == cfg.attn_layer_windows


@pytest.mark.parametrize("phase,L", [(0, 8), (0, 5), (0, 6), (-1, 6),
                                     (1, 7), (-1, 8)])
def test_either_phase_runs_prefill_then_decode_as_one_full_pass(phase, L):
    """A period that opens and one that closes with its global layer,
    at depths that are and are not whole periods (a head-less scan and
    a tail that may hold a global layer): a prompt that wraps the ring,
    then steps that wrap it again, on logits against one full pass."""
    cfg = tiny_test().replace(
        num_layers=L, alt_sliding_window=True, sliding_pattern=4,
        global_phase=phase, sliding_window=W, window_ring=True,
        rope_skip_global=True, dtype=jnp.float32)
    p = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(_tokens(22, seed=6)[None] % 512)
    full, _ = llama.forward(p, cfg, toks)
    cache = _per_slot(llama.KVCache.create(cfg, 1, 32))
    assert cache.k.shape[0] == cfg.kv_cache_layers
    assert cache.wk.shape[:3] == (cfg.window_layers, 1, W)
    lg, cache = llama.forward(p, cfg, toks[:, :11], cache=cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :11]),
                               atol=ATOL)
    for t in range(11, 22):
        lg, cache = llama.forward(p, cfg, toks[:, t:t + 1], cache=cache)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]), atol=ATOL)
    # the phase matters: the other one is another model
    other, _ = llama.forward(p, cfg.replace(global_phase=phase + 2), toks)
    assert float(jnp.abs(other - full).max()) > 100 * ATOL


def test_the_global_layers_rows_are_where_the_phase_puts_them(cut):
    """Layer 0 (global) writes slab 0 and layer 4 slab 1; layers 1-3
    and 5-7 the six rings in order: every slab and ring of a one-pass
    prompt holds rows, and the first slab's are the keys of layer 0,
    which reads the embedding alone and applies no rotary."""
    cfg, p, _ = cut
    toks = _tokens(6, seed=8)
    cache = _per_slot(llama.KVCache.create(cfg, 1, 16))
    _, cache = llama.forward(p, cfg, jnp.asarray(toks[None]), cache=cache)
    assert cache.k.shape[0] == 2 and cache.wk.shape[0] == 6
    for slab in (cache.k, cache.wk):
        assert all(float(jnp.abs(slab[i, 0, :6]).min(-1).max()) > 0
                   for i in range(slab.shape[0]))
    lp = {k: v[0] for k, v in p["layers"].items()}
    x = jnp.take(p["embed"], jnp.asarray(toks), axis=0)[None]
    h = llama.block_norm(x, lp, "attn_norm", cfg)
    want = jnp.einsum("bsd,khd->bskh", h, lp["wk"])
    np.testing.assert_allclose(np.asarray(cache.k[0, 0, :6]),
                               np.asarray(want[0]), atol=1e-6)


# -- the program's weights and logits are the reference's ---------------


def test_reference_makes_the_served_weights(cut):
    cfg, p, w = cut
    assert set(w["layers"]) == set(p["layers"])
    assert "dense_layers" not in p
    for mine, theirs in ((p["layers"], w["layers"]),
                         (p, {k: w[k] for k in ("embed", "lm_head",
                                                "final_norm")})):
        for name, leaf in theirs.items():
            np.testing.assert_array_equal(
                np.asarray(mine[name]), np.asarray(stored(name, leaf)),
                err_msg=name)
    assert llama.param_count(p) == sum(
        x.size for x in jax.tree.leaves(w))
    assert p["layers"]["router"].shape == (8, 64, 8)
    assert p["layers"]["we_gate"].shape == (8, 4, 64, 32)


@pytest.mark.parametrize("std", [0.002, 0.05])
def test_the_seeded_deviation_is_config_jsons(std):
    """`initializer_range` sets the deviation of every seeded matrix,
    in the program and in the reference's own copy of the recipe
    alike, to the bit in the served dtype; without the key both make
    the 0.02 they always made (the digests of the older families
    below hold the program to that)."""
    hf = dict(CUT, initializer_range=std)
    cfg = ModelConfig.from_hf_config(hf).replace(moe_impl="ragged")
    assert cfg.init_std == std and cfg.dtype == jnp.bfloat16
    assert ModelConfig.from_hf_config(CUT).init_std == 0.02
    p, w = _params(cfg), ref.init_weights(hf)
    plain = ref.init_weights(CUT)
    for mine, theirs, old in (
            (p["layers"], w["layers"], plain["layers"]),
            (p, {k: w[k] for k in ("embed", "lm_head")}, plain)):
        for name, leaf in theirs.items():
            np.testing.assert_array_equal(
                np.asarray(mine[name]), np.asarray(stored(name, leaf)),
                err_msg=name)
            if "norm" not in name:
                ratio = float(jnp.std(leaf.astype(jnp.float32))
                              / jnp.std(old[name].astype(jnp.float32)))
                assert abs(ratio / (std / 0.02) - 1) < 0.01, name


@pytest.mark.parametrize("n", [5, 8, 29])
def test_forward_matches_the_reference(cut, n):
    """One full pass, no cache, shorter than, equal to and longer than
    the window."""
    cfg, p, w = cut
    toks = _tokens(n)
    lg, _ = llama.forward(p, cfg, jnp.asarray(toks[None]))
    want = ref.logits(w, CUT, toks, 0, n)
    assert float(want.std()) > 0.1
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(want),
                               atol=ATOL)


def test_the_program_in_bfloat16_would_fail_the_tolerance(cut):
    """The tolerance's other side: the program in bfloat16 differs
    from the reference by about a hundred times ATOL (0.00508 with
    the experts' results added up in bfloat16, 0.00479 since they are
    summed in float32 and rounded once)."""
    cfg, p, w = cut
    toks = _tokens(29)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, p)
    lg, _ = llama.forward(low, cfg.replace(dtype=jnp.bfloat16),
                          jnp.asarray(toks[None]))
    want = ref.logits(w, CUT, toks, 0, 29)
    assert float(jnp.abs(lg[0] - want).max()) > 50 * ATOL


@pytest.mark.parametrize("n0", [5, 8, 19])
def test_prefill_then_decode_through_slab_and_ring_matches_one_full_pass(
        cut, n0):
    """A right-padded bucket for a prompt shorter than the window (the
    ring wraps during DECODE, as every prompt under 4096 does at the
    published window), equal to it, and longer (it wraps in the
    prefill), then one token at a time through the ring of the window
    layers and the slab of the global ones, across at least two wraps
    (20 steps of a ring of 8), on logits against the reference's one
    pass."""
    cfg, p, w = cut
    toks, bucket = _tokens(n0 + 21), 32
    want = np.asarray(ref.logits(w, CUT, toks, 0, len(toks)))
    cache = llama.KVCache.create(cfg, 1, 64)
    assert cache.wk.shape == (6, 1, W, 1, 16) and cache.k.shape[0] == 2
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n0] = toks[:n0]
    true_len = jnp.asarray([n0], jnp.int32)
    lg, cache = llama.forward(p, cfg, jnp.asarray(padded),
                              cache=_per_slot(cache),
                              logits_at=true_len - 1, valid_len=true_len)
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n0 - 1],
                               atol=ATOL)
    cache = dataclasses.replace(cache, index=true_len)
    for t in range(n0, len(toks)):
        lg, cache = llama.forward(p, cfg, jnp.asarray(toks[None, t:t + 1]),
                                  cache=cache)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[t],
                                   atol=ATOL, err_msg=f"row {t}")


# -- the expert layer: decided before attention, computed behind it -----


def _one_layer(p):
    return {k: v[1] for k, v in p["layers"].items()}


@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_routing_decided_ahead_equals_routing_decided_behind(impl):
    """Fed the same input, `moe_decide` ahead of the experts and the
    expert layer deciding for itself give the same numbers, to the
    bit; fed another input, the decision follows the router's input
    and the experts compute on theirs."""
    cfg = _cfg(HF).replace(moe_impl=impl)
    lp = _one_layer(_params(cfg))
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    behind = llama.moe_mlp(h, lp, cfg)
    ahead = llama.moe_mlp(h, lp, cfg, routed=llama.moe_decide(h, lp, cfg))
    np.testing.assert_array_equal(np.asarray(ahead), np.asarray(behind))
    routed = llama.moe_decide(x, lp, cfg)
    assert (routed.plan is None) == (impl == "dense")
    got = llama.moe_mlp(h, lp, cfg, routed=routed)
    assert float(jnp.abs(got - behind).max()) > 1e-3
    # by hand: x picks and weighs, h is what the experts are fed
    idx, top = ref.route(x.reshape(18, 64), lp["router"], 3)
    np.testing.assert_array_equal(np.asarray(routed.idx.reshape(18, 3)),
                                  np.asarray(idx))
    want = jnp.zeros((18, 64))
    hf = h.reshape(18, 64)
    for t in range(18):
        for j in range(3):
            e = int(idx[t, j])
            y = (jax.nn.relu(hf[t] @ lp["we_gate"][e])
                 * (hf[t] @ lp["we_up"][e])) @ lp["we_down"][e]
            want = want.at[t].add(top[t, j] * y)
    np.testing.assert_allclose(np.asarray(got.reshape(18, 64)),
                               np.asarray(want), atol=2e-6)


def test_the_layer_routes_from_its_input_and_not_from_what_attention_made(
        cut):
    """`_layer` of this family: the decision comes from x as the layer
    receives it (so it does not move when the attention's weights do),
    the experts are fed the normed stream behind attention (so the
    result does)."""
    cfg, p, _ = cut
    lp = _one_layer(p)
    x = 0.05 * jax.random.normal(jax.random.PRNGKey(7), (1, 12, 64))
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    freqs = llama._rope_frequencies(cfg)

    def run(lp):
        y, _, stats = llama._layer(x, lp, cfg, freqs, pos, None, None, None,
                                   moe_stats=True)
        return y, stats

    y, stats = run(lp)
    routed = llama.moe_decide(x, lp, cfg)
    assert int(stats[1]) == int(routed.plan.counts.sum())
    # by hand from the published equations
    a, _ = llama._mha(llama.block_norm(x, lp, "attn_norm", cfg), lp, cfg,
                      freqs, pos, None, None, None, cfg.sliding_window,
                      False)
    h = x + a
    n = llama.block_norm(h, lp, "mlp_norm", cfg)
    want = h + llama.moe_mlp(n, lp, cfg, routed=routed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6)
    # another attention: the same pairs land, another result
    y2, stats2 = run(dict(lp, wo=-lp["wo"]))
    assert int(stats2[1]) == int(stats[1]) and int(stats2[0]) == int(stats[0])
    assert float(jnp.abs(y2 - y).max()) > 1e-4
    # a router fed the normed stream behind attention would pick others
    late = llama.moe_decide(n, lp, cfg)
    assert (np.sort(np.asarray(late.idx)) != np.sort(
        np.asarray(routed.idx))).any()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """EP-4 over 8 experts: the routed parts the four shares give,
    decided ONCE from the layer's input (the router is replicated and
    reads the same x on every chip), equal the uncut layer, and every
    pair lands on exactly one share."""
    uncut = _cfg(HF)
    lp = _one_layer(_params(uncut))
    x = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    whole = llama.moe_mlp(h, lp, uncut,
                          routed=llama.moe_decide(x, lp, uncut))
    weights, idx = llama._route(x, lp, uncut)          # once
    routed, hits = 0.0, 0
    for r in range(4):
        cfg = uncut.replace(num_experts=2, num_experts_total=8,
                            expert_offset=2 * r)
        held = dict(lp, **{k: lp[k][2 * r:2 * r + 2]
                           for k in ("we_gate", "we_up", "we_down")})
        plan = llama.expert_dispatch(weights.reshape(18, 3),
                                     idx.reshape(18, 3), 2, lo=2 * r)
        part, (hit, pairs) = llama.moe_mlp_ragged(
            h, held, cfg, with_stats=True,
            routed=llama.Routed(weights, idx, plan))
        # the share deciding for itself from x decides the same
        own = llama.moe_mlp_ragged(h, held, cfg,
                                   routed=llama.moe_decide(x, held, cfg))
        np.testing.assert_array_equal(np.asarray(own), np.asarray(part))
        routed = routed + part
        hits += int(pairs)
    assert hits == 18 * 3          # every pair landed on one share
    np.testing.assert_allclose(np.asarray(routed), np.asarray(whole),
                               atol=2e-6)
    # `ragged_experts`, the one call parallel/moe.py makes, is the two
    out, _ = llama.ragged_experts(h.reshape(18, 64), weights.reshape(18, 3),
                                  idx.reshape(18, 3), lp, uncut)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(whole.reshape(18, 64)), atol=2e-6)


# -- each term of the block matters -------------------------------------


def _without(p, cfg, what):
    """The model with one term of the published block taken out."""
    if what == "router_pre_attn":         # the router behind attention
        return p, cfg.replace(router_pre_attn=False)
    if what == "relu":
        return p, cfg.replace(moe_activation="silu")
    if what == "global_first":            # the period closed, not opened
        return p, cfg.replace(global_phase=-1)
    if what == "rope_skip_global":        # rotary on every layer
        return p, cfg.replace(rope_skip_global=False)
    if what == "rotary":                  # none on the window layers
        return p, cfg.replace(rope_theta=1e30)
    if what == "window":                  # every layer sees every row
        return p, cfg.replace(sliding_window=512)
    if what == "softmax_over_the_kept":   # softmax over all, no renorm
        return p, cfg.replace(router_scoring="softmax_v2",
                              norm_topk_prob=False)
    raise KeyError(what)


@pytest.mark.parametrize("what", [
    "router_pre_attn", "relu", "global_first", "rope_skip_global",
    "rotary", "window", "softmax_over_the_kept"])
def test_a_model_without_the_term_differs_from_the_reference(cut, what):
    """Leave one term out and the logits differ from the reference by
    far more than the tolerance (the least of them, no rotary on
    the window layers, by 35 x: without q/k norms the seeded
    attention's logits are small, and so is what rotating them
    changes)."""
    cfg, p, w = cut
    toks = _tokens(29, seed=2)
    want = ref.logits(w, CUT, toks, 0, 29)
    p2, cfg2 = _without(p, cfg, what)
    lg, _ = llama.forward(p2, cfg2, jnp.asarray(toks[None]))
    assert float(jnp.abs(lg[0] - want).max()) > 20 * ATOL, what


# -- the engine: a slot owns rows and a ring ----------------------------


@pytest.fixture(scope="module")
def engine(cut):
    cfg, p, _ = cut
    return InferenceEngine(p, cfg, max_slots=3, max_seq=128)


def _greedy(n):
    return (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32))


def test_two_slots_decode_as_each_alone_and_the_counters_read_right(
        cut, engine):
    """Slots 0 and 2 at lengths 21 (wrapped in its prefill) and 5 (it
    wraps in decode; slot 1 free) through single steps and a
    multi-token chunk: every served token is the reference's best for
    that sequence. The expert counters: 8 expert layers a step (every
    layer is one), at most the 4 held experts hit a layer-step, pairs
    among the 3 x 3 a step's live tokens route; the gauges: two
    global layers' rows and six rings of 8."""
    from ome_tpu.engine.scheduler import Scheduler
    _, _, w = cut
    st = engine.new_state()
    assert st.wk.shape == (6, 3, W, 1 * 16) and st.k.shape[:3] == (2, 3, 128)
    seqs, first = {}, {0: 21, 2: 5}
    for slot, n in first.items():
        ids = [int(t) for t in _tokens(n, seed=slot)]
        tok, kv, true_len, bucket = engine.prefill(ids)
        st = engine.insert(st, kv, slot, true_len, tok, bucket)
        seqs[slot] = ids + [tok]
    for _ in range(5):
        st, toks = engine.decode(st, *_greedy(3))
        for s in seqs:
            seqs[s].append(int(np.asarray(toks)[s]))
    st, toks, adv = engine.decode_multi(
        st, *_greedy(3), 4, np.asarray([4, 0, 2], np.int32),
        np.full((3, 1), -1, np.int32))
    assert list(np.asarray(adv)) == [4, 0, 2]
    for s in seqs:
        seqs[s] += [int(t) for t in np.asarray(toks)[s, :np.asarray(adv)[s]]]
    assert len(seqs[2]) > W + 2          # the short one wrapped in decode
    for s, ids in seqs.items():
        want = ref.logits(w, CUT, np.asarray(ids[:-1]), 0, len(ids) - 1)
        best = [int(t) for t in np.asarray(want.argmax(-1))[first[s] - 1:]]
        assert ids[first[s]:] == best, f"slot {s}"
    counts = engine.moe_counters()
    assert counts["layer_steps"] == 8 * 9            # 5 + 4 steps
    assert 0 < counts["experts_hit"] <= 4 * counts["layer_steps"]
    assert counts["experts_hit"] <= counts["pairs"] \
        <= 3 * 3 * counts["layer_steps"]
    sched = Scheduler(engine)
    sched.update_gauges()
    text = sched.registry.render()

    def metric(name):
        return float(text.split("\n" + name + " ")[1].split()[0])

    for name in ("layer_steps", "experts_hit", "pairs"):
        assert metric(f"ome_engine_moe_{name}_total") == counts[name]
    row = 1 * (16 + 16) * 4                          # K x (Dk + Dv) x f32
    assert metric('ome_engine_kv_cache_bytes{kind="window"}') \
        == 6 * 3 * W * row == engine.ring_bytes()
    assert metric('ome_engine_kv_cache_bytes{kind="global"}') \
        == 2 * 3 * 128 * row
    assert engine.ring_rows == W


def test_at_the_published_shape_the_ring_holds_4096_rows():
    """What `/health` reports as `window_ring_rows` and the gauge's
    two kinds at the configuration's file, from shapes alone."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b-ep4.json")) as f:
        file = json.load(f)
    import modeldir
    cfg = ModelConfig.from_hf_config(modeldir.model_config(file))
    assert cfg.init_std == file["initializer_range"] == 0.002
    assert llama.ring_rows(cfg, 16384) == 4096
    assert (cfg.window_layers, cfg.kv_cache_layers) == (18, 6)
    assert (cfg.router_width, cfg.num_experts) == (64, 16)
    state = jax.eval_shape(lambda: llama.KVCache.create(cfg, 8, 16384))
    assert state.wk.shape == (18, 8, 4096, 4, 128)
    assert state.k.shape == (6, 8, 16384, 4, 128)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 2_966_776_320 \
        == file["benchmark"]["published_params"]
    assert core.slot_state_kind(cfg) == "ring"


# -- what start-up refuses, with the reason -----------------------------


@pytest.mark.parametrize("flags,reason", [
    (["--kv-block", "128"], "ONE block table"),
    (["--prefix-cache-mb", "64"], "LAST rows"),
    (["--spec-tokens", "4"], "rolled back"),
    (["--disaggregation-mode", "prefill"], "PD transfer"),
    (["--journal", "/tmp/j"], "--journal"),
    (["--tp", "2"], "--tp"),
])
def test_serve_stops_at_start_up_with_the_reason(tmp_path, flags, reason):
    from ome_tpu.engine import serve
    with open(tmp_path / "config.json", "w") as f:
        json.dump(CUT, f)
    base = ["--model-dir", str(tmp_path), "--random-weights",
            "--dtype", "float32", "--max-slots", "2", "--max-seq", "128"]
    if "--prefix-cache-mb" not in flags:
        base += ["--prefix-cache-mb", "0"]
    args = serve.build_parser().parse_args(base + flags)
    with pytest.raises(SystemExit) as e:
        serve.load_engine(args)
    assert reason in str(e.value) and "window-cache.md" in str(e.value)


def _serve(tmp_path, hf):
    from ome_tpu.engine import serve
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    return serve.load_engine(serve.build_parser().parse_args(
        ["--model-dir", str(tmp_path), "--random-weights", "--dtype",
         "float32", "--max-slots", "2", "--max-seq", "64"]))


def test_serve_builds_the_engine_with_the_ring_and_the_held_share(tmp_path):
    eng = _serve(tmp_path, CUT)
    assert eng.cfg.window_layers == 6 and eng.cfg.moe_impl == "ragged"
    assert eng.cfg.router_width == 8 and eng.cfg.num_experts == 4
    assert eng.ring_rows == W and eng.prefix_cache.capacity_bytes == 0
    with pytest.raises(ValueError, match="rolled back"):
        eng.verify(eng.new_state(), np.zeros((2, 2), np.int32),
                   np.zeros(2, np.int32), *_greedy(2))


@pytest.mark.parametrize("archs,served", [
    (["NoSuchModelForCausalLM"], False), ([], True), (None, True),
    (["LlamaForCausalLM"], True)])
def test_random_weights_refuse_a_named_architecture_nobody_wrote(
        tmp_path, archs, served):
    """`--random-weights` under a NAMED architecture outside
    `SUPPORTED_ARCHITECTURES` used to fall through to a dense llama of
    4 x hidden under that name; it stops, as a checkpoint's load does.
    No name, or an empty list, is a test's toy model and is served."""
    hf = dict(hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
              num_key_value_heads=1, vocab_size=64, intermediate_size=48,
              max_position_embeddings=64)
    if archs is not None:
        hf["architectures"] = archs
    if served:
        eng = _serve(tmp_path, hf)
        assert not eng.cfg.is_moe and eng.cfg.num_layers == 1
    else:
        with pytest.raises(SystemExit, match="NoSuchModelForCausalLM"):
            _serve(tmp_path, hf)


def test_int8_quantization_covers_the_experts_and_leaves_the_router(cut):
    """`--quantization int8`, the program's own lower precision (the
    second control of the cell's limits): every matrix but the router,
    through the period scan's stacked experts and the routing decided
    ahead of attention."""
    cfg, p, _ = cut
    from ome_tpu.models.quant import QTensor, quantize_params
    q = quantize_params(p, mode="int8")
    for name in ("wq", "wk", "wo", "we_gate", "we_up", "we_down"):
        assert isinstance(q["layers"][name], QTensor), name
    assert not isinstance(q["layers"]["router"], QTensor)
    toks = jnp.asarray(_tokens(29)[None])
    a, _ = llama.forward(p, cfg, toks)
    b, _ = llama.forward(q, cfg, toks)
    assert 0 < float(jnp.abs(a - b).max()) < 0.3 * float(a.std())


# -- the checkpoint's names ---------------------------------------------


def test_a_checkpoint_under_the_published_names_loads(tmp_path):
    """safetensors under the family's tensor names
    (`block_sparse_moe.primary_router`, `block_sparse_moe.experts.N.
    {gate,up,down}`), all 8 experts and a vocabulary of 256: the cut
    config loads its 4 held experts and its first 160 rows, and serves
    the logits of the tree it was written from."""
    from ome_tpu.models import checkpoint as ck
    uncut = _cfg(HF)
    p = _params(uncut)
    t = {}

    def lin(name, a):                     # [in, out] -> HF [out, in]
        t[name] = np.asarray(a, np.float32).reshape(a.shape[0], -1).T

    blk = p["layers"]
    for i in range(8):
        pre = f"model.layers.{i}."
        t[pre + "input_layernorm.weight"] = np.asarray(blk["attn_norm"][i])
        t[pre + "post_attention_layernorm.weight"] = \
            np.asarray(blk["mlp_norm"][i])
        for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v")):
            # out-major [heads, Dh, D]: HF's [out, in] with heads split
            t[pre + f"self_attn.{theirs}_proj.weight"] = np.asarray(
                blk[ours][i], np.float32).reshape(-1, 64)
        t[pre + "self_attn.o_proj.weight"] = np.asarray(
            blk["wo"][i]).reshape(-1, 64).T
        lin(pre + "block_sparse_moe.primary_router.weight", blk["router"][i])
        for n in ("gate", "up", "down"):
            for e in range(8):
                lin(pre + f"block_sparse_moe.experts.{e}.{n}.weight",
                    blk[f"we_{n}"][i, e])
    t["model.embed_tokens.weight"] = np.asarray(p["embed"])
    t["model.norm.weight"] = np.asarray(p["final_norm"])
    t["lm_head.weight"] = np.asarray(p["lm_head"]).T
    ck.save_safetensors(str(tmp_path / "model.safetensors"), t)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(dict(CUT, vocab_size=160), f)
    loaded, cfg = ck.load_params(str(tmp_path), dtype=jnp.float32)
    assert "SmallThinkerForCausalLM" in ck.SUPPORTED_ARCHITECTURES
    assert loaded["layers"]["we_gate"].shape == (8, 4, 64, 32)
    assert loaded["layers"]["router"].shape == (8, 64, 8)
    assert loaded["embed"].shape == (160, 64)
    np.testing.assert_array_equal(
        np.asarray(loaded["layers"]["we_up"]),
        np.asarray(p["layers"]["we_up"][:, 2:6]))
    cfg = cfg.replace(moe_impl="ragged")
    toks = jnp.asarray(_tokens(20)[None] % 160)
    lg, _ = llama.forward(loaded, cfg, toks)
    held = dict(p, embed=p["embed"][:160], lm_head=p["lm_head"][:, :160],
                layers=dict(p["layers"], **{
                    k: p["layers"][k][:, 2:6]
                    for k in ("we_gate", "we_up", "we_down")}))
    want, _ = llama.forward(held, cfg, toks)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want), atol=ATOL)


# -- the families that shared the period scan before --------------------

# sha256 of the float32 logits (one full pass of 14 tokens, then a
# padded prompt of 6 and 8 decode steps through the cache) of the four
# families `_alt_window_scan` served before it took a phase, read on
# the parent commit (cd8cb81) in this container: the re-phased scan
# gives them the same numbers to the bit. Since PR 41 the digests are
# read on the parent's form of the attention projections
# (`_parent_proj.parent_form`: in float32 XLA's CPU dot adds a one-row
# product's terms in another order once the weight lies out-major),
# and the out-major dots are held beside them to 1e-5. Since PR 47
# the two sparse families' are read on that PR's tree: an expert layer
# sums a token's k weighted results as one expression where it
# scatter-added them, XLA's CPU backend contracts the products into
# the sum, and the last bit of some logits moved (largest difference
# 1.8e-7 on logits up to 0.58 / 0.56; the parent's digests were
# 91910728b8d3... and d4c7792d80ae...)
PARENT_DIGESTS = {
    "afmoe":
        "a89ded87ea0173502e653b2fb39197d49b62c6c41d5f58d1e8d9a65cc23fa8a9",
    "cohere2":
        "a81a257fe2ae10650454f8ee0ea3fd2c5fb2427b5187e1bb603d30a287820c23",
    "gemma2":
        "38479a844e3b74def031432390a49bab8f925684db12915ca22558c4df290bca",
    "gpt_oss":
        "be7515fa40d84cd0b7406486f8df62de4d0e1c78f4b2d843185cb7e8b71c4969",
}


def _periodic_model(family, tmp_path):
    if family == "afmoe":
        hf = dict(
            architectures=["AfmoeForCausalLM"], hidden_size=64,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=120,
            intermediate_size=96, moe_intermediate_size=32, num_experts=4,
            num_experts_per_tok=2, num_shared_experts=1,
            num_dense_layers=2, global_attn_every_n_layers=4,
            sliding_window=4, rope_theta=10000, rms_norm_eps=1e-5,
            ep_num_experts_total=8, ep_expert_offset=2,
            max_position_embeddings=128)
        cfg = ModelConfig.from_hf_config(hf).replace(
            dtype=jnp.float32, moe_impl="ragged")
        return _params(cfg), cfg
    import torch
    import transformers
    from ome_tpu.models import checkpoint as ck
    common = dict(vocab_size=120, hidden_size=64, intermediate_size=96,
                  num_hidden_layers=4, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  max_position_embeddings=128, sliding_window=4)
    if family == "gemma2":
        hf = transformers.Gemma2Config(
            **common, query_pre_attn_scalar=16,
            attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
            hidden_activation="gelu_pytorch_tanh")
    elif family == "cohere2":
        hf = transformers.Cohere2Config(
            **common, logit_scale=0.5, sliding_window_pattern=4)
    else:
        hf = transformers.GptOssConfig(
            **common, num_local_experts=4, num_experts_per_tok=2,
            rope_scaling=None, tie_word_embeddings=False)
    torch.manual_seed(0)
    model = transformers.AutoModelForCausalLM.from_config(hf)
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    params, cfg = ck.load_params(str(tmp_path), dtype=jnp.float32)
    if cfg.is_moe:
        cfg = cfg.replace(moe_impl="ragged")
    return params, cfg


@pytest.mark.parametrize("family", sorted(PARENT_DIGESTS))
def test_the_rephased_scan_serves_the_older_families_to_the_bit(
        tmp_path, family):
    params, cfg = _periodic_model(family, tmp_path)
    assert cfg.alt_sliding_window and cfg.sliding_window == 4
    assert cfg.global_phase % cfg.sliding_pattern == cfg.sliding_pattern - 1
    toks = jnp.asarray(_tokens(14, seed=11)[None] % 120)

    def passes():
        """Every logit of the full pass, the padded prompt and the
        eight decode steps through the cache."""
        full, _ = llama.forward(params, cfg, toks)
        out = [full]
        cache = _per_slot(llama.KVCache.create(cfg, 1, 32))
        padded = jnp.zeros((1, 8), jnp.int32).at[:, :6].set(toks[:, :6])
        n = jnp.asarray([6], jnp.int32)
        lg, cache = llama.forward(params, cfg, padded, cache=cache,
                                  logits_at=n - 1, valid_len=n)
        out.append(lg)
        cache = dataclasses.replace(cache, index=n)
        for t in range(6, 14):
            lg, cache = llama.forward(params, cfg, toks[:, t:t + 1],
                                      cache=cache)
            out.append(lg)
            np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                       np.asarray(full[0, t]), atol=1e-4)
        return [np.asarray(x, np.float32) for x in out]

    with parent_form():
        parents = passes()
    digest = hashlib.sha256()
    for x in parents:
        digest.update(x.tobytes())
    assert digest.hexdigest() == PARENT_DIGESTS[family]
    for got, want in zip(passes(), parents):
        np.testing.assert_allclose(got, want, atol=1e-5)


# -- the kernels at 7 query heads a KV head ------------------------------


@pytest.mark.parametrize("window", [None, 128])
def test_flash_decode_at_a_group_of_seven(window):
    """The Pallas decode kernel (interpret mode) at H = 28, K = 4,
    D = 128 over a layer of the stacked slabs, against XLA's
    attention: a global layer's rows and a ring's, slots of different
    length."""
    from ome_tpu.ops.attention import attention
    L, B, S, H, K, D = 2, 3, 512, 28, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (L, B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (L, B, S, K, D), jnp.float32)
    pos = jnp.asarray([[400], [37], [511]], jnp.int32)
    kw = dict(positions=pos, kv_len=pos[:, 0] + 1, sliding_window=window)
    got = attention(q, k, v, backend="pallas_interpret",
                    layer=jnp.asarray(1, jnp.int32), **kw)
    want = attention(q, k, v, backend="xla", layer=1, **kw)
    assert got.shape == (B, 1, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    # a head's output is its own KV head's: group g reads head g // 7
    alone = attention(q[:, :, 7:14], k[:, :, :, 1:2], v[:, :, :, 1:2],
                      backend="xla", layer=1, **kw)
    np.testing.assert_allclose(np.asarray(got[:, :, 7:14]),
                               np.asarray(alone), atol=2e-5)


@pytest.mark.parametrize("window", [None, 64])
def test_flash_prefill_at_a_group_of_seven(window):
    """The Pallas prefill kernel (interpret mode) at H = 28, K = 4,
    D = 128: a query block of 7 x 128 = 896 lanes, a padded prompt,
    against XLA's attention."""
    from ome_tpu.ops import flash
    from ome_tpu.ops.attention import attention
    B, S, H, K, D = 1, 256, 28, 4, 128
    assert flash._prefill_blocks(S, S, H // K, D) == (256, 256)
    assert flash._prefill_blocks(16384, 16384, H // K, D) == (256, 512)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    kw = dict(positions=pos, kv_len=jnp.asarray([200], jnp.int32),
              sliding_window=window)
    got = attention(q, k, v, backend="pallas_interpret", **kw)
    want = attention(q, k, v, backend="xla", **kw)
    np.testing.assert_allclose(np.asarray(got[:, :200]),
                               np.asarray(want[:, :200]), atol=2e-5)
