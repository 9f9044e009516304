"""Arcee AFMoE (Trinity) on the serve path: window layers that keep a
RING of `sliding_window` KV rows a slot beside global layers that keep
every row, leading dense layers ahead of a sigmoid-routed expert layer
that holds a share of its experts.

Small sizes with the true ratios (window 8, period 4, 2 dense layers,
2 periods, 8 experts top-2 of which 4 are held): the program in
float32 against the benchmark's plain reference
(benchmark/reference/window_moe.py, which imports nothing of the
program and keeps no ring); prefill then decode through the ring
against one full pass, across wraps; the ring's rows against a
full-length slab's, bit for bit; slots of different length together
against each alone; the four EP-4 shares adding up to the uncut layer;
each term of the block shown to matter; what start-up refuses.

Tolerances: float32 under "highest" precision on both sides, logits of
standard deviation 0.16: 5e-5 absolute is a few float32 roundings
through 8 layers (readings up to 4e-6), and under a hundredth of what
a bfloat16 pass differs by, so a lower precision fails every one of
them (`test_the_reference_in_bfloat16_would_fail_the_tolerance`).
"""

import dataclasses
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
from ome_tpu.models.config import ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import window_moe as ref  # noqa: E402

from _parent_proj import stored  # noqa: E402

W = 8
HF = dict(
    architectures=["AfmoeForCausalLM"], model_type="afmoe",
    hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, vocab_size=256,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=2,
    global_attn_every_n_layers=4, sliding_window=W,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    rope_theta=10000, rope_scaling=None, rms_norm_eps=1e-5,
    route_norm=True, route_scale=2.826, score_func="sigmoid",
    n_group=1, topk_group=1, mup_enabled=True,
    tie_word_embeddings=False, max_position_embeddings=512)
# the chip's share: experts 2..5 of 8 held, the router 8 wide
CUT = dict(HF, num_experts=4, ep_num_experts_total=8, ep_expert_offset=2)
ATOL = 5e-5


def _cfg(hf):
    return ModelConfig.from_hf_config(hf).replace(
        dtype=jnp.float32, moe_impl="ragged")


def _params(cfg, bias_seed=7):
    """Seeded weights with a NON-ZERO selection bias (the recipe's is
    zero, under which a bias that leaked into the weights would not
    show)."""
    p = jax.jit(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))()
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(bias_seed),
                                   p["layers"]["router_bias"].shape)
    return dict(p, layers=dict(p["layers"], router_bias=bias))


def _ref_weights(hf, p):
    w = ref.init_weights(hf, jnp.float32)
    return dict(w, moe=dict(w["moe"],
                            router_bias=p["layers"]["router_bias"]))


@pytest.fixture(scope="module")
def cut():
    cfg = _cfg(CUT)
    p = _params(cfg)
    return cfg, p, _ref_weights(CUT, p)


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


# arcee-ai/Trinity-Mini's published config.json (the model-configs
# catalog's row of it, every key), so that the test runs wherever the
# checkout does
PUBLISHED = dict(
    global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
    hidden_size=2048, intermediate_size=6144,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 8,
    load_balance_coeff=0.001, max_position_embeddings=131072,
    model_type="afmoe", moe_intermediate_size=1024, mup_enabled=True,
    n_group=1, num_attention_heads=32, num_dense_layers=2,
    num_expert_groups=1, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=32, num_key_value_heads=4, num_limited_groups=1,
    num_shared_experts=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, route_norm=True, route_scale=2.826,
    score_func="sigmoid", sliding_window=2048,
    tie_word_embeddings=False, topk_group=1, use_grouped_mm=True,
    vocab_size=200192)


def test_the_published_config_parses_to_the_published_shape():
    """The published config.json: 32 layers of which 2 dense, 128
    experts top-8 with one shared, window 2048 in periods of 4, and
    every term of the block that has a field. Where the catalog is at
    hand, the copy above is held to its row."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert row["config"] == PUBLISHED
    cfg = ModelConfig.from_hf_config(
        dict(PUBLISHED, architectures=["AfmoeForCausalLM"]))
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
            cfg.experts_per_token, cfg.num_shared_experts,
            cfg.sliding_window, cfg.sliding_pattern) \
        == (32, 2, 128, 8, 1, 2048, 4)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.vocab_size) \
        == (2048, 32, 4, 128, 6144, 1024, 200192)
    assert cfg.alt_sliding_window and cfg.rope_skip_global
    assert cfg.qk_norm and cfg.attn_output_gate and cfg.post_block_norms
    assert cfg.embed_scale and cfg.router_bias and cfg.norm_topk_prob
    assert cfg.router_scoring == "sigmoid_v3"
    assert cfg.routed_scaling_factor == 2.826 and cfg.rope_theta == 10000
    assert cfg.window_ring
    assert (cfg.window_layers, cfg.kv_cache_layers) == (24, 8)
    assert not cfg.tie_word_embeddings and not cfg.unit_offset_norm


@pytest.mark.parametrize("change,what", [
    (dict(n_group=4), "group-limited"),
    (dict(topk_group=2), "group-limited"),
    (dict(score_func="softmax"), "score_func"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
     "rope_scaling"),
    (dict(mup_enabled=False), "mup_enabled"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(layer_types=["full_attention"] * 8), "layer_types"),
])
def test_an_unimplemented_variant_raises(change, what):
    with pytest.raises(ValueError, match=what):
        ModelConfig.from_hf_config(dict(HF, **change))


def test_the_shared_and_dense_counts_are_read_under_both_spellings():
    """`num_shared_experts` / `num_dense_layers` (afmoe) and
    `n_shared_experts` / `first_k_dense_replace` (DeepSeek)."""
    base = dict(architectures=["MixtralForCausalLM"], num_local_experts=4,
                num_experts_per_tok=2)
    for shared, dense in (("num_shared_experts", "num_dense_layers"),
                          ("n_shared_experts", "first_k_dense_replace")):
        cfg = ModelConfig.from_hf_config(dict(base, **{shared: 2, dense: 1}))
        assert (cfg.num_shared_experts, cfg.first_k_dense) == (2, 1)


# -- the program's weights and logits are the reference's ---------------


def test_reference_makes_the_served_weights(cut):
    cfg, p, _ = cut
    p = jax.jit(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))()
    w = ref.init_weights(CUT, jnp.float32)
    assert set(w["moe"]) == set(p["layers"])
    assert set(w["dense"]) == set(p["dense_layers"])
    for mine, theirs in ((p["layers"], w["moe"]),
                         (p["dense_layers"], w["dense"]),
                         (p, {k: w[k] for k in ("embed", "lm_head",
                                                "final_norm")})):
        for name, leaf in theirs.items():
            np.testing.assert_array_equal(
                np.asarray(mine[name]), np.asarray(stored(name, leaf)),
                err_msg=name)
    assert llama.param_count(p) == sum(
        x.size for x in jax.tree.leaves(w))


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


def test_the_reference_in_bfloat16_would_fail_the_tolerance(cut):
    """The tolerance's other side: the program in bfloat16 differs
    from the reference by hundreds of times ATOL."""
    cfg, p, w = cut
    toks = _tokens(29)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, p)
    lg, _ = llama.forward(low, cfg.replace(dtype=jnp.bfloat16),
                          jnp.asarray(toks[None]))
    want = ref.logits(w, CUT, toks, 0, 29)
    assert float(jnp.abs(lg[0] - want).max()) > 100 * ATOL


@pytest.mark.parametrize("n0", [5, 8, 19])
def test_prefill_then_decode_through_the_ring_matches_one_full_pass(
        cut, n0):
    """A right-padded bucket for a prompt shorter than, equal to and
    longer than the window, then one token at a time through the ring
    of the window layers and the slab of the global ones, across at
    least two wraps (20 steps of a ring of 8), on logits against the
    reference's one pass."""
    cfg, p, w = cut
    toks, bucket = _tokens(n0 + 21), 32
    want = np.asarray(ref.logits(w, CUT, toks, 0, len(toks)))
    cache = llama.KVCache.create(cfg, 1, 64)
    assert cache.wk.shape == (6, 1, W, 2, 16) and cache.k.shape[0] == 2
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


@pytest.mark.parametrize("n", [3, 8, 21])
def test_the_ring_holds_exactly_the_last_window_rows(cut, n):
    """After n tokens (a prompt of n - 2, two decode steps) the first
    window layer's ring holds the keys and values of positions
    n - W .. n - 1 and nothing else, position p in row p % W,
    bit-equal to the rows of a cache that keeps them all: the same
    program with a window of 64, whose ring no sequence here wraps
    (layer 0 reads the embedding alone, so its rows do not depend on
    what the layers above see)."""
    cfg, p, _ = cut
    toks = _tokens(n, seed=3)

    def run(c):
        cache = _per_slot(llama.KVCache.create(c, 1, 64))
        _, cache = llama.forward(
            p, c, jnp.asarray(toks[None, :n - 2]), cache=cache)
        for t in (n - 2, n - 1):
            _, cache = llama.forward(
                p, c, jnp.asarray(toks[None, t:t + 1]), cache=cache)
        return cache

    ring, full = run(cfg), run(cfg.replace(sliding_window=64))
    assert ring.wk.shape[2] == W and full.wk.shape[2] == 64
    kept = range(max(n - W, 0), n)
    assert len({pos % W for pos in kept}) == min(n, W)
    for name in ("wk", "wv"):
        got = np.asarray(getattr(ring, name))[0, 0]
        have = np.asarray(getattr(full, name))[0, 0]
        for pos in kept:
            np.testing.assert_array_equal(got[pos % W], have[pos],
                                          err_msg=f"{name} pos {pos}")


def test_every_window_layers_ring_is_the_tail_of_its_keys(cut):
    """All six window layers: the ring after a prompt of 21 holds, in
    row p % W, exactly the key rows the layer computed for positions
    13 .. 20 of one full pass with no cache at all (taken from the
    cache of a full-length run of the SAME windowed model, whose
    window mask sees the same rows)."""
    cfg, p, _ = cut
    n = 21
    toks = _tokens(n, seed=4)
    cache = _per_slot(llama.KVCache.create(cfg, 1, 32))
    _, ring = llama.forward(p, cfg, jnp.asarray(toks[None]), cache=cache)
    # a padded bucket leaves the same ring as the unpadded prompt
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = toks
    _, ring_p = llama.forward(
        p, cfg, jnp.asarray(padded),
        cache=_per_slot(llama.KVCache.create(cfg, 1, 32)),
        valid_len=jnp.asarray([n], jnp.int32))
    np.testing.assert_array_equal(np.asarray(ring.wk), np.asarray(ring_p.wk))
    np.testing.assert_array_equal(np.asarray(ring.wv), np.asarray(ring_p.wv))
    # keys by position, from decode steps that write one row each
    cache = _per_slot(llama.KVCache.create(cfg, 1, 32))
    rows = {}
    for t in range(n):
        _, cache = llama.forward(p, cfg, jnp.asarray(toks[None, t:t + 1]),
                                 cache=cache)
        rows[t] = np.asarray(cache.wk)[:, 0, t % W]
    for pos in range(n - W, n):
        np.testing.assert_allclose(np.asarray(ring.wk)[:, 0, pos % W],
                                   rows[pos], atol=1e-5)


# -- the engine: a slot owns rows and a ring ----------------------------


@pytest.fixture(scope="module")
def engine(cut):
    cfg, p, _ = cut
    return InferenceEngine(p, cfg, max_slots=3, max_seq=128)


def _greedy(n):
    return (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32))


@pytest.mark.parametrize("n", [5, 33, 70])
def test_padded_prompt_equals_unpadded(engine, n):
    """A prompt right-padded to its bucket hands back the ring, the
    global rows and the first token of the same prompt run unpadded
    (the engine's rows merged [.., K * D], the plain cache's heads
    apart: the same numbers)."""
    cfg, p = engine.cfg, engine.params
    ids = [int(t) for t in _tokens(n, seed=n)]
    tok, kv, true_len, bucket = engine.prefill(ids)
    assert (true_len, len(kv)) == (n, 3) and bucket >= n
    lg, exact = llama.forward(
        p, cfg, jnp.asarray([ids]),
        cache=_per_slot(llama.KVCache.create(cfg, 1, n)))
    assert tok == int(lg[0, -1].argmax())
    rows = min(n, W)
    for name in ("wk", "wv"):
        held = np.asarray(kv[2][name])[:, :, :rows]
        np.testing.assert_allclose(
            held, np.asarray(getattr(exact, name))[:, :, :rows]
            .reshape(held.shape), atol=1e-5, err_msg=name)
    held = np.asarray(kv[0][:, :, :n])
    np.testing.assert_allclose(
        held, np.asarray(exact.k).reshape(held.shape), atol=1e-5)


def test_two_slots_of_different_length_decode_as_each_alone(cut, engine):
    """Slots 0 and 2 at lengths 21 (wrapped) and 5 (not yet; slot 1
    free) through single steps, a multi-token chunk that freezes one
    of them midway, and single steps again, far enough that the short
    one wraps too: every served token is the reference's best for that
    sequence, and a frozen slot's ring is left as it was."""
    _, _, w = cut
    st = engine.new_state()
    assert st.wk.shape == (6, 3, W, 2 * 16) and st.k.shape[:3] == (2, 3, 128)
    seqs, first = {}, {0: 21, 2: 5}
    for slot, n in first.items():
        ids = [int(t) for t in _tokens(n, seed=slot)]
        tok, kv, true_len, bucket = engine.prefill(ids)
        st = engine.insert(st, kv, slot, true_len, tok, bucket)
        seqs[slot] = ids + [tok]
    for _ in range(3):
        st, toks = engine.decode(st, *_greedy(3))
        for s in seqs:
            seqs[s].append(int(np.asarray(toks)[s]))
    before = np.asarray(st.wk)[:, 1]
    st, toks, adv = engine.decode_multi(
        st, *_greedy(3), 4, np.asarray([4, 0, 2], np.int32),
        np.full((3, 1), -1, np.int32))
    assert list(np.asarray(adv)) == [4, 0, 2]
    # slot 1 was frozen for the whole chunk: not a row of its ring moved
    np.testing.assert_array_equal(np.asarray(st.wk)[:, 1], before)
    for s in seqs:
        seqs[s] += [int(t) for t in np.asarray(toks)[s, :np.asarray(adv)[s]]]
    for _ in range(4):
        st, toks = engine.decode(st, *_greedy(3))
        for s in seqs:
            seqs[s].append(int(np.asarray(toks)[s]))
    assert len(seqs[2]) > W + 2          # the short one wrapped too
    for s, ids in seqs.items():
        want = ref.logits(w, CUT, np.asarray(ids[:-1]), 0, len(ids) - 1)
        best = [int(t) for t in np.asarray(want.argmax(-1))[first[s] - 1:]]
        assert ids[first[s]:] == best, f"slot {s}"
    counts = engine.moe_counters()
    # 6 expert layers a step; 3 + 4 + 4 steps
    assert counts["layer_steps"] == 6 * 11
    assert 0 < counts["experts_hit"] <= 4 * counts["layer_steps"]
    assert counts["experts_hit"] <= counts["pairs"] \
        <= 3 * 2 * counts["layer_steps"]


def test_a_frozen_slot_midway_keeps_the_rows_it_needs(cut, engine):
    """A slot that spends its budget after 2 of 6 iterations: the 4
    frozen iterations write nothing into its ring, and its next real
    step is the reference's."""
    _, _, w = cut
    st = engine.new_state()
    ids = [int(t) for t in _tokens(19, seed=9)]
    tok, kv, true_len, bucket = engine.prefill(ids)
    st = engine.insert(st, kv, 0, true_len, tok, bucket)
    seq = ids + [tok]
    st, toks, adv = engine.decode_multi(
        st, *_greedy(3), 6, np.asarray([2, 0, 0], np.int32),
        np.full((3, 1), -1, np.int32))
    assert int(np.asarray(adv)[0]) == 2
    seq += [int(t) for t in np.asarray(toks)[0, :2]]
    st, toks = engine.decode(st, *_greedy(3))
    seq.append(int(np.asarray(toks)[0]))
    want = ref.logits(w, CUT, np.asarray(seq[:-1]), 0, len(seq) - 1)
    assert seq[19:] == [int(t) for t in np.asarray(want.argmax(-1))[18:]]


def test_a_slots_ring_is_counted_and_dense_models_have_none(engine):
    cfg = engine.cfg
    assert engine.ring_rows == W
    assert engine.ring_bytes() == 6 * 3 * W * 2 * (16 + 16) * 4
    assert engine.kv_row_bytes() == 2 * 2 * (16 + 16) * 4
    from ome_tpu.perf.hbm import HbmAccountant, kv_capacity_bytes
    from ome_tpu.telemetry.registry import Registry
    part = HbmAccountant.for_engine(engine, Registry()).update(engine)
    assert part["window_ring"] == engine.ring_bytes()
    assert part["kv_cache"] == kv_capacity_bytes(engine) \
        == 3 * 128 * engine.kv_row_bytes()
    from ome_tpu.models.config import tiny_test
    dense_cfg = tiny_test().replace(dtype=jnp.float32)
    dense = InferenceEngine(
        llama.init_params(jax.random.PRNGKey(0), dense_cfg), dense_cfg,
        max_slots=2, max_seq=64)
    st = dense.new_state()
    assert st.wk is None and dense.ring_bytes() == 0 == dense.ring_rows
    assert len(jax.tree.leaves(st)) == 5     # k, v, lengths, tokens, adapters
    # a ring no sequence can wrap is every row: max_seq under the window
    short = InferenceEngine(engine.params, cfg, max_slots=1, max_seq=4)
    assert short.ring_rows == 4


# -- the expert layer that is told which experts it holds --------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """EP-4 over 8 experts under sigmoid routing with a non-zero
    selection bias: the routed parts the four shares give, with the
    shared expert (which every chip computes alike) counted once,
    equal the uncut layer; the bias picks and does not weigh."""
    uncut = _cfg(HF)
    p = _params(uncut)
    lp = {k: v[1] for k, v in p["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64))
    h = llama.block_norm(x, lp, "mlp_norm", uncut)
    whole = llama.moe_mlp(h, lp, uncut)
    routed, hits = 0.0, 0
    for r in range(4):
        cfg = uncut.replace(num_experts=2, num_experts_total=8,
                            expert_offset=2 * r)
        held = dict(lp, **{k: lp[k][2 * r:2 * r + 2]
                           for k in ("we_gate", "we_up", "we_down")})
        part, (hit, pairs) = llama.moe_mlp_ragged(h, held, cfg,
                                                  with_stats=True)
        routed = routed + part
        hits += int(pairs)
    assert hits == 18 * 2          # every pair landed on one share
    shared = whole - llama.moe_mlp_ragged(h, lp, uncut)
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(whole), atol=2e-6)
    # against the plain reference's whole layer (mixer left out: the
    # expert layer alone, through the post-MLP norm)
    w = _ref_weights(HF, p)
    leaf = lambda name: w["moe"][name][1]            # noqa: E731
    s = jax.nn.sigmoid(h.reshape(18, 64) @ leaf("router"))
    _, idx = jax.lax.top_k(s + leaf("router_bias"), 2)
    top = jnp.take_along_axis(s, idx, -1)
    top = 2.826 * top / top.sum(-1, keepdims=True)
    want = ref._swiglu(h.reshape(18, 64), leaf("ws_gate"), leaf("ws_up"),
                       leaf("ws_down"))
    for t in range(18):
        for j in range(2):
            e = int(idx[t, j])
            want = want.at[t].add(top[t, j] * ref._swiglu(
                h.reshape(18, 64)[t], leaf("we_gate")[e],
                leaf("we_up")[e], leaf("we_down")[e]))
    np.testing.assert_allclose(np.asarray(whole.reshape(18, 64)),
                               np.asarray(want), atol=2e-6)
    # the bias moved the selection: without it other experts are picked
    _, plain = jax.lax.top_k(s, 2)
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(idx))).any()


# -- each term of the block matters -------------------------------------


def _without(p, cfg, what):
    """The model with one term of the published block taken out."""
    if what == "embed_scale":
        return p, cfg.replace(embed_scale=False)
    if what == "rope_skip_global":            # rotary on every layer
        return p, cfg.replace(rope_skip_global=False)
    if what == "attn_output_gate":
        return p, cfg.replace(attn_output_gate=False)
    if what == "post_block_norms":
        return p, cfg.replace(post_block_norms=False)
    if what == "qk_norm":
        return p, cfg.replace(qk_norm=False)
    if what == "rotary":                      # none on the window layers
        return p, cfg.replace(rope_theta=1e30)
    if what == "window":                      # every layer sees every row
        return p, cfg.replace(sliding_window=512)
    if what == "routed_scaling_factor":
        return p, cfg.replace(routed_scaling_factor=1.0)
    if what == "shared_expert":
        zero = {k: jnp.zeros_like(p["layers"][k])
                for k in ("ws_gate", "ws_up", "ws_down")}
        return dict(p, layers=dict(p["layers"], **zero)), cfg
    if what == "router_bias":
        return dict(p, layers=dict(
            p["layers"],
            router_bias=jnp.zeros_like(p["layers"]["router_bias"]))), cfg
    raise KeyError(what)


@pytest.mark.parametrize("what", [
    "embed_scale", "rope_skip_global", "attn_output_gate",
    "post_block_norms", "qk_norm", "rotary", "window",
    "routed_scaling_factor", "shared_expert", "router_bias"])
def test_a_model_without_the_term_differs_from_the_reference(cut, what):
    """W_g, the four norms, sqrt(D) and the rest: leave one out and
    the logits differ from the reference by far more than the
    tolerance. Rotary on window layers ONLY is two of the cases:
    `rope_skip_global` off puts it on the global layers too, `rotary`
    takes it off the window layers, and each differs."""
    cfg, p, w = cut
    toks = _tokens(29, seed=2)
    want = ref.logits(w, CUT, toks, 0, 29)
    p2, cfg2 = _without(p, cfg, what)
    lg, _ = llama.forward(p2, cfg2, jnp.asarray(toks[None]))
    assert float(jnp.abs(lg[0] - want).max()) > 100 * ATOL, what


# -- what start-up refuses, with the reason -----------------------------


@pytest.mark.parametrize("kw,reason", [
    (dict(kv_block=128), "ONE block table"),
    (dict(prefix_cache_bytes=1 << 20), "LAST rows"),
    (dict(prefix_host_bytes=1 << 20), "LAST rows"),
])
def test_engine_refuses_what_assumes_every_row_is_kept(cut, kw, reason):
    cfg, p, _ = cut
    with pytest.raises(ValueError, match=reason) as e:
        InferenceEngine(p, cfg, max_slots=2, max_seq=128, **kw)
    assert "ring" in str(e.value)


@pytest.mark.parametrize("flags,reason", [
    (["--kv-block", "128"], "ONE block table"),
    (["--kv-blocks", "64"], "ONE block table"),
    (["--prefix-cache-mb", "64"], "LAST rows"),
    (["--prefix-cache-host-mb", "64"], "LAST rows"),
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


def test_serve_builds_the_engine_and_verify_is_refused(tmp_path):
    from ome_tpu.engine import serve
    with open(tmp_path / "config.json", "w") as f:
        json.dump(CUT, f)
    args = serve.build_parser().parse_args(
        ["--model-dir", str(tmp_path), "--random-weights", "--dtype",
         "float32", "--max-slots", "2", "--max-seq", "128",
         "--prefix-cache-mb", "0"])
    eng = serve.load_engine(args)
    assert eng.cfg.window_layers == 6 and eng.cfg.moe_impl == "ragged"
    assert eng.cfg.router_width == 8 and eng.cfg.num_experts == 4
    with pytest.raises(ValueError, match="rolled back"):
        eng.verify(eng.new_state(), np.zeros((2, 2), np.int32),
                   np.zeros(2, np.int32), *_greedy(2))
    # a model whose slots own full-length rows only is refused nothing
    assert core.slot_state_refusals(
        ModelConfig(), kv_block=128, spec_tokens=4) == []
    # a sliding-window model outside the pool's path is told which
    # property keeps it out and what to do
    from ome_tpu.models.config import tiny_test
    uniform = tiny_test().replace(sliding_window=16)
    with pytest.raises(ValueError, match="a sliding window.*drop --kv-block"):
        InferenceEngine(llama.init_params(jax.random.PRNGKey(0), uniform),
                        uniform, max_slots=2, max_seq=64, kv_block=16)


def test_a_prefix_cache_nobody_asked_for_is_off_and_said(tmp_path, caplog):
    """`--prefix-cache-mb` defaults to 256 MiB; for a ring model the
    default is 0, said in the log, and only a value that was asked
    for stops the server."""
    from ome_tpu.engine import serve
    with open(tmp_path / "config.json", "w") as f:
        json.dump(CUT, f)
    args = serve.build_parser().parse_args(
        ["--model-dir", str(tmp_path), "--random-weights", "--dtype",
         "float32", "--max-slots", "2", "--max-seq", "128"])
    assert args.prefix_cache_mb is None
    with caplog.at_level("INFO", logger=serve.log.name):
        eng = serve.load_engine(args)
    assert args.prefix_cache_mb == 0 and eng.prefix_cache.capacity_bytes == 0
    assert "prefix cache off" in caplog.text and "ring" in caplog.text


# -- the period scan's small repairs -------------------------------------


def test_a_depth_that_is_no_whole_number_of_periods_runs():
    """Six layers in periods of four: one whole period and a tail of
    two window layers (it used to assert); prefill then decode agree
    with one full pass."""
    from ome_tpu.models.config import tiny_test
    cfg = tiny_test().replace(
        num_layers=6, alt_sliding_window=True, sliding_pattern=4,
        sliding_window=W, window_ring=True, dtype=jnp.float32)
    assert (cfg.window_layers, cfg.kv_cache_layers) == (5, 1)
    p = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(_tokens(20, seed=6)[None] % 512)
    full, _ = llama.forward(p, cfg, toks)
    cache = _per_slot(llama.KVCache.create(cfg, 1, 32))
    lg, cache = llama.forward(p, cfg, toks[:, :11], cache=cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :11]),
                               atol=ATOL)
    for t in range(11, 20):
        lg, cache = llama.forward(p, cfg, toks[:, t:t + 1], cache=cache)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]), atol=ATOL)


def test_the_period_scan_honours_leading_dense_layers_and_a_share():
    """`_alt_window_scan` used to ignore `first_k_dense` and an expert
    share: the dense block's leaves are the ones layers 0 and 1 run
    (zeroing the expert block's MLP of those indices changes nothing,
    zeroing the dense block's does), and a share without the ragged
    dispatch is refused."""
    cfg = _cfg(CUT)
    p = _params(cfg)
    toks = jnp.asarray(_tokens(12)[None])
    base, _ = llama.forward(p, cfg, toks)
    gone = dict(p, dense_layers=dict(
        p["dense_layers"],
        w_down=jnp.zeros_like(p["dense_layers"]["w_down"])))
    lg, _ = llama.forward(gone, cfg, toks)
    assert float(jnp.abs(lg - base).max()) > 100 * ATOL
    with pytest.raises(ValueError, match="ragged"):
        llama.forward(p, cfg.replace(moe_impl="dense"), toks)


def test_int8_quantization_covers_both_blocks(cut):
    cfg, p, _ = cut
    from ome_tpu.models.quant import QTensor, quantize_params
    q = quantize_params(p, mode="int8")
    for block, names in (("layers", ("wq", "w_ogate", "we_up", "ws_down")),
                         ("dense_layers", ("wq", "w_ogate", "w_gate",
                                           "w_down"))):
        for name in names:
            assert isinstance(q[block][name], QTensor), (block, name)
    assert not isinstance(q["layers"]["router"], QTensor)
    toks = jnp.asarray(_tokens(29)[None])
    a, _ = llama.forward(p, cfg, toks)
    b, _ = llama.forward(q, cfg, toks)
    assert 0 < float(jnp.abs(a - b).max()) < 0.3 * float(a.std())


# -- the checkpoint's names ---------------------------------------------


def test_a_checkpoint_under_the_published_names_loads(tmp_path):
    """safetensors under afmoe's tensor names (`self_attn.gate_proj`,
    `pre_mlp_layernorm`, `mlp.router.gate`, `mlp.expert_bias`,
    `mlp.shared_experts.*`, `mlp.experts.N.*`), all 8 experts and a
    vocabulary of 256: the cut config loads its 4 held experts and its
    first 160 rows, and serves the logits of the tree it was written
    from."""
    from ome_tpu.models import checkpoint as ck
    uncut = _cfg(HF)
    p = _params(uncut)
    t = {}

    def lin(name, a):                     # [in, out] -> HF [out, in]
        t[name] = np.asarray(a, np.float32).reshape(a.shape[0], -1).T

    for i in range(8):
        blk, j = (p["dense_layers"], i) if i < 2 else (p["layers"], i - 2)
        pre = f"model.layers.{i}."
        for ours, theirs in (("attn_norm", "input_layernorm"),
                             ("attn_post_norm", "post_attention_layernorm"),
                             ("mlp_norm", "pre_mlp_layernorm"),
                             ("mlp_post_norm", "post_mlp_layernorm"),
                             ("q_norm", "self_attn.q_norm"),
                             ("k_norm", "self_attn.k_norm")):
            t[pre + theirs + ".weight"] = np.asarray(blk[ours][j])
        for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                             ("w_ogate", "gate")):
            # out-major [heads, Dh, D]: HF's [out, in] with heads split
            t[pre + f"self_attn.{theirs}_proj.weight"] = np.asarray(
                blk[ours][j], np.float32).reshape(-1, 64)
        t[pre + "self_attn.o_proj.weight"] = np.asarray(
            blk["wo"][j]).reshape(-1, 64).T
        if i < 2:
            for n in ("gate", "up", "down"):
                lin(pre + f"mlp.{n}_proj.weight", blk[f"w_{n}"][j])
            continue
        lin(pre + "mlp.router.gate.weight", blk["router"][j])
        t[pre + "mlp.expert_bias"] = np.asarray(blk["router_bias"][j])
        for n in ("gate", "up", "down"):
            lin(pre + f"mlp.shared_experts.{n}_proj.weight",
                blk[f"ws_{n}"][j])
            for e in range(8):
                lin(pre + f"mlp.experts.{e}.{n}_proj.weight",
                    blk[f"we_{n}"][j, e])
    t["model.embed_tokens.weight"] = np.asarray(p["embed"])
    t["model.norm.weight"] = np.asarray(p["final_norm"])
    t["lm_head.weight"] = np.asarray(p["lm_head"]).T
    ck.save_safetensors(str(tmp_path / "model.safetensors"), t)
    cut_hf = dict(CUT, vocab_size=160)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(cut_hf, f)
    loaded, cfg = ck.load_params(str(tmp_path), dtype=jnp.float32)
    assert "AfmoeForCausalLM" in ck.SUPPORTED_ARCHITECTURES
    assert loaded["layers"]["we_gate"].shape == (6, 4, 64, 32)
    assert loaded["layers"]["router_bias"].shape == (6, 8)
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


# -- the decode kernel over a layer of a stacked slab --------------------


def test_flash_decode_over_a_stacked_slab_is_the_layers_own(monkeypatch):
    """The Pallas decode kernel (interpret mode) handed the stacked
    slabs [L, B, S, K, D] and a layer index gives, bit for bit, what
    it gives for that layer sliced out, and the XLA path's numbers."""
    from ome_tpu.ops.attention import attention
    L, B, S, H, K, D = 3, 2, 256, 8, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (L, B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (L, B, S, K, D), jnp.float32)
    pos = jnp.asarray([[200], [37]], jnp.int32)
    kv_len = pos[:, 0] + 1
    for layer in (0, 2):
        stacked = attention(q, k, v, positions=pos, kv_len=kv_len,
                            backend="pallas_interpret",
                            layer=jnp.asarray(layer, jnp.int32))
        sliced = attention(q, k[layer], v[layer], positions=pos,
                           kv_len=kv_len, backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(sliced))
        plain = attention(q, k, v, positions=pos, kv_len=kv_len,
                          backend="xla", layer=layer)
        np.testing.assert_allclose(np.asarray(stacked), np.asarray(plain),
                                   atol=2e-5)
