"""Model correctness tests: causality, decode/prefill consistency,
MoE routing, parameter accounting."""

import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _parent_proj import in_major, parent_form, stored
from ome_tpu.models import config as cfgs
from ome_tpu.models import llama


@pytest.fixture(scope="module")
def tiny():
    return cfgs.tiny_test().replace(dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_params(tiny):
    return llama.init_params(jax.random.PRNGKey(0), tiny)


class TestForward:
    def test_shapes(self, tiny, tiny_params):
        tokens = jnp.ones((2, 16), jnp.int32)
        logits, cache = llama.forward(tiny_params, tiny, tokens)
        assert logits.shape == (2, 16, tiny.vocab_size)
        assert logits.dtype == jnp.float32
        assert cache is None

    def test_causality(self, tiny, tiny_params):
        """Changing a future token must not affect earlier logits."""
        rng = jax.random.PRNGKey(1)
        tokens = jax.random.randint(rng, (1, 12), 0, tiny.vocab_size)
        logits_a, _ = llama.forward(tiny_params, tiny, tokens)
        tampered = tokens.at[0, 8].set((tokens[0, 8] + 7) % tiny.vocab_size)
        logits_b, _ = llama.forward(tiny_params, tiny, tampered)
        assert jnp.allclose(logits_a[0, :8], logits_b[0, :8], atol=1e-5)
        assert not jnp.allclose(logits_a[0, 8:], logits_b[0, 8:], atol=1e-3)

    def test_decode_matches_prefill(self, tiny, tiny_params):
        """Cached chunked decode must reproduce uncached prefill logits."""
        rng = jax.random.PRNGKey(2)
        T = 10
        tokens = jax.random.randint(rng, (2, T), 0, tiny.vocab_size)
        full_logits, _ = llama.forward(tiny_params, tiny, tokens)

        cache = llama.KVCache.create(tiny, batch=2, max_seq=32,
                                     dtype=jnp.float32)
        pre_logits, cache = llama.forward(tiny_params, tiny, tokens[:, :6],
                                          cache=cache)
        assert jnp.allclose(pre_logits, full_logits[:, :6], atol=1e-4)
        # decode one token at a time
        for t in range(6, T):
            step_logits, cache = llama.forward(tiny_params, tiny,
                                               tokens[:, t:t + 1], cache=cache)
            assert jnp.allclose(step_logits[:, 0], full_logits[:, t],
                                atol=1e-4), f"mismatch at {t}"
        assert int(cache.index) == T

    def test_jit_decode_compiles_once(self, tiny, tiny_params):
        decode = jax.jit(lambda p, tok, c: llama.forward(p, tiny, tok, cache=c))
        cache = llama.KVCache.create(tiny, batch=1, max_seq=32)
        tok = jnp.zeros((1, 1), jnp.int32)
        logits, cache = decode(tiny_params, tok, cache)
        logits, cache = decode(tiny_params, tok + 1, cache)
        assert int(cache.index) == 2

    def test_tied_embeddings(self):
        cfg = cfgs.tiny_test().replace(tie_word_embeddings=True,
                                       dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        assert "lm_head" not in params
        logits, _ = llama.forward(params, cfg, jnp.ones((1, 4), jnp.int32))
        assert logits.shape == (1, 4, cfg.vocab_size)

    def test_sliding_window(self, tiny, tiny_params):
        cfg = tiny.replace(sliding_window=4)
        tokens = jnp.ones((1, 12), jnp.int32)
        logits, _ = llama.forward(tiny_params, cfg, tokens)
        assert logits.shape == (1, 12, cfg.vocab_size)


class TestRoPE:
    def test_llama3_scaling_matches_reference_formula(self):
        """Check all three bands against transformers'
        _compute_llama3_parameters (modeling_rope_utils.py) in numpy."""
        import numpy as np
        cfg = cfgs.tiny_test().replace(
            head_dim=128, rope_theta=500000.0,
            rope_scaling={"rope_type": "llama3", "factor": 8.0,
                          "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                          "original_max_position_embeddings": 8192})
        got = np.asarray(llama._rope_frequencies(cfg))

        inv = 1.0 / cfg.rope_theta ** (np.arange(64) / 64)
        lo_wave = 8192 / 1.0
        hi_wave = 8192 / 4.0
        want = []
        for f in inv:
            wl = 2 * np.pi / f
            if wl < hi_wave:
                want.append(f)
            elif wl > lo_wave:
                want.append(f / 8.0)
            else:
                smooth = (8192 / wl - 1.0) / (4.0 - 1.0)
                want.append((1 - smooth) * f / 8.0 + smooth * f)
        np.testing.assert_allclose(got, np.array(want, np.float32), rtol=1e-6)


class TestMoE:
    def test_shared_experts_contribute(self):
        cfg = cfgs.tiny_test(moe=True).replace(dtype=jnp.float32,
                                               num_shared_experts=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        assert "ws_gate" in params["layers"]
        tokens = jnp.ones((1, 4), jnp.int32)
        logits, _ = llama.forward(params, cfg, tokens)
        # zeroing the shared expert weights must change the output
        params2 = dict(params)
        params2["layers"] = dict(params["layers"])
        params2["layers"]["ws_down"] = jnp.zeros_like(
            params["layers"]["ws_down"])
        logits2, _ = llama.forward(params2, cfg, tokens)
        assert not jnp.allclose(logits, logits2, atol=1e-5)

    def test_moe_forward_and_grad(self):
        cfg = cfgs.tiny_test(moe=True).replace(dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        assert "router" in params["layers"]
        tokens = jnp.ones((2, 8), jnp.int32)
        logits, _ = llama.forward(params, cfg, tokens)
        assert logits.shape == (2, 8, cfg.vocab_size)
        g = jax.grad(llama.loss_fn)(params, cfg, tokens, tokens)
        assert jnp.isfinite(g["layers"]["router"]).all()


class TestAccounting:
    def test_llama3_8b_param_count(self):
        cfg = cfgs.llama3_8b()
        # analytic count (no materialization): embed + head + layers
        L, D, H, K, Dh, F, V = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                                cfg.num_kv_heads, cfg.head_dim,
                                cfg.intermediate_size, cfg.vocab_size)
        n = V * D * 2 + D  # embed + lm_head + final norm
        n += L * (2 * D + D * H * Dh + 2 * D * K * Dh + H * Dh * D + 3 * D * F)
        assert n == pytest.approx(8.03e9, rel=0.01)

    def test_loss_decreases_with_sgd(self):
        cfg = cfgs.tiny_test().replace(dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                    cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)

        @jax.jit
        def step(p):
            l, g = jax.value_and_grad(llama.loss_fn)(p, cfg, tokens, targets)
            return l, jax.tree.map(lambda w, gw: w - 0.05 * gw, p, g)

        l0, params = step(params)
        for _ in range(5):
            l1, params = step(params)
        assert l1 < l0


# -- the attention projections lie out-major (PR 41) ---------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the benchmark's five families at the toy size its own rehearsals run:
# family -> (fixture directory, configuration, reference module,
# reference's groups of leaves -> the program's blocks)
FAMILIES = {
    "dense": ("fixture", "tiny-qwen3", "dense_gqa", {None: "layers"}),
    "hybrid": ("fixture_hybrid", "tiny-qwen3-next", "hybrid_gdn_moe",
               {"full": "layers", "linear": "linear_layers"}),
    "window": ("fixture_window", "tiny-afmoe", "window_moe",
               {"moe": "layers", "dense": "dense_layers"}),
    "preroute": ("fixture_preroute", "tiny-smallthinker", "preroute_moe",
                 {"layers": "layers"}),
    "latent": ("fixture_latent", "tiny-pangu", "latent_moe",
               {"moe": "layers", "dense": "dense_layers"}),
}


def _family(name, dtype, **widths):
    fixture, config, ref, groups = FAMILIES[name]
    with open(os.path.join(_ROOT, "tests", "benchmark", fixture,
                           "benchmark", "configs", config + ".json")) as f:
        file = json.load(f)
    hf = {k: v for k, v in file.items()
          if k not in ("source", "reduced", "assumed", "benchmark")}
    hf.update(widths)
    cfg = cfgs.ModelConfig.from_hf_config(hf).replace(dtype=dtype)
    if cfg.is_moe:
        cfg = cfg.replace(moe_impl="ragged")
    params = jax.jit(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))()
    sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
    try:
        ref = importlib.import_module("reference." + ref)
    finally:
        sys.path.pop(0)
    return hf, cfg, params, ref, groups


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_init_params_draws_what_the_benchmarks_reference_draws(family):
    """`--random-weights` at the one seed gives every leaf the values
    the configuration's plain reference draws from its own code
    (benchmark/reference/*: `normal(key, (L, D, heads, Dh))` for the
    attention projections): an out-major leaf is drawn in that shape
    and key order and re-laid after the draw, never drawn in a new
    shape, so `check.py`'s reference still scores the served model."""
    hf, cfg, params, ref, groups = _family(family, jnp.bfloat16)
    w = ref.init_weights(hf)
    seen = set()
    for group, block in groups.items():
        theirs = w if group is None else w[group]
        for name, mine in params[block].items():
            want = stored(name, theirs[name])
            assert mine.shape == want.shape, (block, name)
            assert (np.asarray(mine.astype(jnp.float32))
                    == np.asarray(want.astype(jnp.float32))).all(), name
            seen.add(name)
    if cfg.mla:     # a latent model's one out-major leaf: [H, qk, q_rank]
        assert set(llama.OUT_MAJOR) & seen == {"wq_b"}
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        for block in groups.values():
            assert params[block]["wq_b"].shape[1:] == (
                cfg.num_heads, qk, cfg.q_lora_rank)
        return
    assert set(llama.OUT_MAJOR) & seen >= {"wq", "wk", "wv"}
    D = cfg.hidden_size
    for block in groups.values():
        for name in set(llama.OUT_MAJOR) & set(params[block]):
            heads = cfg.num_heads if name in ("wq", "w_ogate") \
                else cfg.num_kv_heads
            assert params[block][name].shape[1:] == (heads, cfg.head_dim, D)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_out_major_projections_give_the_parents_logits_to_the_bit(family):
    """bfloat16, every family of the benchmark: one full pass, then a
    prompt and six decode steps through the cache (the slab and, where
    the family has one, the ring and the recurrent state), with the
    projections stored out-major and on the parent's form of the same
    leaves: the same bits."""
    hf, cfg, params, _, _ = _family(family, jnp.bfloat16)
    toks = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (1, 20)))

    def passes():
        full, _ = llama.forward(params, cfg, toks)
        out = [full]
        cache = llama.KVCache.create(cfg, 1, 32)
        cache = dataclasses.replace(
            cache, index=jnp.zeros((1,), jnp.int32))
        n = jnp.asarray([14], jnp.int32)
        lg, cache = llama.forward(params, cfg, toks[:, :14], cache=cache,
                                  valid_len=n)
        out.append(lg)
        for t in range(14, 20):
            lg, cache = llama.forward(params, cfg, toks[:, t:t + 1],
                                      cache=cache)
            out.append(lg)
        return [np.asarray(x.astype(jnp.float32)) for x in out]

    got = passes()
    with parent_form():
        want = passes()
    assert float(np.std(want[0])) > 0.01
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_parent_form_is_the_in_major_dot():
    """The helper the comparisons above stand on: under `parent_form`
    an out-major leaf reaches the in-major einsum as [D, heads, Dh]."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 4, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 64))
    want = jnp.einsum("bsd,dhk->bshk", x, w)
    lay = llama.to_out_major(w)
    assert lay.shape == (4, 16, 64)
    np.testing.assert_array_equal(np.asarray(in_major(lay)), np.asarray(w))
    got = llama._proj(x, lay, jnp.float32, out_dims=(4, 16), out_major=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    with parent_form():
        old = llama._proj(x, lay, jnp.float32, out_dims=(4, 16),
                          out_major=True)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(
        llama._proj(x, w, jnp.float32, out_dims=(4, 16))))


# -- a right-padded prompt attends over its true length (PR 48) ----------


def _kernel_sized(family):
    """The family's toy model with heads the prefill kernels take
    (ops/flash.py: 128 lanes a head), float32."""
    widths = dict(qk_nope_head_dim=128, v_head_dim=128) \
        if family == "latent" else dict(head_dim=128)
    _, cfg, params, _, _ = _family(family, jnp.float32, **widths)
    return cfg, params


def _per_slot_cache(cfg, rows):
    cache = llama.KVCache.create(cfg, 1, rows)
    return dataclasses.replace(cache, index=jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_padded_prompt_is_the_unpadded_one(family, backend, monkeypatch):
    """Every layer scan of `forward`: a prompt of 40 tokens right-padded
    to a bucket of 96 (three query blocks of 32: real, crossed by the
    length, padding) with `valid_len` gives the last real row's logits
    and leaves the first 40 cache rows, the rings and the recurrent
    state as the unpadded prompt does, by XLA's attention and by the
    kernels (which the unpadded 40 rows do not fit: XLA's there)."""
    from ome_tpu.engine.core import prefill_attn_block_kinds
    monkeypatch.setenv("OME_ATTN_BACKEND", backend)
    cfg, params = _kernel_sized(family)
    T, S = 40, 96
    kinds = prefill_attn_block_kinds(cfg, S, S, 0, T)
    assert (kinds["none"] > 0) == (backend != "xla"), kinds
    toks = np.random.RandomState(5).randint(1, cfg.vocab_size, (1, T))
    padded = np.zeros((1, S), np.int32)
    padded[:, :T] = toks
    n = jnp.asarray([T], jnp.int32)
    got, have = llama.forward(params, cfg, jnp.asarray(padded),
                              cache=_per_slot_cache(cfg, S),
                              logits_at=n - 1, valid_len=n)
    want, kept = llama.forward(params, cfg, jnp.asarray(toks),
                               cache=_per_slot_cache(cfg, S))
    assert float(np.std(np.asarray(want[0, T - 1]))) > 1e-3
    np.testing.assert_allclose(np.asarray(got[0, 0]),
                               np.asarray(want[0, T - 1]), atol=2e-4)
    for name in ("k", "v"):         # [L, B, rows, ..]: the real rows
        np.testing.assert_allclose(
            np.asarray(getattr(have, name))[:, :, :T],
            np.asarray(getattr(kept, name))[:, :, :T], atol=2e-4,
            err_msg=name)
    state = {name: getattr(have, name, None) for name in ("wk", "wv", "rec")}
    assert any(x is not None for x in state.values()) == (
        family in ("hybrid", "window", "preroute")), family
    for name, x in state.items():
        if x is None:
            continue
        for a, b in zip(jax.tree.leaves(x),
                        jax.tree.leaves(getattr(kept, name))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, err_msg=name)


@pytest.mark.parametrize("family", ["dense", "latent"])
def test_a_decode_step_keeps_every_row_whatever_valid_len_says(family):
    """S == 1 is the multi-token loop's step, whose `valid_len` 0 / 1
    freezes a slot and pads nothing: the step attends over `index + 1`
    rows as with no `valid_len` at all, to the bit (these two families
    read `valid_len` nowhere else)."""
    _, cfg, params, _, _ = _family(family, jnp.float32)
    toks = jnp.asarray(np.random.RandomState(6).randint(
        1, cfg.vocab_size, (2, 9)))
    cache = llama.KVCache.create(cfg, 2, 16)
    cache = dataclasses.replace(cache, index=jnp.zeros((2,), jnp.int32))
    _, cache = llama.forward(params, cfg, toks[:, :8], cache=cache)
    want, kept = llama.forward(params, cfg, toks[:, 8:], cache=cache)
    got, have = llama.forward(params, cfg, toks[:, 8:], cache=cache,
                              valid_len=jnp.asarray([0, 1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(have.k), np.asarray(kept.k))
