"""MoE: ragged (sorted grouped-GEMM) dispatch vs dense reference.

The ragged path must be numerically equivalent to computing every
expert — it only skips the experts the router didn't pick. Also
checks the degenerate routing cases (all tokens on one expert) and
that the serving config flows through forward().
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test


def _cfg(**kw):
    return tiny_test(moe=True).replace(dtype=jnp.float32, **kw)


def test_ragged_matches_dense():
    cfg = _cfg()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.hidden_size),
                          jnp.float32)
    dense = llama.moe_mlp_dense(x, lp, cfg)
    ragged = llama.moe_mlp_ragged(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               atol=1e-5)


def test_ragged_matches_dense_under_jit_bf16():
    cfg = tiny_test(moe=True)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg.hidden_size),
                          jnp.float32).astype(cfg.dtype)
    dense = jax.jit(llama.moe_mlp_dense, static_argnums=2)(x, lp, cfg)
    ragged = jax.jit(llama.moe_mlp_ragged, static_argnums=2)(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(dense, np.float32),
                               np.asarray(ragged, np.float32), atol=2e-2)


def test_ragged_single_expert_hotspot():
    """All tokens routed to one expert (bincount ragged edge)."""
    cfg = _cfg()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    lp = dict(jax.tree.map(lambda a: a[0], params["layers"]))
    # bias the router so expert 3 wins everywhere
    router = np.zeros(lp["router"].shape, np.float32)
    router[:, 3] = 10.0
    router[:, 5] = 5.0
    lp["router"] = jnp.asarray(router)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, cfg.hidden_size),
                          jnp.float32)
    dense = llama.moe_mlp_dense(x, lp, cfg)
    ragged = llama.moe_mlp_ragged(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               atol=1e-5)


def test_forward_with_ragged_impl():
    cfg = _cfg(moe_impl="ragged")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    ragged_logits, _ = llama.forward(params, cfg, tok)
    dense_logits, _ = llama.forward(params, _cfg(), tok)
    np.testing.assert_allclose(np.asarray(ragged_logits),
                               np.asarray(dense_logits), atol=1e-4)


# -- the combine: each token's k results fetched back and summed ---------


def _plain_experts(xf, weights, idx, p, cfg, lo=0):
    """What the experts held give each token, one pair at a time in a
    float32 loop: the pairs routed to experts lo .. lo + held - 1, each
    times the router's weight, added up in the order the router named
    them."""
    def w(name):
        return np.asarray(p[name], np.float32)

    gate_w, up_w, down_w = w("we_gate"), w("we_up"), w("we_down")
    held = gate_w.shape[0]
    x = np.asarray(xf, np.float32)
    wt = np.asarray(weights, np.float32).reshape(len(x), -1)
    ix = np.asarray(idx).reshape(len(x), -1)
    out = np.zeros_like(x)
    for t in range(len(x)):
        for j in range(ix.shape[1]):
            e = ix[t, j] - lo
            if not 0 <= e < held:
                continue
            gate, up = x[t] @ gate_w[e], x[t] @ up_w[e]
            if cfg.moe_bias:
                gate, up = gate + w("we_gate_b")[e], up + w("we_up_b")[e]
            y = (gate / (1 + np.exp(-gate)) * up) @ down_w[e]
            if cfg.moe_bias:
                y = y + w("we_down_b")[e]
            out[t] += wt[t, j] * y
    return out


def _layer_and_routing(cfg, tokens=24, seed=0):
    """One layer's parameters, `tokens` inputs and what the router
    makes of them."""
    params = llama.init_params(jax.random.PRNGKey(seed), cfg)
    lp = dict(jax.tree.map(lambda a: a[0], params["layers"]))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (1, tokens, cfg.hidden_size), jnp.float32)
    weights, idx = llama._route(x, lp, cfg)
    return lp, x[0], weights[0], idx[0]


def _held(lp, lo, n):
    return dict(lp, **{k: lp[k][lo:lo + n]
                       for k in ("we_gate", "we_up", "we_down")})


def _poison_rows_behind_the_last_group(monkeypatch):
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(group_sizes), out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)


COMBINE_CASES = ["all_held", "held_range", "poisoned_rows", "stacked",
                 "bias", "k1", "chunked", "bf16_rounded_once"]


@pytest.mark.parametrize("case", COMBINE_CASES)
def test_combine_gives_each_token_its_pairs_sum(case, monkeypatch):
    """`expert_compute` fetches a token's k results back from the
    sorted rows by `Dispatch.place` and sums them: the same numbers as
    a plain per-token loop, whichever experts are held, whatever lies
    in the rows no group owns, through every caller's way in."""
    cfg = _cfg(moe_impl="ragged")
    if case == "k1":
        cfg = cfg.replace(experts_per_token=1)
    if case == "bias":
        cfg = cfg.replace(moe_bias=True)
    lp, x, weights, idx = _layer_and_routing(cfg)
    lo = 0
    if case in ("held_range", "poisoned_rows", "bf16_rounded_once"):
        lo, lp = 3, _held(lp, 3, 3)
    here = (np.asarray(idx) >= lo) \
        & (np.asarray(idx) < lo + lp["we_gate"].shape[0])
    assert here.any() and (lo == 0 or not here.all())
    if case == "poisoned_rows":
        _poison_rows_behind_the_last_group(monkeypatch)
    if case == "bias":
        E, F, D = lp["we_gate"].shape[0], lp["we_gate"].shape[2], \
            cfg.hidden_size
        keys = jax.random.split(jax.random.PRNGKey(9), 3)
        lp.update(we_gate_b=jax.random.normal(keys[0], (E, F)),
                  we_up_b=jax.random.normal(keys[1], (E, F)),
                  we_down_b=jax.random.normal(keys[2], (E, D)))
    if case == "stacked":
        # the layers' stacks [Ls, E, ..] and a traced layer index
        stacks = {k: jnp.stack([lp[k] * 0 + 7.0, lp[k], lp[k] * 0 - 7.0])
                  for k in ("we_gate", "we_up", "we_down")}
        got, _ = jax.jit(
            lambda layer: llama.ragged_experts(
                x, weights, idx, dict(lp, **stacks, expert_layer=layer),
                cfg))(jnp.int32(1))
    elif case == "chunked":
        monkeypatch.setattr(llama, "_MOE_PAIRS_LIMIT", 4096)
        monkeypatch.setattr(llama, "_MOE_PAIRS_CHUNK", 4096)
        assert llama._moe_token_chunks(24, 2, cfg.hidden_size, 4) == 8
        got = llama.moe_mlp_ragged(x[None], lp, cfg)[0]
    elif case == "bf16_rounded_once":
        # the grouped matmul's own result, caught on its way out, is
        # what the sum starts from: float32 sum, ONE rounding
        caught, real = [], jax.lax.ragged_dot
        monkeypatch.setattr(
            jax.lax, "ragged_dot",
            lambda *a, **kw: caught.append(real(*a, **kw)) or caught[-1])
        low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), lp)
        plan = llama.expert_dispatch(weights, idx, 3, lo)
        got, _ = llama.expert_compute(x.astype(jnp.bfloat16), plan, low,
                                      cfg.replace(dtype=jnp.bfloat16))
        assert got.dtype == jnp.bfloat16 and len(caught) == 3
        rows = np.asarray(caught[-1].astype(jnp.float32))
        place, mine = np.asarray(plan.place), np.asarray(plan.mine)
        acc = np.zeros(got.shape, np.float32)
        for j in range(len(place)):
            acc += np.where(mine[j, :, None],
                            rows[place[j]]
                            * np.asarray(plan.weights)[j, :, None], 0)
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(jnp.asarray(acc).astype(jnp.bfloat16)
                       .astype(jnp.float32)))
        # and a sum that rounds after every pair is another number
        running = jnp.zeros(got.shape, jnp.bfloat16)
        for j in range(len(place)):
            term = (caught[-1][place[j]]
                    * plan.weights[j, :, None].astype(jnp.bfloat16))
            running = running + jnp.where(mine[j, :, None], term, 0)
        assert np.any(np.asarray(running != got))
        return
    else:
        got, (hit, pairs) = llama.ragged_experts(x, weights, idx, lp, cfg,
                                                 lo=lo)
        assert int(pairs) == here.sum()
        assert int(hit) == len(set(np.asarray(idx)[here].tolist()))
    want = _plain_experts(x, weights, idx, lp, cfg, lo)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # a token with no pair here gets exactly nothing
    assert not np.asarray(got)[~here.any(-1)].any()


@pytest.mark.parametrize("seed,T,k,E,lo,held", [
    (0, 33, 2, 8, 0, 8), (1, 64, 6, 16, 4, 4), (2, 17, 8, 5, 3, 2),
    (3, 1, 1, 4, 0, 4), (4, 40, 10, 512, 128, 128)])
def test_place_is_the_inverse_of_the_dispatchs_sort(seed, T, k, E, lo,
                                                    held):
    """Pair t * k + j lies at sorted row `place[j, t]`: that row's
    token is t, its group is the pair's held expert (behind every
    group when the expert is elsewhere), and every row is some pair's,
    for routings full of ties (an expert drawn many times, even twice
    by one token)."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, E, (T, k)).astype(np.int32)
    weights = rng.random((T, k), dtype=np.float32)
    plan = llama.expert_dispatch(jnp.asarray(weights), jnp.asarray(idx),
                                 held, lo)
    assert plan.place.shape == (k, T)
    place = np.asarray(plan.place).T
    assert sorted(place.reshape(-1).tolist()) == list(range(T * k))
    token_of = np.asarray(plan.token_of)
    np.testing.assert_array_equal(token_of[place],
                                  np.arange(T)[:, None].repeat(k, 1))
    local = np.where((idx >= lo) & (idx < lo + held), idx - lo, held)
    np.testing.assert_array_equal(np.asarray(plan.mine).T, local < held)
    np.testing.assert_array_equal(np.asarray(plan.weights).T, weights)
    counts = np.asarray(plan.counts)
    np.testing.assert_array_equal(
        counts, np.bincount(local.reshape(-1), minlength=held + 1)[:held])
    ends = np.cumsum(np.append(counts, T * k - counts.sum()))
    np.testing.assert_array_equal(np.searchsorted(ends, place, "right"),
                                  local)
    # the sort is stable: pairs of one expert keep the tokens' order
    order = np.argsort(local.reshape(-1), kind="stable")
    np.testing.assert_array_equal(order[place.reshape(-1)],
                                  np.arange(T * k))
