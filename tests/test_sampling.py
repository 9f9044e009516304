"""sampling.filtered_logits: two tiers, one distribution.

The function sorts only when a row of the call filters, and then makes
its mask from one sort and a comparison against the last kept rank.
The reference below is the function as it stood before (argsort, a
[B, V] gather, the keep-mask scattered back by rank): every filtering
row must keep exactly the tokens it kept, ties included; every other
row must come back as logits / temperature.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine import sampling
from ome_tpu.engine.sampling import NEG_INF, filtered_logits, sample

V = 4096
B = 4


def reference_filtered_logits(logits, temperature, top_k, top_p):
    """The plain reference: filtered_logits before the tiers."""
    logits = logits.astype(jnp.float32)
    _, v = logits.shape
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / safe_t
    order = jnp.argsort(scaled, axis=-1)[:, ::-1]
    sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
    ranks = jnp.arange(v)[None, :]
    keep_k = jnp.where(top_k[:, None] > 0, ranks < top_k[:, None], True)
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(probs_sorted, axis=-1)
    keep_p = (cumulative - probs_sorted) < top_p[:, None]
    keep_sorted = keep_k & keep_p
    keep = jax.vmap(
        lambda o, m: jnp.zeros((v,), bool).at[o].set(m))(order, keep_sorted)
    return jnp.where(keep, scaled, NEG_INF)


def tied_logits(seed, rows=B, std=3.0):
    """Seeded logits rounded to bf16, as the model's are: a third as
    many distinct values as tokens, so most of a row is tied."""
    x = np.random.default_rng(seed).normal(scale=std, size=(rows, V))
    return jnp.asarray(x, jnp.bfloat16)


def params(rows, temperature, top_k, top_p):
    return (jnp.full(rows, temperature, jnp.float32),
            jnp.full(rows, top_k, jnp.int32),
            jnp.full(rows, top_p, jnp.float32))


new_jit = jax.jit(filtered_logits)
ref_jit = jax.jit(reference_filtered_logits)


@pytest.mark.parametrize("temperature", [0.0, 0.8, 1.5])
@pytest.mark.parametrize("top_p", [1e-6, 0.5, 0.95, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 5, V])
def test_matches_reference(top_k, top_p, temperature):
    logits = tied_logits(seed=1000 * top_k + int(100 * top_p))
    assert len(np.unique(np.asarray(logits[0], np.float32))) < V // 2
    t, k, p = params(B, temperature, top_k, top_p)
    got = np.asarray(new_jit(logits, t, k, p))
    scaled = np.asarray(logits.astype(jnp.float32)
                        / (temperature if temperature > 0 else 1.0))
    if temperature > 0 and (top_k > 0 or top_p < 1):
        # the reference at top_p = 1.0 compares the cumsum with 1.0,
        # which it reaches (and wavers around, by an ulp) before the
        # tail: a mask of rounding that is not even a prefix. Nucleus
        # disabled is what the reference gives for a top_p no cumsum
        # reaches; with top_k 1 or 5 the two are the same mask.
        ref_p = p if top_p < 1 else jnp.full(B, 2.0, jnp.float32)
        want = np.asarray(ref_jit(logits, t, k, ref_p))
        if top_k in (1, 5):
            np.testing.assert_array_equal(
                want, np.asarray(ref_jit(logits, t, k, p)))
        # the same tokens kept, and the kept ones untouched
        np.testing.assert_array_equal(got == NEG_INF, want == NEG_INF)
        np.testing.assert_array_equal(got, want)
        assert (got != NEG_INF).sum(-1).min() >= 1
    else:
        assert not (got == NEG_INF).any()
        np.testing.assert_array_equal(got, scaled)


@pytest.mark.parametrize("std", [1.0, 3.0])
@pytest.mark.parametrize("top_p", [0.999, 0.9999999])
def test_mask_is_a_prefix_where_the_cumsum_wavers(top_p, std):
    """Near top_p = 1 the float32 cumsum stops growing before the tail
    ends and wavers there by an ulp, so the reference's mask is not
    always a prefix of ranks (on the chip at V = 151 936, top_p 0.999:
    5-28 tokens of 13 rows). The kept set is the ranks before the first
    dropped one: a prefix, and never more than the reference kept."""
    logits = tied_logits(seed=int(top_p * 1e7) % 1000, std=std)
    t, k, p = params(B, 1.0, 0, top_p)
    got = np.asarray(new_jit(logits, t, k, p))
    want = np.asarray(ref_jit(logits, t, k, p))
    kept, ref_kept = got != NEG_INF, want != NEG_INF
    assert not (kept & ~ref_kept).any()
    assert (ref_kept & ~kept).sum() <= 64
    scaled = np.asarray(logits, np.float32)
    for b in range(B):
        assert kept[b].any()
        if not kept[b].all():
            assert scaled[b][kept[b]].min() >= scaled[b][~kept[b]].max()


@pytest.mark.parametrize("top_k,top_p", [(5, 1.0), (0, 0.5), (20, 0.95),
                                         (V, 0.95)])
def test_filtering_row_alone_and_among_plain_rows(top_k, top_p):
    """A mixed batch runs the filter tier with n_keep = V for the plain
    rows: the filtering row's mask does not depend on its neighbours,
    and the neighbours lose nothing."""
    logits = tied_logits(seed=7 + top_k, rows=16)
    row = 5
    t = jnp.full(16, 0.8, jnp.float32).at[3].set(0.0)  # one greedy
    k = jnp.zeros(16, jnp.int32).at[row].set(top_k)
    p = jnp.ones(16, jnp.float32).at[row].set(top_p)
    among = np.asarray(new_jit(logits, t, k, p))
    alone = np.asarray(new_jit(logits[row:row + 1], t[row:row + 1],
                               k[row:row + 1], p[row:row + 1]))
    want = np.asarray(ref_jit(logits[row:row + 1], t[row:row + 1],
                              k[row:row + 1], p[row:row + 1]))
    np.testing.assert_array_equal(alone, want)
    np.testing.assert_array_equal(among[row], alone[0])
    assert (among[row] == NEG_INF).any()
    plain = np.delete(np.arange(16), row)
    scaled = np.asarray(logits.astype(jnp.float32)
                        / jnp.where(t > 0, t, 1.0)[:, None])
    np.testing.assert_array_equal(among[plain], scaled[plain])


@pytest.mark.parametrize("temperature", [0.6, 0.8, 1.5])
def test_same_key_same_token_for_plain_rows(temperature):
    """A plain row draws categorical(key, logits / T), as it always
    did, alone or beside a filtering row."""
    logits = tied_logits(seed=11, rows=8)
    key = jax.random.PRNGKey(42)
    t, k, p = params(8, temperature, 0, 1.0)
    want = np.asarray(jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature, axis=-1))
    got = np.asarray(jax.jit(sample)(logits, key, t, k, p))
    np.testing.assert_array_equal(got, want)
    mixed = np.asarray(jax.jit(sample)(logits, key, t, k.at[0].set(1), p))
    np.testing.assert_array_equal(mixed[1:], want[1:])
    assert mixed[0] == int(jnp.argmax(logits[0]))


def test_greedy_rows_never_force_the_filter_tier():
    """temperature <= 0 with top_k / top_p left over from a finished
    request (freed slots are greedy) filters nothing."""
    logits = tied_logits(seed=3)
    t, k, p = params(B, 0.0, 20, 0.5)
    got = np.asarray(new_jit(logits, t, k, p))
    np.testing.assert_array_equal(got, np.asarray(logits, np.float32))


def _regions(text, start):
    """The brace-delimited regions of the MLIR op that begins at
    `start`, up to the end of that op."""
    out, i = [], text.index("{", start)
    while True:
        depth, j = 0, i
        while True:
            depth += {"{": 1, "}": -1}.get(text[j], 0)
            j += 1
            if depth == 0:
                break
        out.append(text[i:j])
        nxt = re.match(r"\s*,\s*\{", text[j:])
        if not nxt:
            return out
        i = j + nxt.end() - 1


def test_lowered_sample_has_a_sortless_branch():
    logits = tied_logits(seed=5)
    t, k, p = params(B, 0.8, 0, 1.0)
    text = jax.jit(sample).lower(
        logits, jax.random.PRNGKey(0), t, k, p).as_text()
    ops = [m.start() for m in re.finditer(r"stablehlo\.(case|if)\b", text)]
    assert len(ops) == 1, "one conditional, on whether any row filters"
    branches = _regions(text, ops[0])
    assert len(branches) == 2
    heavy = re.compile(r"stablehlo\.(sort|gather|scatter|dynamic_gather)"
                       r"|call @")
    plain = [b for b in branches if not heavy.search(b)]
    sorting = [b for b in branches if "stablehlo.sort" in b]
    assert len(plain) == 1 and len(sorting) == 1
    # outside the conditional nothing sorts either
    outside = text
    for b in branches:
        outside = outside.replace(b, "")
    assert "stablehlo.sort" not in outside
    # and no [B, V] gather or scatter is left in the filter tier: the
    # reads at the cutoff are [B, 1]
    for m in re.finditer(r"stablehlo\.(gather|scatter)\"?\(.*", text):
        assert f"{B}x{V}x" not in m.group(0).split("->")[-1], m.group(0)


def test_host_predicate_matches_device_predicate():
    """The scheduler counts the tier with `row_filters` on its host
    (numpy) copy of the vectors; the device masks exactly those rows."""
    t = np.array([0.0, 0.8, 0.8, 0.8, 0.0], np.float32)
    k = np.array([20, 0, 5, 0, 0], np.int32)
    p = np.array([0.5, 1.0, 1.0, 0.9, 1.0], np.float32)
    host = sampling.row_filters(t, k, p)
    assert isinstance(host, np.ndarray)
    assert host.tolist() == [False, False, True, True, False]
    logits = tied_logits(seed=9, rows=5)
    got = np.asarray(new_jit(logits, jnp.asarray(t), jnp.asarray(k),
                             jnp.asarray(p)))
    assert ((got == NEG_INF).any(-1) == host).all()


class TestSchedulerCountsTheTier:
    """ome_engine_sample_tier_steps_total: the host evaluates the
    device's predicate where it rebuilds the device copy of the three
    vectors, and every decode dispatch counts under the tier it ran."""

    @pytest.fixture(scope="class")
    def engine(self):
        from ome_tpu.engine import InferenceEngine
        from ome_tpu.models import config as cfgs
        from ome_tpu.models import llama
        cfg = cfgs.tiny_test().replace(max_seq_len=128)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        return InferenceEngine(params, cfg, max_slots=4,
                               prefill_buckets=[16])

    def test_one_filtering_request_flips_the_tier_and_back(self, engine):
        from ome_tpu.engine import Request, Scheduler
        sched = Scheduler(engine)

        def tiers():
            return {t: int(c.value)
                    for t, c in sched._c_sample_tier.items()}

        plain = [sched.submit(Request(prompt_ids=[1, 5 + i],
                                      max_new_tokens=40, temperature=0.8))
                 for i in range(2)]
        plain.append(sched.submit(Request(prompt_ids=[1, 9],
                                          max_new_tokens=40)))  # greedy
        for _ in range(4):
            sched.step()
        before = tiers()
        assert before["plain"] >= 3 and before["filtered"] == 0

        nucleus = sched.submit(Request(prompt_ids=[1, 7], max_new_tokens=4,
                                       temperature=0.8, top_p=0.9))
        while not nucleus.done.is_set():
            sched.step()
        during = tiers()
        # its first token comes from the prefill; every decode step it
        # sat in ran the filter tier, and no other step did
        assert during["filtered"] >= len(nucleus.output_ids) - 1 >= 1
        assert during["plain"] - before["plain"] <= 1

        for _ in range(3):
            sched.step()
        after = tiers()
        assert not all(r.done.is_set() for r in plain)
        assert after["filtered"] == during["filtered"]
        assert after["plain"] == during["plain"] + 3
        assert (after["plain"] + after["filtered"]
                == sched.stats["decode_steps_total"])
        assert ('ome_engine_sample_tier_steps_total{tier="filtered"} '
                f'{after["filtered"]}') in sched.registry.render()
