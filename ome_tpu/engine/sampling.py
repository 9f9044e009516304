"""Token sampling — per-slot parameters, fully vectorized.

Each decode step samples one token per batch slot. Because slots in the
continuous-batching engine belong to different requests, temperature /
top-k / top-p are [B] vectors rather than scalars, and everything is
computed with static shapes so the whole step stays inside one compiled
XLA program. The work follows what the rows ask for, decided on the
device from those vectors: when no row filters (every row greedy, or
top_k <= 0 with top_p >= 1) nothing is sorted; when one does, one sort
of (value, token id) and one comparison against the last kept rank make
the mask — no [B, V] gather, no scatter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1.0e30


def row_filters(temperature, top_k, top_p):
    """[B] bool: which rows ask for a filter. Greedy rows never do;
    top_k <= 0 with top_p >= 1 is the documented "disabled". Plain
    operators, so the scheduler evaluates the same expression on its
    host (numpy) copy of the vectors to count the tier a step ran."""
    return (temperature > 0) & ((top_k > 0) | (top_p < 1.0))


def _mask_below_cutoff(scaled: jax.Array, filters: jax.Array,
                       top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """The filter tier: `scaled` [B, V] with every token outside a
    filtering row's top-k / nucleus prefix set to NEG_INF."""
    B, V = scaled.shape
    # one descending ordering, values and token ids together: a stable
    # ascending sort read backwards, so among equal values the HIGHER
    # token id ranks first (bf16 logits tie by the thousand; the order
    # of the ties decides who sits inside a prefix)
    ids = lax.broadcasted_iota(jnp.int32, (B, V), 1)
    asc_logits, asc_ids = lax.sort((scaled, ids), dimension=1,
                                   is_stable=True, num_keys=1)
    sorted_logits = asc_logits[:, ::-1]

    # both filters are rank-based prefix masks — never
    # probability-threshold comparisons, which are brittle to softmax
    # rounding across recomputations
    ranks = jnp.arange(V)[None, :]
    # top-k: keep the first k ranks (top_k<=0 disables)
    keep_k = jnp.where(top_k[:, None] > 0, ranks < top_k[:, None], True)

    # top-p (nucleus): smallest prefix of the sorted distribution whose
    # mass reaches top_p — a rank is kept if the mass before it is < top_p
    # (top_p>=1 disables: the float32 cumsum reaches 1.0 before the tail
    # does, and wavers by an ulp there, so comparing against 1.0 would
    # mask by rounding)
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(probs_sorted, axis=-1)
    keep_p = jnp.where(top_p[:, None] < 1.0,
                       (cumulative - probs_sorted) < top_p[:, None], True)

    # the kept set is a prefix of ranks (rank 0 always survives both),
    # so its length says everything: the first dropped rank, V for a
    # row of a mixed batch that does not filter
    dropped = ~(keep_k & keep_p) & filters[:, None]
    n_keep = jnp.min(jnp.where(dropped, ranks, V), axis=-1)  # [B] >= 1

    # the value and token id at the last kept rank, a [B, 1] read;
    # token j is kept iff it ranks at or before that one: a larger
    # value, or the same value and (the tie order above) an id >= its
    cut = (V - n_keep)[:, None]  # position in the ascending order
    v_cut = jnp.take_along_axis(asc_logits, cut, axis=-1)
    id_cut = jnp.take_along_axis(asc_ids, cut, axis=-1)
    keep = (scaled > v_cut) | ((scaled == v_cut) & (ids >= id_cut))
    return jnp.where(keep, scaled, NEG_INF)


def filtered_logits(logits: jax.Array, temperature: jax.Array,
                    top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Temperature-scaled, top-k/top-p-masked logits.

    The distribution `sample` (and the speculative verify acceptance
    rule) actually draws from: logits [B, V] float, params [B].
    Filtered-out entries are NEG_INF; greedy rows (temperature<=0)
    pass through with temperature 1 and unmasked — callers pick argmax
    for those. Returns [B, V] float32.

    A row filters iff temperature > 0 and (top_k > 0 or top_p < 1)
    (`row_filters`); top_k <= 0 with top_p >= 1 filters nothing, and
    such a row comes back as logits / temperature. The sort runs only when some row of
    the call filters (a `lax.cond` on that one scalar). Then ranks
    descend by value and, among equal values, by token id; the kept
    set is the prefix of ranks inside both top-k and the nucleus.
    """
    logits = logits.astype(jnp.float32)

    # scale by temperature (guard the greedy rows against div-by-zero)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / safe_t

    filters = row_filters(temperature, top_k, top_p)
    return lax.cond(
        jnp.any(filters),
        lambda s: _mask_below_cutoff(s, filters, top_k, top_p),
        lambda s: s, scaled)


def sample(logits: jax.Array, key: jax.Array, temperature: jax.Array,
           top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Sample next tokens.

    logits: [B, V] float; temperature/top_k/top_p: [B]
    (temperature<=0 means greedy; top_k<=0 disables top-k;
    top_p>=1 disables nucleus filtering).
    Returns [B] int32.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = filtered_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def spec_verify(logits: jax.Array, drafts: jax.Array,
                draft_len: jax.Array, key: jax.Array,
                temperature: jax.Array, top_k: jax.Array,
                top_p: jax.Array) -> tuple:
    """Batched draft verification (Leviathan et al. 2023).

    One verify forward scored `S = k+1` positions per slot: position 0
    follows the committed last token, position i (1<=i<=k) follows
    draft token i-1. This decides, per slot, the longest accepted
    draft prefix and the one extra token the step emits beyond it.

    logits: [B, S, V] — verify-forward logits; drafts: [B, k] int32;
    draft_len: [B] int32 in [0, k] (0 = slot did not draft: the step
    degenerates to a plain decode for that slot); key: PRNG key;
    temperature/top_k/top_p: [B].

    Acceptance: greedy slots accept draft d_i iff it equals the argmax
    at position i; temperature>0 slots accept d_i with probability
    p_i(d_i) under the *filtered* target distribution (the same one
    `sample` draws from — a point-mass n-gram draft makes the
    Leviathan rule reduce to this), and on rejection resample from
    p_i with d_i zeroed and renormalized, which preserves the target
    distribution exactly.

    Returns (out_tokens [B, S] int32, accepted [B] int32): slot b
    emits out_tokens[b, :accepted[b]+1]; out_tokens[b, accepted[b]]
    is the slot's new "last sampled token" (the next step's input).
    """
    logits = logits.astype(jnp.float32)
    B, S, V = logits.shape
    k = S - 1
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    filt = filtered_logits(
        logits.reshape(B * S, V),
        jnp.repeat(temperature, S), jnp.repeat(top_k, S),
        jnp.repeat(top_p, S)).reshape(B, S, V)
    probs = jax.nn.softmax(filt, axis=-1)

    pos = jnp.arange(k)[None, :]
    in_draft = pos < draft_len[:, None]
    kacc, kres, kbon = jax.random.split(key, 3)

    # per-position accept decisions, then the longest accepted prefix
    draft_p = jnp.take_along_axis(
        probs[:, :k], drafts[..., None], axis=-1)[..., 0]  # [B, k]
    u = jax.random.uniform(kacc, (B, k))
    accept = jnp.where(temperature[:, None] > 0,
                       u < draft_p, drafts == greedy[:, :k])
    run = jnp.cumprod((accept & in_draft).astype(jnp.int32), axis=1)
    accepted = jnp.sum(run, axis=1).astype(jnp.int32)  # [B] in [0, k]

    # the token emitted at the stop position: on rejection at i, the
    # residual sample (p_i with d_i removed, renormalized); on full
    # acceptance (stop == draft_len), a plain sample from p_stop
    is_draft_tok = jnp.arange(V)[None, None, :] == drafts[..., None]
    resid_tok = jax.random.categorical(
        kres, jnp.where(is_draft_tok, NEG_INF, filt[:, :k]),
        axis=-1).astype(jnp.int32)  # [B, k]
    bonus_tok = jax.random.categorical(
        kbon, filt, axis=-1).astype(jnp.int32)  # [B, S]
    stop_tok = jnp.concatenate([
        jnp.where(pos == draft_len[:, None],
                  bonus_tok[:, :k], resid_tok),
        bonus_tok[:, k:]], axis=1)  # [B, S]
    # greedy slots emit argmax(raw logits) at the stop position either
    # way: on rejection the masked argmax equals the unmasked one
    # (the rejected draft wasn't the argmax), matching `sample`
    stop_tok = jnp.where(temperature[:, None] > 0, stop_tok, greedy)

    next_tok = jnp.take_along_axis(stop_tok, accepted[:, None], axis=1)
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)  # [B, S]
    j = jnp.arange(S)[None, :]
    out = jnp.where(j < accepted[:, None], drafts_pad,
                    jnp.where(j == accepted[:, None], next_tok, 0))
    return out.astype(jnp.int32), accepted
