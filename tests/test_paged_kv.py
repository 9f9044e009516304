"""Paged (block) KV cache: ops/paged.py + engine integration.

The round-4 verdict's #2 structural item: the dense decode cache
allocates worst-case [L, B, Smax, K, D] HBM per slot; the paged pool
allocates by tokens in flight. These tests pin:

  * numerics: the XLA paged path is exactly the dense computation on
    gathered blocks of the layer it is pointed at; the Pallas kernel
    (interpret mode, handed the whole pool and a layer index) agrees
    layer by layer within the platform's reduced-precision matmul
    noise; `forward_paged` (the pool in the layer scan's carry) equals
    a plain loop over the layers, each on its own pool;
  * the engine serves TOKEN-IDENTICAL outputs dense vs paged across
    mixed lengths, slot reuse, and block-boundary growth;
  * 2x the slot count fits the SAME cache HBM budget with mixed-length
    sequences (the capacity win);
  * pool exhaustion fails fast with a sizing hint;
  * structured outputs ride the paged masked-decode program.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine.core import InferenceEngine
from ome_tpu.engine.scheduler import Request, Scheduler
from ome_tpu.engine.tokenizer import ByteTokenizer
from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test
from ome_tpu.ops.attention import attention
from ome_tpu.ops.paged import (TRASH_BLOCK, paged_attention_xla,
                               paged_flash_decode)

CFG = tiny_test().replace(dtype=jnp.float32, max_seq_len=128)


LAYERS = 3


def _pool(rng, B, H, K, D, bs, M, N, L=LAYERS):
    """A whole pool [L, N, bs, K, D], every layer its own rows, and a
    table of distinct blocks (the same chain in every layer)."""
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((L, N, bs, K, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((L, N, bs, K, D)), jnp.float32)
    # block TRASH_BLOCK is no chain's: the kernel ends a walk there
    ids = (rng.permutation(N - 1)[:B * M] + 1).reshape(B, M)
    return q, kp, vp, jnp.asarray(ids, jnp.int32)


def _quantize_pool(pool):
    """Per-(row, head) symmetric int8 as the engine's pool holds it:
    int8 [L, N, bs, K, D] + f32 scales [L, N, K, bs]."""
    from ome_tpu.ops.flash import quantize_kv_block
    L, N = pool.shape[:2]
    qv, sc = quantize_kv_block(pool.reshape((L * N,) + pool.shape[2:]))
    return (qv.reshape(pool.shape),
            sc.reshape((L, N) + sc.shape[1:]))


class TestPagedAttentionNumerics:
    @pytest.mark.parametrize("layer", range(LAYERS))
    def test_xla_matches_dense_gather(self, layer):
        rng = np.random.default_rng(0)
        B, H, K, D, bs, M, N = 4, 16, 8, 128, 128, 4, 32
        q, kp, vp, table = _pool(rng, B, H, K, D, bs, M, N)
        kv_len = jnp.asarray([5, 128, 200, 512], jnp.int32)
        out = paged_attention_xla(q, kp, vp, table, kv_len, layer)
        kg = jnp.take(kp[layer], table, axis=0).reshape(B, M * bs, K, D)
        vg = jnp.take(vp[layer], table, axis=0).reshape(B, M * bs, K, D)
        ref = attention(q, kg, vg, positions=(kv_len - 1)[:, None],
                        kv_len=kv_len, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    @pytest.mark.parametrize("layer", range(LAYERS))
    def test_pallas_kernel_matches_xla(self, layer):
        """The kernel reads layer `layer` of the whole pool through
        its scalar-prefetched index; the layer is traced, as under
        the layer scan."""
        rng = np.random.default_rng(1)
        B, H, K, D, bs, M, N = 4, 16, 8, 128, 128, 4, 32
        q, kp, vp, table = _pool(rng, B, H, K, D, bs, M, N)
        kv_len = jnp.asarray([1, 100, 256, 512], jnp.int32)
        out = jax.jit(lambda l: paged_flash_decode(
            q, kp, vp, table, kv_len, l, interpret=True))(
                jnp.int32(layer))
        ref = paged_attention_xla(q, kp, vp, table, kv_len, layer)
        # platform note: this CPU build's default f32 matmul is
        # reduced-precision, so block partitioning differences show up
        # at ~1e-2 — the same kernels on TPU agree with XLA at bf16
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2)
        # and it is that layer's rows, not a neighbour's
        other = paged_attention_xla(q, kp, vp, table, kv_len,
                                    (layer + 1) % LAYERS)
        assert np.abs(np.asarray(out) - np.asarray(other)).max() > 0.1

    # lengths 1, bs - 1, bs, bs + 1, mid-chain and a full table in one
    # batch; `freed` is the slot whose row is all TRASH_BLOCK while its
    # device length counts on far past the row (core.free_slot)
    RAGGED = (1, 127, 128, 129, 300, 512)

    @pytest.mark.parametrize("freed", [None, 0, 3, 5])
    @pytest.mark.parametrize("layer", range(LAYERS))
    @pytest.mark.parametrize("pool_dtype", ["bf16", "int8"])
    def test_kernel_walks_the_chain(self, pool_dtype, layer, freed):
        """One grid step a slot, a loop over the blocks its chain
        holds: every live row agrees with the XLA gather, whatever
        the lengths; a freed slot reads nothing, returns zeros, and
        moves no live row by a bit."""
        rng = np.random.default_rng(11)
        H, K, D, bs, M, N = 16, 8, 128, 128, 4, 32
        B = len(self.RAGGED)
        q, kp, vp, table = _pool(rng, B, H, K, D, bs, M, N)
        kv_len = np.asarray(self.RAGGED, np.int32)
        if pool_dtype == "int8":
            (kp, ks), (vp, vs) = _quantize_pool(kp), _quantize_pool(vp)
        else:
            q = q.astype(jnp.bfloat16)
            kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
            ks = vs = None

        def kernel(q, table, kv_len):
            return np.asarray(jax.jit(lambda l: paged_flash_decode(
                q, kp, vp, table, jnp.asarray(kv_len), l, k_scale=ks,
                v_scale=vs, interpret=True))(jnp.int32(layer)),
                np.float32)

        live = np.ones(B, bool)
        if freed is not None:
            live[freed] = False
            table = table.at[freed].set(TRASH_BLOCK)
            kv_len[freed] = 7 * M * bs + 5
        out = kernel(q, table, kv_len)
        ref = np.asarray(paged_attention_xla(
            q, kp, vp, table, jnp.asarray(kv_len), layer, k_scale=ks,
            v_scale=vs), np.float32)
        # bf16 probabilities into the second dot: the tolerance of
        # the kernel-against-XLA tests beside this one
        np.testing.assert_allclose(out[live], ref[live], atol=2e-2)
        if freed is not None:
            assert np.all(out[freed] == 0.0)
            without = kernel(q[live], table[live], kv_len[live])
            np.testing.assert_array_equal(out[live], without)

    def test_kernel_uncovered_shapes_return_none(self):
        rng = np.random.default_rng(2)
        q, kp, vp, table = _pool(rng, 2, 4, 2, 64, 16, 2, 8)
        assert paged_flash_decode(
            q, kp, vp, table, jnp.asarray([3, 9], jnp.int32), 0,
            interpret=True) is None


def _plain_paged_forward(params, cfg, tokens, cache):
    """`forward_paged` the plain way: a Python loop over the layers,
    each on a pool of its own (numpy writes, one row at a time), dense
    attention over the slot's gathered chain. Independent of the scan,
    of the scatter and of ops/paged.py."""
    B, S = tokens.shape
    bs = cache.k.shape[2]
    index, table = np.asarray(cache.index), np.asarray(cache.table)
    positions = jnp.asarray(index[:, None] + np.arange(S)[None, :],
                            jnp.int32)
    kpool, vpool = np.array(cache.k), np.array(cache.v)
    x = llama._embed(params, cfg, tokens)
    freqs = llama._rope_frequencies(cfg)
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = llama.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = llama._qkv(h, lp, cfg, freqs, positions, False, None)
        for b in range(B):
            for s in range(S):
                pos = index[b] + s
                kpool[l, table[b, pos // bs], pos % bs] = k[b, s]
                vpool[l, table[b, pos // bs], pos % bs] = v[b, s]
        kg = jnp.asarray(kpool[l][table]).reshape(
            B, -1, *kpool.shape[3:])
        vg = jnp.asarray(vpool[l][table]).reshape(
            B, -1, *vpool.shape[3:])
        attn = attention(q, kg, vg, positions=positions,
                         kv_len=jnp.asarray(index + S), backend="xla")
        x = x + llama._proj(attn, lp["wo"], cfg.dtype, flatten=2)
        h = llama.rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + llama.dense_mlp(h, lp, cfg)
    return llama._final_logits(params, cfg, x), kpool, vpool


@pytest.mark.parametrize("S", [1, 3])
def test_forward_paged_equals_plain_layer_loop(S):
    """The pool carried through the layer scan and written in place
    at (layer, block, offset) gives the logits and the pool of a plain
    per-layer loop, for decode (S = 1) and verify (S = 3) shapes,
    with rows that cross a block boundary."""
    cfg = CFG
    B, bs, M, N = 3, 16, 4, 16
    rng = np.random.default_rng(7)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    cache = llama.PagedKVCache.create(cfg, B, N, bs, M)
    shape = cache.k.shape
    table = 1 + rng.permutation(N - 1)[:B * M].reshape(B, M)
    cache = llama.PagedKVCache(
        k=jnp.asarray(rng.standard_normal(shape), jnp.float32),
        v=jnp.asarray(rng.standard_normal(shape), jnp.float32),
        index=jnp.asarray([0, 15, 37], jnp.int32),
        table=jnp.asarray(table, jnp.int32))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                         jnp.int32)
    ref, kref, vref = _plain_paged_forward(params, cfg, tokens, cache)
    logits, nc = jax.jit(
        lambda p, t, c: llama.forward_paged(p, cfg, t, c))(
            params, tokens, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(nc.k), kref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nc.v), vref, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nc.index),
                                  np.asarray(cache.index) + S)


def _run(engine, prompts, max_new=24, temperature=0.0, maskers=None):
    tok = ByteTokenizer()
    sched = Scheduler(engine)
    reqs = []
    for i, p in enumerate(prompts):
        kw = {}
        if maskers:
            kw["masker"] = maskers[i]
        reqs.append(sched.submit(Request(
            prompt_ids=tok.encode(p), max_new_tokens=max_new,
            temperature=temperature, stop_ids=[tok.eos_id], **kw)))
    while not all(r.done.is_set() for r in reqs):
        sched.step()
    return [r.output_ids for r in reqs]


PROMPTS = ["hello world", "a", "the quick brown fox jumps over",
           "xyzzy plugh abc", "short", "another prompt here",
           "yet more text", "z"]


def test_paged_tokens_identical_to_dense():
    """Greedy tokens byte-exact vs the dense path, incl. slot reuse
    (8 requests through 4 slots) and growth across block boundaries
    (24 new tokens cross the 16-token block repeatedly)."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    dense = InferenceEngine(params, CFG, max_slots=4,
                            prefill_buckets=[16, 32])
    paged = InferenceEngine(params, CFG, max_slots=4,
                            prefill_buckets=[16, 32], kv_block=16)
    out_d = _run(dense, PROMPTS)
    out_p = _run(paged, PROMPTS)
    assert out_d == out_p
    # every block returned to the pool after the last request
    assert paged.kv_pool_stats["kv_blocks_free"] == \
        paged.kv_blocks - 1


def test_table_fill_gauge_reads_the_owned_lists():
    """`ome_engine_kv_table_fill_ratio`: blocks owned over slots x
    table width, read at scrape from the allocator's lists; a freed
    slot's row is TRASH_BLOCK in every entry, which is what ends the
    kernel's walk there."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    paged = InferenceEngine(params, CFG, max_slots=4,
                            prefill_buckets=[16, 32], kv_block=16)
    sched = Scheduler(paged)

    def fill():
        sched.update_gauges()
        return sched.registry.gauge(
            "ome_engine_kv_table_fill_ratio").value

    assert fill() == 0.0
    paged._owned[1] = [paged._free_blocks.pop() for _ in range(3)]
    paged._table[1, :3] = paged._owned[1]
    assert fill() == 3 / (4 * paged.max_blocks)
    paged.free_slot(1)
    assert fill() == 0.0
    assert (paged._table[1] == TRASH_BLOCK).all()


def test_double_slots_same_hbm_budget():
    """The capacity win: dense 4 slots x 128 rows = 512 cache rows;
    the paged pool with the SAME 512-row budget serves 8 slots of
    mixed-length sequences concurrently."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    rows_budget = 4 * CFG.max_seq_len  # dense HBM budget, in rows
    paged = InferenceEngine(params, CFG, max_slots=8,
                            prefill_buckets=[16, 32], kv_block=16,
                            kv_blocks=rows_budget // 16 + 1)
    k_bytes = paged.new_state().k.nbytes
    dense_bytes = InferenceEngine(
        params, CFG, max_slots=4,
        prefill_buckets=[16, 32]).new_state().k.nbytes
    assert k_bytes <= dense_bytes + paged.kv_block * 16 * 1024
    out = _run(paged, PROMPTS, max_new=20)  # 8 concurrent slots
    assert all(len(o) == 20 for o in out)


def test_pool_pressure_preempts_and_recovers():
    """An undersized pool (tokens in flight < sum of worst cases) is a
    NORMAL condition: requests are requeued / preempted with their
    progress carried as prompt, and all finish — no node outage."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    # each request worst-case: ~16 prompt + 25 new + 1 = 42 rows = 3
    # blocks; pool of 4 usable blocks fits ONE such stream at a time
    paged = InferenceEngine(params, CFG, max_slots=4,
                            prefill_buckets=[16], kv_block=16,
                            kv_blocks=5)
    tok = ByteTokenizer()
    sched = Scheduler(paged)
    reqs = [sched.submit(Request(prompt_ids=tok.encode(p)[:16],
                                 max_new_tokens=25, temperature=0.0,
                                 stop_ids=[tok.eos_id]))
            for p in PROMPTS[:4]]
    for _ in range(2000):
        if all(r.done.is_set() for r in reqs):
            break
        sched.step()
    assert all(r.done.is_set() for r in reqs)
    # a resumed stream may legitimately emit EOS before the budget
    # (resume prompts recompute the HONEST continuation — the fold of
    # generated tokens into the prompt is deduplicated across repeated
    # preemptions); every other request must use its full budget
    for r in reqs:
        if r.finish_reason == "stop":
            assert r.output_ids[-1] == tok.eos_id
            assert len(r.output_ids) <= 25
        else:
            assert r.finish_reason == "length"
            assert len(r.output_ids) == 25, len(r.output_ids)
    # pool fully reclaimed
    assert paged.kv_pool_stats["kv_blocks_free"] == paged.kv_blocks - 1


def test_impossible_request_rejected_upfront():
    """A request whose worst case exceeds the whole pool would
    livelock (always its own cheapest victim): reject at admission."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    paged = InferenceEngine(params, CFG, max_slots=2,
                            prefill_buckets=[16], kv_block=16,
                            kv_blocks=3)  # 2 usable blocks = 32 rows
    tok = ByteTokenizer()
    sched = Scheduler(paged)
    req = sched.submit(Request(prompt_ids=tok.encode("hi"),
                               max_new_tokens=100, temperature=0.0,
                               stop_ids=[tok.eos_id]))
    for _ in range(50):
        if req.done.is_set():
            break
        sched.step()
    assert req.done.is_set()
    assert req.finish_reason == "error"


def test_paged_structured_outputs():
    """The masked decode program has a paged variant: a schema-
    constrained request over the paged engine emits conforming JSON."""
    from ome_tpu.engine.schema import SchemaAutomaton
    from ome_tpu.engine.structured import TokenMasker
    cfg = tiny_test().replace(dtype=jnp.float32, max_seq_len=160)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    paged = InferenceEngine(params, cfg, max_slots=2,
                            prefill_buckets=[16], kv_block=16)
    tok = ByteTokenizer()
    schema = {"type": "object",
              "properties": {"n": {"type": "integer"}},
              "required": ["n"], "additionalProperties": False}
    out = _run(paged, ["emit json"], max_new=40, temperature=0.9,
               maskers=[TokenMasker(tok,
                                    automaton=SchemaAutomaton(schema))])
    obj = json.loads(tok.decode(out[0]))
    assert isinstance(obj["n"], int)


def test_paged_rejects_unsupported_models():
    cfg = tiny_test().replace(dtype=jnp.float32, sliding_window=8)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="paged KV"):
        InferenceEngine(params, cfg, max_slots=2, kv_block=16)


def test_grow_blocks_exhaustion_preempts_explicitly(monkeypatch):
    """When the pool is empty and no victim is evictable, _grow_blocks
    must preempt the growing slot EXPLICITLY (requeue via
    take_preempted) — never let its next write land in the trash
    block, which would silently desync host/device lengths."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    paged = InferenceEngine(params, CFG, max_slots=2,
                            prefill_buckets=[16], kv_block=16,
                            kv_blocks=3)  # blocks 1,2 usable; 0=trash
    # hand-build the corner: slot 0 owns the whole pool and its next
    # write needs a third block
    paged._owned[0] = [1, 2]
    paged._free_blocks.clear()
    paged._table[0, 0] = 1
    paged._table[0, 1] = 2
    paged._host_len[0] = 32
    # force "nothing evictable" (the defensive branch is unreachable
    # through _preempt_victim today — pin the contract directly)
    monkeypatch.setattr(paged, "_preempt_victim", lambda: False)
    paged._grow_blocks()
    assert paged.take_preempted() == [0]
    assert paged._owned[0] == []        # blocks returned to the pool
    assert len(paged._free_blocks) == 2
    assert paged._host_len[0] == 0      # no phantom write advanced it
