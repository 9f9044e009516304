"""Self-drafting speculative decoding (docs/speculative-decoding.md).

The contracts under test:

  * EQUIVALENCE: greedy streams are byte-identical with speculation
    off and on (any k), including mid-stream stop-token finishes,
    deadline expiry, paged-KV pool pressure with preemption, and an
    injected engine-step crash with a verify step in flight — the
    verify forward accepts exactly what plain decode would emit;
  * ACCEPTANCE RULE: sampling.spec_verify implements the Leviathan
    accept/resample rule — greedy slots accept the longest
    argmax-matching prefix; temperature>0 slots accept draft tokens
    with the filtered target probability (certain drafts always
    accepted, filtered-out drafts always rejected);
  * ROLLBACK: a paged engine pre-allocates blocks for the k+1
    speculative rows and commit_spec() returns the surplus of a
    rejected draft to the pool;
  * DEGRADATION: masked (structured-output) batches never draft, and
    speculation resumes when the masked request finishes;
  * TELEMETRY: acceptance-rate / accepted-tokens histograms observe,
    and the prefix-cache counters mirror into the registry by delta;
  * the check_decode_sync.py lint covers the draft-building step-path
    functions.
"""

import pathlib
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest

from ome_tpu import faults
from ome_tpu.engine import sampling, spec
from ome_tpu.engine.core import InferenceEngine
from ome_tpu.engine.scheduler import Request, Scheduler
from ome_tpu.models import config as cfgs
from ome_tpu.models import llama

from test_pipeline import (CountingEngine, PassMasker, _drive,
                           reference_greedy)

REPO = pathlib.Path(__file__).resolve().parents[1]

# repetitive prompts: the tail n-gram recurs, so the drafter proposes
# from the first decode step and the verify path is exercised hard
PLANS = [([1, 7, 42, 99, 5, 1, 7, 42, 99], 12),
         ([1, 100, 200, 100, 200], 6),
         ([3, 4, 3, 4, 3], 9),
         ([2, 3, 4, 5, 6, 7], 6),
         ([9, 8, 7, 9, 8], 5)]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def world():
    cfg = cfgs.tiny_test().replace(max_seq_len=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(params, cfg, max_slots=4,
                             prefill_buckets=[16, 32, 64])
    return cfg, params, engine


@pytest.fixture(scope="module")
def paged_world():
    """Undersized paged pool so decode growth preempts victims — the
    speculative block pre-allocation must compose with preemption."""
    cfg = cfgs.tiny_test().replace(max_seq_len=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(params, cfg, max_slots=4,
                             prefill_buckets=[32], kv_block=16,
                             kv_blocks=5)
    return cfg, params, engine


def _run(engine, plans, spec_tokens, *, depth=1, iters=2000, **req_kw):
    sched = Scheduler(engine, pipeline_depth=depth,
                      spec_tokens=spec_tokens)
    reqs = []
    for i, (p, n) in enumerate(plans):
        reqs.append(sched.submit(
            Request(prompt_ids=p, max_new_tokens=n, **req_kw)))
        if i % 2:
            sched.step()  # stagger admissions mid-decode
    _drive(sched, reqs, iters=iters)
    return sched, reqs


# -- the n-gram drafter ------------------------------------------------


class TestDrafter:
    def test_tail_match_replays_continuation(self):
        # tail [1, 2, 3] recurs at position 0; what followed is [4, 1, 2]
        d = spec.propose([1, 2, 3, 4, 1, 2, 3], 3)
        assert d.tolist() == [4, 1, 2]

    def test_most_recent_match_wins(self):
        # tail [7] occurs at 0 and 2; the later one's continuation wins
        assert spec.propose([7, 1, 7, 2, 7], 2).tolist() == [2, 7]

    def test_no_match_proposes_nothing(self):
        assert spec.propose([1, 2, 3, 4, 5], 4).size == 0

    def test_degenerate_inputs(self):
        assert spec.propose([5, 5, 5], 0).size == 0
        assert spec.propose([5], 3).size == 0
        assert spec.propose([], 3).size == 0

    def test_proposal_never_exceeds_k(self):
        d = spec.propose([1, 2] * 20, 4)
        assert 0 < d.size <= 4


# -- the acceptance rule (sampling.spec_verify) ------------------------


def _one_hot_logits(tokens, V, hi=50.0):
    """[1, S, V] logits putting ~all mass on tokens[i] at position i."""
    S = len(tokens)
    out = np.zeros((1, S, V), np.float32)
    out[0, np.arange(S), tokens] = hi
    return out


class TestAcceptanceRule:
    V = 16
    KEY = jax.random.PRNGKey(42)

    def _verify(self, logits, drafts, dlen, temp):
        B = logits.shape[0]
        out, acc = sampling.spec_verify(
            logits, np.asarray(drafts, np.int32),
            np.asarray(dlen, np.int32), self.KEY,
            np.full((B,), temp, np.float32),
            np.zeros((B,), np.int32), np.ones((B,), np.float32))
        return np.asarray(out), np.asarray(acc)

    def test_greedy_accepts_longest_argmax_prefix(self):
        logits = _one_hot_logits([3, 5, 7, 9], self.V)
        out, acc = self._verify(logits, [[3, 5, 8]], [3], 0.0)
        assert acc[0] == 2  # draft[2]=8 != argmax 7
        assert out[0, :3].tolist() == [3, 5, 7]  # prefix + correction

    def test_greedy_full_acceptance_emits_bonus(self):
        logits = _one_hot_logits([3, 5, 7, 9], self.V)
        out, acc = self._verify(logits, [[3, 5, 7]], [3], 0.0)
        assert acc[0] == 3
        assert out[0].tolist() == [3, 5, 7, 9]  # k drafts + bonus

    def test_certain_draft_always_accepted_at_temperature(self):
        # one-hot target: p(draft)=1 at every position, so the
        # stochastic rule must accept everything, for any key
        logits = _one_hot_logits([3, 5, 7, 9], self.V)
        out, acc = self._verify(logits, [[3, 5, 7]], [3], 0.8)
        assert acc[0] == 3
        assert out[0].tolist() == [3, 5, 7, 9]

    def test_filtered_out_draft_always_rejected(self):
        # the draft token has ~zero filtered probability -> u < p(d)
        # never holds; the residual resample can't pick it either
        logits = _one_hot_logits([3, 5, 7, 9], self.V)
        out, acc = self._verify(logits, [[4, 5, 7]], [3], 0.8)
        assert acc[0] == 0
        assert out[0, 0] != 4

    def test_draft_len_zero_is_plain_decode(self):
        logits = _one_hot_logits([3, 5], self.V)
        out, acc = self._verify(logits, [[6]], [0], 0.0)
        assert acc[0] == 0
        assert out[0, 0] == 3  # position-0 argmax, draft ignored


# -- equivalence: speculation must never change greedy bytes -----------


class TestSpecEquivalence:
    def test_greedy_streams_identical_spec_on_and_off(self, world):
        cfg, params, engine = world
        want = [reference_greedy(params, cfg, p, n) for p, n in PLANS]
        outs = {}
        for st in (0, 2, 4):
            sched, reqs = _run(engine, PLANS, st)
            outs[st] = [list(r.output_ids) for r in reqs]
            assert all(r.finish_reason == "length" for r in reqs)
            if st:
                # the path must actually engage to mean anything
                assert sched.stats["spec_steps_total"] > 0
                assert sched.stats["spec_proposed_tokens_total"] > 0
        assert outs[0] == outs[2] == outs[4] == want

    def test_acceptance_happens_on_repetitive_streams(self, world):
        cfg, params, engine = world
        sched, _ = _run(engine, PLANS, 3)
        assert sched.stats["spec_accepted_tokens_total"] > 0

    def test_midstream_stop_token_identical(self, world):
        """A stop token landing inside an accepted prefix must drop
        the rest of the prefix — same bytes as the plain run."""
        cfg, params, engine = world
        prompt, n = PLANS[0]
        ref = reference_greedy(params, cfg, prompt, n)
        stop = ref[n // 2]
        first = ref.index(stop)
        outs = {}
        for st in (0, 3):
            sched, reqs = _run(engine, [(prompt, n)], st,
                               stop_ids=(stop,))
            req = reqs[0]
            assert req.finish_reason == "stop"
            outs[st] = list(req.output_ids)
        assert outs[0] == outs[3] == ref[:first + 1]

    def test_paged_pool_pressure_identical(self, paged_world):
        """Preemption under pool pressure composes with speculative
        block pre-allocation: both runs finish every request with the
        same bytes, and preemption actually happened."""
        cfg, params, engine = paged_world
        # 12-token prompts that repeat, as PLANS do: a prompt without
        # a recurring n-gram never drafts, and this model's greedy
        # streams do not start repeating inside 8 tokens
        plans = [([i + 1, 5, 9, 13] * 3, 8) for i in range(4)]
        outs, stats = {}, {}
        for st in (0, 3):
            sched, reqs = _run(engine, plans, st)
            assert all(len(r.output_ids) == 8 for r in reqs)
            outs[st] = [list(r.output_ids) for r in reqs]
            stats[st] = dict(sched.stats)
        assert stats[3]["preemptions_total"] > 0
        assert stats[3]["spec_steps_total"] > 0
        assert outs[0] == outs[3]

    def test_deadline_expiry_is_a_clean_prefix(self, world):
        """Deadline passing mid-run: both runs finish with 'timeout',
        never emit past the finish, and are prefixes of the same
        greedy stream (finish timing is wall-clock, so byte equality
        across runs is not required — prefix consistency is)."""
        cfg, params, engine = world
        prompt = PLANS[0][0]
        outs = {}
        for st in (0, 3):
            sched = Scheduler(engine, pipeline_depth=1, spec_tokens=st)
            req = sched.submit(Request(
                prompt_ids=prompt, max_new_tokens=10_000,
                deadline=time.monotonic() + 0.25))
            _drive(sched, [req], iters=10_000)
            assert req.finish_reason == "timeout"
            n = len(req.output_ids)
            for _ in range(5):  # pending lag-queue tokens must drop
                sched.step()
            assert len(req.output_ids) == n
            outs[st] = list(req.output_ids)
        short, long_ = sorted(outs.values(), key=len)
        assert short == long_[:len(short)]


# -- paged-KV rollback -------------------------------------------------


class TestPagedRollback:
    def test_rejected_draft_blocks_return_to_pool(self):
        """verify() pre-allocates blocks for the k+1 speculative rows;
        a fully rejected draft advances the slot by ONE row, so
        commit_spec() must hand the surplus blocks back."""
        cfg = cfgs.tiny_test().replace(max_seq_len=128)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        eng = InferenceEngine(params, cfg, max_slots=2,
                              prefill_buckets=[16], kv_block=16,
                              kv_blocks=6)
        state = eng.new_state()
        tok, kv, true_len, bucket = eng.prefill([1, 2, 3, 4, 5])
        state = eng.insert(state, kv, 0, true_len, tok, bucket)
        B = eng.max_slots
        t = np.zeros((B,), np.float32)
        k0 = np.zeros((B,), np.int32)
        p = np.ones((B,), np.float32)
        # one plain step to learn the slot's next greedy token
        state, toks = eng.decode(state, t, k0, p)
        nxt = int(np.asarray(toks)[0])
        free_before = eng.kv_pool_stats["kv_blocks_free"]
        # a draft that CANNOT be accepted: position 0 mismatches the
        # argmax, so the greedy prefix is empty. k=13 makes the k+1
        # speculative rows cross the 16-token block boundary.
        k = 13
        drafts = np.zeros((B, k), np.int32)
        drafts[0, :] = (nxt + 1) % cfg.vocab_size
        dlen = np.zeros((B,), np.int32)
        dlen[0] = k
        state, out, acc = eng.verify(state, drafts, dlen, t, k0, p)
        assert int(np.asarray(acc)[0]) == 0
        grown = eng.kv_pool_stats["kv_blocks_free"]
        assert grown < free_before  # speculative rows got real blocks
        eng.commit_spec(0, int(np.asarray(acc)[0]) + 1)
        assert eng.kv_pool_stats["kv_blocks_free"] == free_before


# -- degradation: masked batches stay non-speculative ------------------


class TestMaskedDegradation:
    def test_masked_batch_never_drafts_then_spec_resumes(self, world):
        cfg, params, engine = world
        sched = Scheduler(engine, pipeline_depth=1, spec_tokens=3)
        masked = sched.submit(Request(
            prompt_ids=[1, 2, 1, 2, 1], max_new_tokens=2,
            masker=PassMasker()))
        reqs = [sched.submit(Request(prompt_ids=p, max_new_tokens=n))
                for p, n in PLANS]
        while not masked.done.is_set():
            sched.step()
            # the grammar needs token k on host before masking k+1:
            # no verify step may dispatch while a masked slot is live
            assert sched.stats["spec_steps_total"] == 0
        _drive(sched, reqs, iters=400)
        assert sched.stats["spec_steps_total"] > 0  # resumed after


# -- failure composition -----------------------------------------------


class SpecEngine(CountingEngine):
    """CountingEngine plus a verify op: decode and verify both emit
    the constant token 7 and verify accepts every draft, so the
    stream turns repetitive and the drafter engages deterministically
    after the first couple of tokens."""

    def decode(self, state, t, k, p, mask=None):
        self.steps += 1
        return state, np.full(self.max_slots, 7, np.int32)

    def verify(self, state, drafts, dlen, t, k, p):
        self.steps += 1
        S = drafts.shape[1] + 1
        out = np.full((self.max_slots, S), 7, np.int32)
        return state, out, np.asarray(dlen, np.int32)


class TestCrashWithSpec:
    def test_crash_mid_speculation_deterministic(self):
        """Fake engine, fully deterministic timeline: by engine-step
        hit 6 the scheduler is speculating (hits 4-5 are verify
        steps). The crash errors the active request with only clean
        tokens emitted, and the queued survivor completes after
        recovery — speculation composes with _recover."""
        faults.install("engine_step.raise@6")
        eng = SpecEngine(max_slots=1)
        sched = Scheduler(eng, max_restarts=2, restart_backoff=0.01,
                          pipeline_depth=1, spec_tokens=3)
        a = sched.submit(Request(prompt_ids=[1], max_new_tokens=50))
        b = sched.submit(Request(prompt_ids=[2], max_new_tokens=4))
        sched.start()
        try:
            assert a.done.wait(10)
            assert b.done.wait(10)
        finally:
            sched.stop()
        assert a.finish_reason == "engine_fault"
        assert sched.stats["restarts_total"] == 1
        assert sched.stats["spec_steps_total"] >= 2  # pre-crash
        # every emitted token is verified content — never a stale or
        # half-committed speculative batch
        assert a.output_ids[0] == 100 and set(a.output_ids[1:]) == {7}
        assert b.finish_reason == "length"
        assert b.output_ids == [100, 7, 7, 7]

    def test_crash_recovers_and_streams_stay_clean(self, world):
        """Real engine: crash with speculation enabled — failed
        requests error out with a clean verified prefix (the crashed
        step's tokens are never emitted), the queued survivor
        completes with exact greedy bytes, speculating post-recovery."""
        cfg, params, engine = world
        plans = PLANS[:4] + [(PLANS[4][0], 24)]
        want = [reference_greedy(params, cfg, p, n) for p, n in plans]
        faults.install("engine_step.raise@4")
        sched = Scheduler(engine, max_restarts=2, restart_backoff=0.01,
                          pipeline_depth=1, spec_tokens=3)
        reqs = [sched.submit(Request(prompt_ids=p, max_new_tokens=n))
                for p, n in plans]  # 5 requests, 4 slots: one queued
        sched.start()
        try:
            for r in reqs:
                assert r.done.wait(30), r.id
        finally:
            sched.stop()
        assert sched.stats["restarts_total"] == 1
        assert sched.stats["spec_steps_total"] > 0
        reasons = {r.finish_reason for r in reqs}
        assert "engine_fault" in reasons and "length" in reasons
        for r, w in zip(reqs, want):
            if r.finish_reason == "length":
                assert list(r.output_ids) == w
            else:  # errored: only verified (pre-crash) tokens emitted
                assert list(r.output_ids) == w[:len(r.output_ids)]


# -- telemetry ---------------------------------------------------------


class TestSpecTelemetry:
    def test_spec_histograms_observe_and_render(self, world):
        cfg, params, engine = world
        sched, _ = _run(engine, PLANS[:2], 3)
        assert sched.registry.get("ome_engine_spec_accept_rate") >= 1
        assert sched.registry.get(
            "ome_engine_spec_accepted_tokens_per_step") >= 1
        body = sched.registry.render()
        assert "ome_engine_spec_accept_rate_bucket" in body
        assert "ome_engine_spec_accepted_tokens_per_step_bucket" \
            in body

    def test_prefix_cache_counters_mirror_by_delta(self):
        eng = CountingEngine(max_slots=2)
        eng.prefix_cache = types.SimpleNamespace(
            hits=0, misses=0, evictions=0, bytes=0)
        sched = Scheduler(eng)
        sched.update_gauges()
        eng.prefix_cache.hits = 3
        eng.prefix_cache.misses = 2
        eng.prefix_cache.evictions = 1
        eng.prefix_cache.bytes = 4096
        sched.update_gauges()
        sched.update_gauges()  # idempotent: deltas, not re-adds
        R = sched.registry
        assert R.get("ome_engine_prefix_cache_hits_total") == 3
        assert R.get("ome_engine_prefix_cache_misses_total") == 2
        assert R.get("ome_engine_prefix_cache_evictions_total") == 1
        assert R.get("ome_engine_prefix_cache_bytes") == 4096

    def test_engine_prefix_cache_counts_evictions(self):
        from ome_tpu.engine.core import PrefixCache
        assert PrefixCache().evictions == 0

    def test_cli_flag_and_health_field(self):
        from ome_tpu.engine.serve import build_parser
        assert build_parser().parse_args(
            ["--model-dir", "x"]).spec_tokens == 0
        args = build_parser().parse_args(
            ["--model-dir", "x", "--spec-tokens", "4"])
        assert args.spec_tokens == 4
        sched = Scheduler(CountingEngine(max_slots=1), spec_tokens=4)
        assert sched.spec_tokens == 4  # what /health reports

    def test_sharded_engine_gates_verify(self):
        from ome_tpu.engine.sharded import ShardedInferenceEngine
        assert "verify" in ShardedInferenceEngine.__dict__


# -- the decode-loop sync lint covers the draft path -------------------


class TestSpecLint:
    SCRIPT = REPO / "scripts" / "check_decode_sync.py"

    def test_sync_fetch_in_draft_path_flagged(self, tmp_path):
        bad = tmp_path / "bad_scheduler.py"
        bad.write_text(
            "import numpy as np\n"
            "class S:\n"
            "    def _build_drafts(self, k):\n"
            "        return np.asarray(self.toks)\n"       # sync
            "    def _spec_headroom(self, k):\n"
            "        self.state.lengths.block_until_ready()\n"  # sync
            "        return True\n"
            "    def _drain_spec(self, step):\n"
            "        return np.asarray(step.out)\n")       # sanctioned
        proc = subprocess.run(
            [sys.executable, str(self.SCRIPT), str(bad)],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        assert proc.stdout.count("VIOLATION") == 2
        assert "_build_drafts" in proc.stdout
        assert "_spec_headroom" in proc.stdout
