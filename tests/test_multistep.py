"""Multi-token device decode (docs/multi-step-decode.md).

`--steps-per-dispatch K` runs K decode iterations inside ONE jitted
device program (InferenceEngine.decode_multi: lax.fori_loop over
{forward, sample, KV append} with on-device stop/budget freezing) so
the host syncs once per K tokens. Contracts under test:

  * EQUIVALENCE: greedy streams are byte-identical across
    K in {1, 4, 8} x pipeline depth {0, 1} x {dense, paged}, all
    matching the single-sequence reference — chunking may only move
    WHEN tokens surface, never WHICH tokens;
  * CHUNK SEMANTICS: a stop id sampled mid-chunk freezes the slot on
    device (advanced counts only real tokens), the host discards the
    frozen tail; budget overshoot inside a chunk is discarded at the
    drain; deadline expiry is detected at chunk boundaries with no
    post-finish emission;
  * COMPOSITION: paged pool pressure preempting between chunks and
    journal kill-resume with a chunk in flight both preserve byte
    identity;
  * DEGRADATION: engines without decode_multi clamp K back to 1,
    counted in ome_engine_step_degradations_total{cause} — never
    silently wrong. Masked (structured-output) batches ride chunks
    through forced-token grammar runs and spec-verify steps ARE
    multi-token-shaped dispatches (docs/step-plan.md), so neither
    degrades K anymore; only a masker whose automaton cannot be
    copied falls back to one synchronous masked step at a time;
  * SURFACES: the serve CLI flag, /health, the
    ome_engine_steps_per_dispatch gauge, the device_loop step phase,
    engine.decode_chunk spans, and the check_decode_sync lint's
    sanctioned `_drain_multi` fetch.
"""

import json
import pathlib
import subprocess
import sys
import time
import urllib.request

import jax
import numpy as np
import pytest

from ome_tpu import faults
from ome_tpu.engine import ByteTokenizer, EngineServer
from ome_tpu.engine.core import InferenceEngine
from ome_tpu.engine.journal import RequestJournal
from ome_tpu.engine.scheduler import Request, Scheduler
from ome_tpu.models import config as cfgs
from ome_tpu.models import llama
from ome_tpu.telemetry import export

from test_pipeline import (CountingEngine, PassMasker, _drive,
                           reference_greedy)

REPO = pathlib.Path(__file__).resolve().parents[1]


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
    """Roomy paged pool: block discipline under multi-step chunks
    WITHOUT preemption in the mix (that composition gets its own
    undersized-pool test below)."""
    cfg = cfgs.tiny_test().replace(max_seq_len=128)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(params, cfg, max_slots=4,
                             prefill_buckets=[16, 32, 64],
                             kv_block=16, kv_blocks=40)
    return cfg, params, engine


# -- engine layer: decode_multi against single-step decode ------------


def _seeded(engine, prompt):
    """A fresh state with `prompt` prefilled into slot 0 (scalars, as
    Scheduler._prefill_req passes them), and its first token."""
    tok, kv, tl, bucket = engine.prefill(prompt, 0.0, 0, 1.0)
    return engine.insert(engine.new_state(), kv, 0, tl, tok, bucket), tok


class TestEngineDecodeMulti:
    @pytest.mark.parametrize("paged", [False, True],
                             ids=["dense", "paged"])
    def test_chunk_matches_single_steps_and_freezes(
            self, paged, world, paged_world):
        """One 8-chunk == 8 single steps byte-for-byte; a budget-0
        slot never advances; a stop id sampled mid-chunk freezes the
        slot with `advanced` counting only the real tokens. Runs on
        the module engines (insert() frees the slot before reuse), so
        the compiles here are the same ones the scheduler matrix
        below exercises."""
        cfg, params, engine = paged_world if paged else world
        B = engine.max_slots
        prompt = [1, 7, 3, 9]
        temp = np.zeros(B, np.float32)
        tk = np.zeros(B, np.int32)
        tp = np.ones(B, np.float32)

        def seeded():
            return _seeded(engine, prompt)

        # reference: 8 single-step dispatches; only slot 0 occupied
        st, tok0 = seeded()
        ref = [tok0]
        for _ in range(8):
            st, toks = engine.decode(st, temp, tk, tp)
            ref.append(int(np.asarray(toks)[0]))

        # one fused chunk of 8; the empty slots sit at budget 0
        st2, tok2 = seeded()
        budget = np.zeros(B, np.int32)
        budget[0] = 8
        stops = np.full((B, 4), -1, np.int32)
        st2, out, adv = engine.decode_multi(st2, temp, tk, tp,
                                            steps=8, budget=budget,
                                            stop_ids=stops)
        out, adv = np.asarray(out), np.asarray(adv)
        assert adv.tolist() == [8] + [0] * (B - 1)
        assert [tok2] + [int(t) for t in out[0, :8]] == ref
        if paged:
            # the drain-side contract: commit the advance, pool stays
            # conserved (no leaked or double-owned blocks)
            engine.commit_spec(0, 8)
            ok, _ = engine.kv_conservation()
            assert ok

        # mid-chunk stop: the stop id is a generated token that is new
        # to the stream at its place j (this model's greedy stream
        # repeats itself, so "the 3rd token" may already be the 1st)
        # -> the loop samples it, then freezes the slot for the rest
        # of the chunk
        j = next(i for i in range(2, 8) if ref[i] not in ref[1:i])
        st3, _ = seeded()
        stops3 = np.full((B, 4), -1, np.int32)
        stops3[0, 0] = ref[j]
        st3, out3, adv3 = engine.decode_multi(st3, temp, tk, tp,
                                              steps=8, budget=budget,
                                              stop_ids=stops3)
        out3, adv3 = np.asarray(out3), np.asarray(adv3)
        assert int(adv3[0]) == j
        assert [int(x) for x in out3[0, :j]] == ref[1:j + 1]
        # frozen: the held token fills the tail, the length stands
        assert set(out3[0, j:].tolist()) == {ref[j]}
        assert int(np.asarray(st3.lengths)[0]) == len(prompt) + j

    @pytest.mark.parametrize("family",
                             ["decode", "decode_multi", "verify"])
    @pytest.mark.parametrize("paged", [False, True],
                             ids=["dense", "paged"])
    def test_mask_kinds_agree_and_are_named(
            self, family, paged, world, paged_world, monkeypatch):
        """The program table (InferenceEngine.programs): a family's
        three mask kinds are one body, so no mask, an all-True dense
        mask and every index at row 0 of the mask table give the same
        greedy tokens; each is dispatched under the name the ledger
        knows, jitted as `jit__<name>`, with the family as the root
        scope of what it traces."""
        cfg, params, engine = paged_world if paged else world
        B, V, steps = engine.max_slots, cfg.vocab_size, 4
        greedy = (np.zeros(B, np.float32), np.zeros(B, np.int32),
                  np.ones(B, np.float32))
        captured = []

        def capture(name, static_desc, fn, args, static, **_):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            captured.append((name, static_desc, fn, shapes, static))

        def run(mask_shape=None, idx_shape=None):
            st, _ = _seeded(engine, [1, 7, 3, 9])
            kw = {}
            if mask_shape is not None:
                kw["mask"] = np.ones(mask_shape, bool)
            if idx_shape is not None:
                kw["mask_idx"] = np.zeros(idx_shape, np.int32)
            if family == "decode":
                outs = engine.decode(st, *greedy, **kw)
            elif family == "decode_multi":
                budget = np.zeros(B, np.int32)
                budget[0] = steps
                outs = engine.decode_multi(
                    st, *greedy, steps=steps, budget=budget,
                    stop_ids=np.full((B, 4), -1, np.int32), **kw)
            else:
                drafts = np.zeros((B, steps - 1), np.int32)
                drafts[0] = [420, 77, 5]  # what is accepted must agree
                dlen = np.zeros(B, np.int32)
                dlen[0] = steps - 1
                outs = engine.verify(st, drafts, dlen, *greedy, **kw)
            return [np.asarray(o)[0].tolist() for o in outs[1:]]

        dense, idx = {"decode": ((B, V), (B,)),
                      "decode_multi": ((B, steps, V), (B, steps)),
                      "verify": ((B, V), (B, steps))}[family]
        monkeypatch.setattr(engine, "_ledger_capture", capture)
        plain = run()
        assert run(mask_shape=dense) == plain
        assert run(idx_shape=idx) == plain
        tail = "_paged" if paged else ""
        static = {"decode": "", "decode_multi": f"n={steps}",
                  "verify": f"k={steps - 1}"}[family]
        scope = "verify" if family == "verify" else "decode"
        dispatched = [c for c in captured if c[0].startswith(family)]
        assert [(c[0], c[1]) for c in dispatched] == [
            (family + kind + tail, static)
            for kind in ("", "_masked", "_masked_idx")]
        for name, _, fn, shapes, kw in dispatched:
            assert fn is engine.programs[name]
            text = fn.lower(*shapes, **kw).as_text(debug_info=True)
            paths = [ln for ln in text.splitlines()
                     if ln.startswith("#loc")
                     and f'"jit(_{name})/' in ln]
            assert paths and all(
                f'"jit(_{name})/{scope}/' in ln for ln in paths), name

    def test_prefill_refuses_per_slot_sampling_arrays(self, world):
        """One prompt, one value a parameter: a [1] array used to
        reach the program as [1, 1] and die in the sampling trace."""
        with pytest.raises(ValueError, match="scalars"):
            world[2].prefill([1, 7, 3, 9], np.zeros(1, np.float32),
                             0, 1.0)

    def test_program_table_holds_the_eighteen(self, world):
        """family x mask kind x cache kind, nothing else: the next
        program to go is the deletion of one of these names."""
        assert sorted(world[2].programs) == sorted(
            family + kind + tail
            for family in ("decode", "decode_multi", "verify")
            for kind in ("", "_masked", "_masked_idx")
            for tail in ("", "_paged"))


# -- scheduler layer: the K x depth x backend equivalence matrix ------


PLANS = [([1, 7, 42, 99, 5], 12), ([1, 100, 200, 300], 4),
         ([1, 250], 9), ([2, 3, 4, 5, 6, 7], 6), ([9, 8, 7], 3)]


def _run_matrix(engine, ks=(1, 4, 8), depths=(0, 1)):
    """Staggered admissions + slot reuse under every (K, depth)."""
    outs = {}
    for k in ks:
        for depth in depths:
            sched = Scheduler(engine, pipeline_depth=depth,
                              steps_per_dispatch=k)
            reqs = []
            for i, (p, n) in enumerate(PLANS):
                reqs.append(sched.submit(
                    Request(prompt_ids=p, max_new_tokens=n)))
                if i % 2:
                    sched.step()  # stagger admissions mid-decode
            _drive(sched, reqs, iters=2000)
            assert all(r.finish_reason == "length" for r in reqs), \
                [(k, depth, r.finish_reason) for r in reqs]
            outs[(k, depth)] = [list(r.output_ids) for r in reqs]
    return outs


class TestSchedulerEquivalence:
    def test_greedy_matrix_dense(self, world):
        cfg, params, engine = world
        want = [reference_greedy(params, cfg, p, n) for p, n in PLANS]
        outs = _run_matrix(engine)
        for key, got in outs.items():
            assert got == want, key

    def test_greedy_matrix_paged(self, paged_world):
        """Chunked decode over the block-table path: the host
        pre-grows K*(inflight+1) rows before each dispatch and commits
        at the drain — streams must not depend on K or depth, and the
        pool must conserve. Anchored to the K=1/depth=0 paged stream
        (block-table attention may legitimately flip a greedy argmax
        tie vs the DENSE reference — same discipline as
        test_pipeline's paged equivalence)."""
        cfg, params, engine = paged_world
        outs = _run_matrix(engine)
        base = outs[(1, 0)]
        for key, got in outs.items():
            assert got == base, key
        ok, _ = engine.kv_conservation()
        assert ok

    @pytest.mark.parametrize("depth", [0, 1])
    def test_midchunk_eos(self, world, depth):
        """A stop id sampled as token 2 of an 8-chunk: the stream ends
        at the stop token (finish_reason 'stop'), the chunk's frozen
        tail is never emitted."""
        cfg, params, engine = world
        prompt = [1, 7, 42, 99, 5]
        ref = reference_greedy(params, cfg, prompt, 8)
        stop = ref[2]
        want = ref[:ref.index(stop) + 1]
        sched = Scheduler(engine, pipeline_depth=depth,
                          steps_per_dispatch=8)
        req = sched.submit(Request(prompt_ids=prompt,
                                   max_new_tokens=100,
                                   stop_ids=(stop,)))
        _drive(sched, [req], iters=100)
        assert req.finish_reason == "stop"
        assert req.output_ids == want
        n = len(req.output_ids)
        for _ in range(5):  # frozen-tail tokens must stay discarded
            sched.step()
        assert len(req.output_ids) == n

    def test_deadline_expiry_at_chunk_boundary(self, world):
        """The device loop cannot observe wall-clock: a deadline
        passing mid-chunk finishes 'timeout' at the next drain, and
        nothing is emitted past the finish."""
        cfg, params, engine = world
        sched = Scheduler(engine, pipeline_depth=1,
                          steps_per_dispatch=4)
        req = sched.submit(Request(
            prompt_ids=[3, 1, 4, 1, 5], max_new_tokens=10_000,
            deadline=time.monotonic() + 0.25))
        _drive(sched, [req], iters=10_000)
        assert req.finish_reason == "timeout"
        n = len(req.output_ids)
        for _ in range(5):
            sched.step()
        assert len(req.output_ids) == n
        # what WAS emitted is a clean greedy prefix
        ref = reference_greedy(params, cfg, [3, 1, 4, 1, 5],
                               min(n, 16))
        assert req.output_ids[:len(ref)] == ref[:n]


class TestPagedPreemptionBetweenChunks:
    def test_preemption_streams_identical_across_k(self):
        """Undersized pool (test_pipeline's paged_world shape): chunk
        growth forces preemption between chunks; victims' in-flight
        chunk tokens are discarded via the generation counter and the
        resume must reproduce the same bytes at every (K, depth)."""
        cfg = cfgs.tiny_test().replace(max_seq_len=128)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        engine = InferenceEngine(params, cfg, max_slots=4,
                                 prefill_buckets=[32], kv_block=16,
                                 kv_blocks=5)
        # repetitive prompts: the n-gram drafter engages in the
        # spec cells, so preemption interleaves with verify plans too
        prompts = [[i + 1, 5, 9, 13] * 3 for i in range(4)]
        outs, preempts, proposed = {}, {}, 0
        for spec in (0, 2):
            for k in (1, 4):
                for depth in (0, 1):
                    sched = Scheduler(engine, pipeline_depth=depth,
                                      steps_per_dispatch=k,
                                      spec_tokens=spec)
                    reqs = [sched.submit(Request(prompt_ids=p,
                                                 max_new_tokens=8))
                            for p in prompts]
                    _drive(sched, reqs, iters=2000)
                    assert all(len(r.output_ids) == 8 for r in reqs)
                    outs[(spec, k, depth)] = [list(r.output_ids)
                                              for r in reqs]
                    preempts[(spec, k, depth)] = \
                        sched.stats["preemptions_total"]
                    proposed += sched.stats[
                        "spec_proposed_tokens_total"]
        assert all(n > 0 for n in preempts.values()), preempts
        assert proposed > 0  # the spec cells genuinely drafted
        base = outs[(0, 1, 0)]
        for key, got in outs.items():
            assert got == base, key
        ok, _ = engine.kv_conservation()
        assert ok


# -- the full composition matrix (docs/step-plan.md) ------------------
# spec x chunks x pipeline x {dense, paged} x {masked, plain}: five
# mechanisms as StepPlan features of ONE plan/execute loop. Greedy
# streams must be byte-identical at every cell, and no cell may trip
# a feature-loss degradation cause.


COMP_PLANS = [([1, 2, 3] * 4, 12), ([5, 6] * 5, 9),
              ([9, 8, 7, 9, 8, 7], 6), ([4, 4, 4, 4], 4)]

COMP_SCHEMA = {"type": "object",
               "properties": {"n": {"type": "integer", "minimum": 0,
                                    "maximum": 99}},
               "required": ["n"], "additionalProperties": False}


def _assert_composed(degr):
    """The composition contract: walkable grammars and spec verify
    never cost a feature. Only spec_realign may tick — a planned
    flush when a free-sampled tail invalidates draft alignment, which
    trades pipeline depth for one window, not a mechanism."""
    for cause in ("masked", "spec_verify", "engine_multi_step",
                  "engine_verify"):
        assert degr[cause] == 0, degr


def _run_comp_matrix(engine, masked, specs=(0, 2), ks=(1, 4),
                     depths=(0, 1), grammar_table=True):
    """Every (spec, K, depth) cell over one engine; returns the
    per-cell streams and the count of fused multi-token dispatches
    (device_loop phase observations). ``grammar_table=False`` runs
    the dense-mask baseline the device-resident row-index path must
    match byte-for-byte (docs/structured-outputs.md)."""
    from ome_tpu.engine.schema import SchemaAutomaton
    from ome_tpu.engine.structured import TokenMasker

    tok = ByteTokenizer()
    outs, chunked = {}, {}
    for spec in specs:
        for k in ks:
            for depth in depths:
                sched = Scheduler(engine, pipeline_depth=depth,
                                  steps_per_dispatch=k,
                                  spec_tokens=spec,
                                  grammar_table=grammar_table)
                reqs = []
                if masked:
                    for text in ("emit n:", "n = ", "give n "):
                        reqs.append(sched.submit(Request(
                            prompt_ids=tok.encode(text),
                            max_new_tokens=14,
                            masker=TokenMasker(
                                tok, automaton=SchemaAutomaton(
                                    COMP_SCHEMA)),
                            stop_ids=[tok.eos_id])))
                else:
                    for p, n in COMP_PLANS:
                        reqs.append(sched.submit(Request(
                            prompt_ids=p, max_new_tokens=n)))
                _drive(sched, reqs, iters=3000)
                _assert_composed(sched.degradations)
                if spec and not masked:
                    # the repetitive prompts guarantee the drafter
                    # engages — a spec cell that never drafts would
                    # vacuously "compose"
                    assert sched.stats[
                        "spec_proposed_tokens_total"] > 0, \
                        (spec, k, depth)
                outs[(spec, k, depth)] = [list(r.output_ids)
                                          for r in reqs]
                chunked[(spec, k, depth)] = \
                    sched._ph["device_loop"].count
    return outs, chunked


class TestCompositionMatrix:
    def test_dense_plain(self, world):
        """All 8 (spec, K, depth) cells match the single-sequence
        greedy reference — composing mechanisms moves WHEN tokens
        surface, never WHICH tokens."""
        cfg, params, engine = world
        want = [reference_greedy(params, cfg, p, n)
                for p, n in COMP_PLANS]
        outs, _ = _run_comp_matrix(engine, masked=False)
        for key, got in outs.items():
            assert got == want, key

    def test_paged_plain(self, paged_world):
        """Same matrix over the block-table path, anchored to the
        paged (0, 1, 0) cell (paged attention may flip a greedy
        argmax tie vs dense); pool conserves after every cell."""
        cfg, params, engine = paged_world
        outs, _ = _run_comp_matrix(engine, masked=False)
        base = outs[(0, 1, 0)]
        for key, got in outs.items():
            assert got == base, key
        ok, _ = engine.kv_conservation()
        assert ok

    def test_dense_masked(self, world):
        """A 100%-masked (json-schema) batch across the matrix:
        byte-identical streams, zero cause=masked degradations, and
        the grammar's forced-token runs genuinely ride fused chunks
        (device_loop dispatches observed at K>1) — masked batches no
        longer forfeit multi-token dispatch or pipelining."""
        cfg, params, engine = world
        outs, chunked = _run_comp_matrix(engine, masked=True)
        base = outs[(0, 1, 0)]
        for key, got in outs.items():
            assert got == base, key
        assert any(n > 0 for key, n in chunked.items()
                   if key[1] > 1), chunked

    def test_paged_masked(self, paged_world):
        cfg, params, engine = paged_world
        outs, chunked = _run_comp_matrix(engine, masked=True)
        base = outs[(0, 1, 0)]
        for key, got in outs.items():
            assert got == base, key
        assert any(n > 0 for key, n in chunked.items()
                   if key[1] > 1), chunked
        ok, _ = engine.kv_conservation()
        assert ok

    def test_dense_masked_idx_byte_identity(self, world):
        """The device-resident mask-table contract: plans referencing
        cached grammar states by row index produce byte-identical
        streams to the dense [B,K,V] mask baseline, across the whole
        (spec, K, depth) matrix."""
        cfg, params, engine = world
        idx, _ = _run_comp_matrix(engine, masked=True)
        dense, _ = _run_comp_matrix(engine, masked=True,
                                    grammar_table=False)
        for key in dense:
            assert idx[key] == dense[key], key

    def test_paged_masked_idx_byte_identity(self, paged_world):
        cfg, params, engine = paged_world
        idx, _ = _run_comp_matrix(engine, masked=True)
        dense, _ = _run_comp_matrix(engine, masked=True,
                                    grammar_table=False)
        for key in dense:
            assert idx[key] == dense[key], key
        ok, _ = engine.kv_conservation()
        assert ok

    def test_masked_spec_cell_drafts_and_accepts(self, world):
        """Spec through the grammar: on masked slots the drafter
        proposes (forced grammar runs + screened n-gram extensions),
        the verify accepts some of it, nothing degrades, and the
        output is grammar-valid — the last masked-vs-unmasked
        feature gap (docs/structured-outputs.md)."""
        from ome_tpu.engine.structured import TokenMasker

        cfg, params, engine = world
        tok = ByteTokenizer()
        for k in (1, 4):
            sched = Scheduler(engine, pipeline_depth=1,
                              steps_per_dispatch=k, spec_tokens=2)
            reqs = [sched.submit(Request(
                prompt_ids=tok.encode(text), max_new_tokens=14,
                masker=TokenMasker(tok), stop_ids=[tok.eos_id]))
                for text in ("emit n:", "n = ", "give n ")]
            _drive(sched, reqs, iters=3000)
            _assert_composed(sched.degradations)
            proposed = sched.stats["spec_proposed_tokens_total"]
            accepted = sched.stats["spec_accepted_tokens_total"]
            assert proposed > 0, k
            assert accepted > 0, k  # accept-rate > 0
            for r in reqs:
                json.loads(tok.decode(r.output_ids))


# -- journal kill-resume with a chunk in flight -----------------------


def _wait(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline
        time.sleep(0.005)


class TestJournalResume:
    def test_kill_with_chunk_in_flight_resumes_byte_identical(
            self, world, tmp_path):
        """Fatal engine fault at dispatch 3 (K=4, depth 1): chunk 2 is
        in flight and its tokens are dropped unread; the resumed run
        regenerates them and the combined stream is byte-identical to
        the uninterrupted greedy reference."""
        cfg, params, engine = world
        prompt = [1, 7, 42, 99, 5]
        want = reference_greedy(params, cfg, prompt, 12)

        d = str(tmp_path)
        faults.install("engine_step.raise@3")
        j = RequestJournal(d, fsync="batch", fsync_interval=0.0)
        sched = Scheduler(engine, max_restarts=0, journal=j,
                          pipeline_depth=1, steps_per_dispatch=4)
        sched.start()
        req = sched.submit(Request(prompt_ids=prompt,
                                   max_new_tokens=12))
        assert req.done.wait(30)
        assert req.finish_reason == "engine_fault"
        _wait(lambda: sched.status == "dead", timeout=30)
        got_before = list(req.output_ids)
        # genuinely interrupted mid-stream, with a chunk discarded
        assert 0 < len(got_before) < 12
        assert got_before == want[:len(got_before)]
        sched.stop()
        j.close()
        faults.reset()

        # "new process": fresh engine + scheduler over the same dir
        engine2 = InferenceEngine(params, cfg, max_slots=4,
                                  prefill_buckets=[16, 32, 64])
        j2 = RequestJournal(d)
        sched2 = Scheduler(engine2, journal=j2, pipeline_depth=1,
                           steps_per_dispatch=4)
        assert sched2.resume_from_journal() == 1
        resumed = sched2.pending.queue[0]
        assert resumed.output_ids == got_before
        sched2.start()
        assert resumed.done.wait(30)
        sched2.stop()
        j2.close()
        assert resumed.finish_reason == "length"
        assert resumed.output_ids == want

    def test_kill_with_composed_plan_in_flight_resumes(
            self, world, tmp_path):
        """The COMPOSED version: spec drafts + K=4 chunks + depth-1
        pipelining all live when the engine dies. Whatever mix of
        verify and chunk plans was in flight is discarded unread via
        the generation counter; journal replay plus the same composed
        configuration must regenerate the identical greedy stream."""
        cfg, params, engine = world
        prompt = [1, 2, 3] * 4  # repetitive: the drafter engages
        want = reference_greedy(params, cfg, prompt, 12)

        d = str(tmp_path)
        faults.install("engine_step.raise@3")
        j = RequestJournal(d, fsync="batch", fsync_interval=0.0)
        sched = Scheduler(engine, max_restarts=0, journal=j,
                          pipeline_depth=1, steps_per_dispatch=4,
                          spec_tokens=2)
        sched.start()
        req = sched.submit(Request(prompt_ids=prompt,
                                   max_new_tokens=12))
        assert req.done.wait(30)
        assert req.finish_reason == "engine_fault"
        _wait(lambda: sched.status == "dead", timeout=30)
        got_before = list(req.output_ids)
        assert 0 < len(got_before) < 12
        assert got_before == want[:len(got_before)]
        sched.stop()
        j.close()
        faults.reset()

        engine2 = InferenceEngine(params, cfg, max_slots=4,
                                  prefill_buckets=[16, 32, 64])
        j2 = RequestJournal(d)
        sched2 = Scheduler(engine2, journal=j2, pipeline_depth=1,
                           steps_per_dispatch=4, spec_tokens=2)
        assert sched2.resume_from_journal() == 1
        resumed = sched2.pending.queue[0]
        assert resumed.output_ids == got_before
        sched2.start()
        assert resumed.done.wait(30)
        sched2.stop()
        j2.close()
        assert resumed.finish_reason == "length"
        assert resumed.output_ids == want


# -- degradation: never silently wrong --------------------------------


class TestDegradation:
    def test_engine_without_decode_multi_resets_to_one(self, caplog):
        with caplog.at_level("WARNING", logger="ome.engine"):
            sched = Scheduler(CountingEngine(max_slots=1),
                              steps_per_dispatch=4)
        assert sched.steps_per_dispatch == 1
        assert any("multi-step" in r.message for r in caplog.records)
        # and the degraded scheduler still serves correctly
        req = sched.submit(Request(prompt_ids=[1], max_new_tokens=3))
        _drive(sched, [req], iters=50)
        assert req.finish_reason == "length"

    def test_replicated_engine_carries_multi_step(self):
        """ReplicatedEngine replicates decode_multi / verify /
        commit_spec as explicit ops (docs/step-plan.md), so the
        capability flag is honest: True over an engine with the
        multi-step program, False over one without (where publishing
        would replay a program the follower cannot run)."""
        from ome_tpu.engine.multihost import ReplicatedEngine
        assert ReplicatedEngine.supports_multi_step is True
        for op in ("decode_multi", "verify", "commit_spec"):
            assert op in ReplicatedEngine.__dict__, \
                f"{op} must publish, not leak through __getattr__"

        class FakePub:
            def send(self, m):
                pass

        class MultiStepEngine:
            supports_multi_step = True

            def decode_multi(self, *a, **kw):
                pass

        wrapped = ReplicatedEngine(MultiStepEngine(), FakePub())
        assert wrapped.supports_multi_step is True
        bare = ReplicatedEngine(CountingEngine(max_slots=1), FakePub())
        assert bare.supports_multi_step is False

    def test_masked_batch_degrades_per_step(self, world, caplog):
        """A masker whose automaton cannot be copied (PassMasker has
        no grammar walk) still runs correctly: one synchronous masked
        step at a time, nothing in flight, streams identical — and
        the fallback is scrape-visible on the degradation counter
        under cause=masked instead of log-only."""
        cfg, params, engine = world
        prompt = [1, 7, 42, 99, 5]
        want = reference_greedy(params, cfg, prompt, 6)
        sched = Scheduler(engine, pipeline_depth=1,
                          steps_per_dispatch=4)
        req = sched.submit(Request(prompt_ids=prompt,
                                   max_new_tokens=6,
                                   masker=PassMasker()))
        with caplog.at_level("WARNING", logger="ome.engine"):
            for _ in range(50):
                if req.done.is_set():
                    break
                sched.step()
                assert len(sched._inflight) == 0
        assert req.output_ids == want
        # scrape-visible, not log-only: the counter carries the cause
        assert sched.degradations["masked"] > 0
        assert not any("degraded" in r.message
                       for r in caplog.records)
        # and the counter renders with its cause label
        assert 'ome_engine_step_degradations_total{cause="masked"}' \
            in sched.registry.render()


# -- surfaces: CLI flag, /health, telemetry, spans, lint --------------


class TestSurfaces:
    def test_cli_flag_default_and_parse(self):
        from ome_tpu.engine.serve import build_parser
        assert build_parser().parse_args(
            ["--model-dir", "x"]).steps_per_dispatch == 1
        args = build_parser().parse_args(
            ["--model-dir", "x", "--steps-per-dispatch", "8"])
        assert args.steps_per_dispatch == 8

    def test_health_reports_steps_per_dispatch(self, world):
        _, _, engine = world
        srv = EngineServer(
            Scheduler(engine, steps_per_dispatch=4), ByteTokenizer(),
            model_name="tiny-test")
        srv.start()
        try:
            url = f"http://127.0.0.1:{srv.port}/health"
            with urllib.request.urlopen(url, timeout=10) as r:
                body = json.loads(r.read())
        finally:
            srv.stop()
        assert body["steps_per_dispatch"] == 4

    def test_gauge_and_device_loop_phase(self, world):
        _, _, engine = world
        sched = Scheduler(engine, pipeline_depth=1,
                          steps_per_dispatch=4)
        req = sched.submit(Request(prompt_ids=[1, 2, 3],
                                   max_new_tokens=6))
        _drive(sched, [req], iters=100)
        assert sched.registry.get(
            "ome_engine_steps_per_dispatch") == 4
        assert "ome_engine_steps_per_dispatch" in \
            sched.registry.render()
        # chunk dispatches attribute their device time to the
        # device_loop phase, not the K=1 dispatch phase
        assert sched._ph["device_loop"].count > 0
        # decode_steps_total counts TOKENS-worth of steps, not chunks
        assert sched.stats["decode_steps_total"] >= \
            len(req.output_ids) - 1

    def test_decode_chunk_spans(self, world, tmp_path):
        _, _, engine = world
        log_path = tmp_path / "engine.jsonl"
        sched = Scheduler(engine, pipeline_depth=1,
                          steps_per_dispatch=4,
                          span_log=str(log_path))
        req = sched.submit(Request(prompt_ids=[1, 2, 3],
                                   max_new_tokens=9))
        _drive(sched, [req], iters=100)
        sched.span_log.close()
        chunks = [s for s in export.load_spans([log_path])
                  if s["name"] == "engine.decode_chunk"]
        assert chunks, "no engine.decode_chunk spans emitted"
        assert all(s["attrs"]["steps_per_dispatch"] == 4
                   for s in chunks)
        # emitted tokens across chunks tile the decode stream
        # (prefill contributes the first output token)
        assert sum(s["attrs"]["tokens"] for s in chunks) == \
            len(req.output_ids) - 1

    def test_drain_multi_fetch_sanctioned_by_lint(self, tmp_path):
        ok = tmp_path / "multi_sched.py"
        ok.write_text(
            "import numpy as np\n"
            "class S:\n"
            "    def _decode(self):\n"
            "        st, out, adv = self.engine.decode_multi(\n"
            "            self.state)\n"
            "        self.q.append((out, adv))\n"
            "        self._drain_multi()\n"
            "    def _drain_multi(self):\n"
            "        out, adv = self.q.pop()\n"
            "        return np.asarray(out), np.asarray(adv)\n")
        proc = subprocess.run(
            [sys.executable,
             str(REPO / "scripts" / "check_decode_sync.py"),
             str(ok)],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr
