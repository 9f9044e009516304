"""Cross-replica prefix KV reuse (engine/peering.py + scheduler
admission hook): the fetch is an OPTIMIZATION, never a dependency.

Every failure mode — non-HTTP peer URL, connect error, open circuit
breaker, expired deadline, injected fault — must degrade to local
prefix recompute with the SAME tokens, never to a failed request. A
successful fetch must return exactly what the peer's engine.prefill()
would, seed the local prefix cache, and ship int8 blobs at about half
the bytes (docs/kv-hierarchy.md Tier 2).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine import InferenceEngine, Scheduler
from ome_tpu.engine.peering import PrefixPeerClient
from ome_tpu.engine.pd import (deserialize_kv, make_pd_prefill_handler,
                               serialize_kv)
from ome_tpu.engine.scheduler import Request
from ome_tpu.engine.server import EngineServer
from ome_tpu.models import config as cfgs
from ome_tpu.models import llama

MB64 = 64 << 20


@pytest.fixture(scope="module")
def world():
    cfg = cfgs.tiny_test().replace(max_seq_len=128, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(world, **kw):
    cfg, params = world
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_buckets", [16, 32, 64])
    return InferenceEngine(params, cfg, **kw)


@pytest.fixture(scope="module")
def donor(world):
    """A peer replica whose /pd/prefill serves prefix KV blobs (the
    donor wiring serve.py gives every single-host engine) — one per
    module, the donor side is stateless across tests."""
    eng = _engine(world)
    srv = EngineServer(Scheduler(eng), model_name="m",
                       pd_prefill=make_pd_prefill_handler(eng))
    srv.start()
    yield srv, eng
    srv.stop()


def _run_one(sched, **req_kw):
    req_kw.setdefault("max_new_tokens", 6)
    req_kw.setdefault("temperature", 0.0)
    req = sched.submit(Request(**req_kw))
    for _ in range(500):
        if req.done.is_set():
            break
        sched.step()
    assert req.done.is_set()
    return req


PROMPT = list(range(2, 42))  # 40 tokens -> one cached 32-block


@pytest.fixture(scope="module")
def want_tokens(world):
    """Reference greedy stream for PROMPT on a peerless engine —
    shared by every tokens-identical assertion."""
    return _run_one(Scheduler(_engine(world)),
                    prompt_ids=PROMPT).output_ids


class TestClientFallbacks:
    def test_non_http_scheme_refused_outright(self):
        c = PrefixPeerClient()
        assert c.fetch("file:///etc/passwd", [1, 2, 3]) is None
        assert c.fetch("ftp://peer:21", [1, 2, 3]) is None
        assert c.fallbacks == 2 and c.fetches == 0
        assert not c._peers  # no breaker state for garbage URLs

    def test_connect_failure_charges_breaker_then_opens(self):
        url = "http://127.0.0.1:9"  # nothing listens
        c = PrefixPeerClient(timeout=1.0, cb_threshold=2,
                             cb_cooldown=30.0)
        assert c.fetch(url, [1, 2]) is None
        assert c.fetch(url, [1, 2]) is None
        peer = c._backend(url)
        assert peer.fails >= 2 and not peer.selectable(time.monotonic())
        # breaker open: the next fetch falls back WITHOUT a connect
        t0 = time.monotonic()
        assert c.fetch(url, [1, 2]) is None
        assert time.monotonic() - t0 < 0.5
        assert c.fallbacks == 3 and c.fetches == 0

    def test_expired_deadline_skips_the_attempt(self, donor):
        srv, _ = donor
        c = PrefixPeerClient()
        url = f"http://127.0.0.1:{srv.port}"
        got = c.fetch(url, PROMPT,
                      deadline=time.monotonic() - 1.0)
        assert got is None and c.fallbacks == 1
        # the refusal did not poison the breaker: a live-deadline
        # fetch right after succeeds
        assert c.fetch(url, PROMPT,
                       deadline=time.monotonic() + 30) is not None

    def test_fault_point_degrades_to_fallback(self, donor):
        """The deterministic `prefix_peer_fetch` fault (chaos uses it)
        produces a fallback, not an exception; the next fetch works
        and matches the donor engine's own prefill exactly."""
        from ome_tpu import faults
        srv, donor_eng = donor
        url = f"http://127.0.0.1:{srv.port}"
        c = PrefixPeerClient(cb_threshold=3)
        try:
            faults.install(f"prefix_peer_fetch|{url}.raise@1")
            assert c.fetch(url, PROMPT) is None
            assert c.fallbacks == 1
            got = c.fetch(url, PROMPT)
            assert got is not None and c.fetches == 1
            tok, (k, v), tl, bucket = got
            want_tok, (wk, wv), wtl, wb = donor_eng.prefill(PROMPT)
            assert (tok, tl, bucket) == (want_tok, wtl, wb)
            # (the wire's rows lie heads apart, the donor's merged)
            np.testing.assert_array_equal(
                np.asarray(wk).reshape(np.shape(k)), np.asarray(k))
            np.testing.assert_array_equal(
                np.asarray(wv).reshape(np.shape(v)), np.asarray(v))
        finally:
            faults.reset()


def test_int8_wire_blob_halves_bytes_within_tolerance():
    """quantize=True ships int8 + per-(row, head) scales: ~1/4 the
    fp32 plane bytes, values within one quantization step — what an
    int8-pool donor sends a fetching peer."""
    rng = np.random.default_rng(5)
    k = rng.standard_normal((2, 1, 32, 4, 16)).astype(np.float32)
    v = rng.standard_normal((2, 1, 32, 4, 16)).astype(np.float32)
    full = serialize_kv(7, k, v, true_len=30, bucket=32)
    quant = serialize_kv(7, k, v, true_len=30, bucket=32,
                         quantize=True)
    assert len(quant) < 0.35 * len(full)
    tok, k2, v2, tl, b = deserialize_kv(quant)
    assert (tok, tl, b) == (7, 30, 32)
    assert k2.dtype == k.dtype
    step = np.abs(k).max(axis=-1, keepdims=True) / 127.0
    assert (np.abs(k2 - k) <= step + 1e-7).all()
    step_v = np.abs(v).max(axis=-1, keepdims=True) / 127.0
    assert (np.abs(v2 - v) <= step_v + 1e-7).all()


class TestSchedulerPeerPrefill:
    def test_peer_fetch_seeds_local_cache_tokens_identical(
            self, world, donor, want_tokens):
        """E2E over real HTTP: a request carrying X-OME-Prefix-Peer
        (Request.prefix_peer) fetches the prefix from the donor, emits
        the SAME greedy tokens as a peerless run, seeds the LOCAL
        prefix cache, and the next same-prefix request hits on device
        without touching the peer."""
        srv, _ = donor
        url = f"http://127.0.0.1:{srv.port}"
        want = want_tokens

        local = _engine(world, prefix_cache_bytes=MB64)
        sched = Scheduler(local)
        got = _run_one(sched, prompt_ids=PROMPT, prefix_peer=url)
        assert got.output_ids == want
        assert sched._peer_client.fetches == 1
        assert local.prefix_cache.bytes > 0  # seeded by the fetch
        # same prefix again, NO peer: served from the local cache
        got2 = _run_one(sched, prompt_ids=PROMPT)
        assert got2.output_ids == want
        assert local.prefix_cache.hits >= 1
        assert sched._peer_client.fetches == 1  # no second fetch

    def test_dead_peer_recomputes_locally(self, world, want_tokens):
        """A dead/bogus peer never fails the request: local recompute
        with identical tokens, fallback counted."""
        sched = Scheduler(_engine(world, prefix_cache_bytes=MB64))
        got = _run_one(sched, prompt_ids=PROMPT,
                       prefix_peer="http://127.0.0.1:9")
        assert got.output_ids == want_tokens
        assert got.finish_reason == "length"
        assert sched._peer_client.fallbacks >= 1
        assert sched._peer_client.fetches == 0

    def test_constrained_requests_skip_the_peer_path(self, world):
        """Grammar-masked KV is mask-conditioned: the peer path must
        not be consulted at all (same for adapters and PD decode)."""
        from ome_tpu.engine.schema import SchemaAutomaton
        from ome_tpu.engine.structured import TokenMasker
        from ome_tpu.engine.tokenizer import ByteTokenizer
        tok = ByteTokenizer()
        sched = Scheduler(_engine(world, prefix_cache_bytes=MB64))

        def boom(req, peer):  # pragma: no cover - failure path
            raise AssertionError("peer path used for masked request")

        sched._peer_prefill = boom
        schema = {"type": "object",
                  "properties": {"n": {"type": "integer"}},
                  "required": ["n"], "additionalProperties": False}
        masker = TokenMasker(tok, automaton=SchemaAutomaton(schema))
        req = _run_one(sched, prompt_ids=tok.encode("emit json"),
                       max_new_tokens=20, temperature=0.9,
                       prefix_peer="http://127.0.0.1:9",
                       masker=masker, stop_ids=[tok.eos_id])
        assert req.finish_reason in ("stop", "length")
