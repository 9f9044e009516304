"""PD-disaggregated serving (engine/pd.py): KV wire format, the
remote-prefill engine, and the e2e contract — a prefill+decode node
pair must produce byte-identical completions to a monolithic engine.

Reference role: SGLang's --disaggregation-mode pair with RDMA KV
transfer (/root/reference/config/runtimes/srt/deepseek-rdma-pd-rt.yaml
:101-103), re-owned because this repo's engine is in-repo.
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine import InferenceEngine, Scheduler
from ome_tpu.engine.pd import (PDError, RemotePrefillEngine,
                               deserialize_kv, make_pd_prefill_handler,
                               serialize_kv)
from ome_tpu.engine.server import EngineServer
from ome_tpu.models import config as cfgs
from ome_tpu.models import llama


@pytest.fixture(scope="module")
def world():
    cfg = cfgs.tiny_test().replace(max_seq_len=128, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(world, **kw):
    cfg, params = world
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_buckets", [16, 32])
    return InferenceEngine(params, cfg, **kw)


def test_kv_wire_roundtrip():
    k = np.arange(2 * 1 * 4 * 2 * 3, dtype=np.float32).reshape(
        2, 1, 4, 2, 3)
    v = -k
    blob = serialize_kv(7, k, v, true_len=3, bucket=4)
    tok, k2, v2, tl, b = deserialize_kv(blob)
    assert (tok, tl, b) == (7, 3, 4)
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(v, v2)


def test_kv_wire_rejects_truncation():
    blob = serialize_kv(1, np.zeros((1, 1, 2, 1, 2), np.float32),
                        np.zeros((1, 1, 2, 1, 2), np.float32), 2, 2)
    with pytest.raises(PDError):
        deserialize_kv(blob[:-8])
    with pytest.raises(PDError):
        deserialize_kv(b"\x01")


def test_prefill_handler_exports_engine_result(world):
    eng = _engine(world)
    handler = make_pd_prefill_handler(eng)
    blob = handler({"ids": [5, 6, 7], "temperature": 0.0})
    tok, k, v, tl, b = deserialize_kv(blob)
    want_tok, (wk, wv), wtl, wb = eng.prefill([5, 6, 7])
    assert (tl, b) == (wtl, wb)
    assert tok == want_tok  # greedy: same logits both calls
    # the wire carries a row's heads apart, [L, 1, S, K, D], whoever
    # sends; the slab engine's own prefill hands them merged
    assert k.ndim == 5 and np.asarray(wk).ndim == 4
    np.testing.assert_array_equal(np.asarray(wk).reshape(k.shape), k)
    with pytest.raises(PDError):
        handler({"ids": []})


def test_pd_pair_matches_monolithic_over_http(world):
    """The full e2e: completions served by a decode node whose prefill
    comes from a separate prefill node over HTTP must be byte-identical
    to a monolithic engine's output (same params, greedy)."""
    # monolithic reference
    mono = EngineServer(Scheduler(_engine(world)), model_name="m")
    mono.start()
    # prefill node (serve.py wiring: no decode loop, /v1/* rejected)
    from ome_tpu.engine.serve import _PrefillNodeScheduler
    pre_engine = _engine(world)
    pre_srv = EngineServer(_PrefillNodeScheduler(pre_engine),
                           model_name="m",
                           pd_prefill=make_pd_prefill_handler(
                               pre_engine))
    pre_srv.start()
    # decode node (overlap on: the remote fetch rides the admission
    # thread, like production)
    decode_engine = RemotePrefillEngine(
        _engine(world), f"http://127.0.0.1:{pre_srv.port}")
    pd_srv = EngineServer(Scheduler(decode_engine, overlap=True),
                          model_name="m")
    pd_srv.start()

    def complete(port, stream=False):
        body = json.dumps({"model": "m", "prompt": "hi there pd",
                           "max_tokens": 6, "temperature": 0,
                           "stream": stream}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.read()

    try:
        want = json.loads(complete(mono.port))
        got = json.loads(complete(pd_srv.port))
        assert got["choices"] == want["choices"]
        assert got["usage"] == want["usage"]
        # streaming surface: identical SSE event payload bytes modulo
        # the request id counter
        want_s = complete(mono.port, stream=True)
        got_s = complete(pd_srv.port, stream=True)
        # identical SSE event payloads modulo the request-id counter
        assert [l.split(b'", ', 1)[-1] for l in want_s.splitlines()
                if l.startswith(b"data:")] == \
               [l.split(b'", ', 1)[-1] for l in got_s.splitlines()
                if l.startswith(b"data:")]
        # the prefill node rejects completions; the decode node rejects
        # nothing extra
        with pytest.raises(urllib.error.HTTPError) as ei:
            complete(pre_srv.port)
        assert ei.value.code == 503
    finally:
        for s in (mono, pre_srv, pd_srv):
            s.stop()


def _prefill_server(world):
    """A PD prefill node over real HTTP (serve.py wiring)."""
    from ome_tpu.engine.serve import _PrefillNodeScheduler
    eng = _engine(world)
    srv = EngineServer(_PrefillNodeScheduler(eng), model_name="m",
                       pd_prefill=make_pd_prefill_handler(eng))
    srv.start()
    return srv


def test_pool_failover_order(world):
    """A failed fetch on the first peer retries on the NEXT healthy
    peer (round-robin from the head), and the result is the same KV
    the healthy peer would have served directly."""
    from ome_tpu import faults
    a, b = _prefill_server(world), _prefill_server(world)
    a_url = f"http://127.0.0.1:{a.port}"
    b_url = f"http://127.0.0.1:{b.port}"
    eng = RemotePrefillEngine(_engine(world), peer_urls=[a_url, b_url],
                              timeout=10.0)
    try:
        # keyed rule: only peer A's fetch fails, proving A was the
        # first attempt and B the failover target
        faults.install(f"pd_fetch|{a_url}.raise@1")
        tok, (k, v), tl, bucket = eng.prefill([5, 6, 7])
        assert eng.failovers == 1
        assert eng._last_peer == b_url
        want_tok, (wk, wv), wtl, wb = eng._engine.prefill([5, 6, 7])
        assert (tok, tl, bucket) == (want_tok, wtl, wb)
        np.testing.assert_array_equal(
            np.asarray(wk).reshape(np.shape(k)), np.asarray(k))
        # peer A took the breaker charge, B did not
        assert eng.pool.peers[0].fails == 1
        assert eng.pool.peers[1].fails == 0
    finally:
        faults.reset()
        a.stop()
        b.stop()


def test_peer_death_mid_handoff_fails_over(world):
    """Killing a prefill peer between handoffs: later requests fail
    over to the surviving peer and the decode scheduler never
    restarts (the ISSUE 6 acceptance scenario, in-process)."""
    from ome_tpu.engine import Request
    a, b = _prefill_server(world), _prefill_server(world)
    eng = RemotePrefillEngine(
        _engine(world),
        peer_urls=[f"http://127.0.0.1:{a.port}",
                   f"http://127.0.0.1:{b.port}"],
        timeout=5.0)
    sched = Scheduler(eng, overlap=True)
    sched.start()
    try:
        def run(ids):
            req = sched.submit(Request(prompt_ids=ids,
                                       max_new_tokens=3))
            assert req.done.wait(60)
            return req
        assert run([1, 2, 3]).finish_reason == "length"  # served by A
        a.stop()  # peer death
        assert run([4, 5]).finish_reason == "length"     # rotation: B
        # rotation returns to the dead A: the fetch must fail over
        before = eng.failovers
        assert run([6, 7, 8]).finish_reason == "length"
        assert eng.failovers > before
        assert sched.healthy
        assert sched.stats["restarts_total"] == 0
    finally:
        sched.stop()
        b.stop()


def test_deadline_caps_attempt_timeout(world):
    """The per-attempt timeout is min(timeout, deadline remaining):
    a black-hole peer (accepts, never answers) cannot pin a request
    past its own deadline even with a 60s flat timeout — and a
    request whose deadline already expired fails immediately,
    skipping even the local fallback."""
    import socket
    import time
    sink = socket.socket()
    sink.bind(("127.0.0.1", 0))
    sink.listen(4)
    url = f"http://127.0.0.1:{sink.getsockname()[1]}"
    eng = RemotePrefillEngine(_engine(world), peer_urls=[url],
                              timeout=60.0, local_fallback=True)
    try:
        # expired deadline: no attempt, no fallback — PDError now
        t0 = time.monotonic()
        with pytest.raises(PDError):
            eng.prefill([1, 2], deadline=time.monotonic() - 1.0)
        assert time.monotonic() - t0 < 2.0
        assert eng.local_fallbacks == 0
        # live-but-tight deadline: attempt capped at ~1.5s (not 60s),
        # then the pool is exhausted and the local fallback serves it
        t0 = time.monotonic()
        tok, kv, tl, bucket = eng.prefill(
            [1, 2], deadline=time.monotonic() + 1.5)
        elapsed = time.monotonic() - t0
        assert elapsed < 20.0  # attempt + reprobe sweep, NOT 60s
        assert eng.local_fallbacks == 1
        want = eng._engine.prefill([1, 2])
        assert tok == want[0]
    finally:
        sink.close()


def test_pd_journal_kill_resume_byte_identical(world, tmp_path):
    """A journaled PD request killed mid-decode resumes on a fresh
    decode node byte-identical to an uninterrupted monolithic run —
    the journal's admit record carries the PD provenance, and the
    resume re-prefills (prompt + generated prefix) through the
    pool."""
    import time

    from ome_tpu import faults
    from ome_tpu.engine import Request
    from ome_tpu.engine.journal import RequestJournal
    d = str(tmp_path)
    pre = _prefill_server(world)
    url = f"http://127.0.0.1:{pre.port}"
    try:
        # uninterrupted monolithic reference
        ref_sched = Scheduler(_engine(world))
        ref_sched.start()
        ref = ref_sched.submit(Request(prompt_ids=[9, 8, 7],
                                       max_new_tokens=8))
        assert ref.done.wait(60) and ref.finish_reason == "length"
        ref_sched.stop()

        # PD decode node, journaled; die mid-decode (deterministic:
        # engine_step fault with no restart budget -> dead ->
        # journal entries resumable)
        faults.install("engine_step.raise@4")
        j = RequestJournal(d, fsync="always",
                           provenance={"mode": "pd-decode",
                                       "peers": [url]})
        sched = Scheduler(
            RemotePrefillEngine(_engine(world), peer_urls=[url]),
            overlap=True, max_restarts=0, journal=j)
        sched.start()
        req = sched.submit(Request(prompt_ids=[9, 8, 7],
                                   max_new_tokens=8))
        assert req.done.wait(60)
        assert req.finish_reason == "engine_fault"
        deadline = time.monotonic() + 15
        while sched.status != "dead" and time.monotonic() < deadline:
            time.sleep(0.01)
        got_before = list(req.output_ids)
        assert 0 < len(got_before) < 8  # genuinely interrupted
        sched.stop()
        j.close()
        faults.reset()

        # "new process": fresh engines over the same journal dir
        j2 = RequestJournal(d)
        entries = j2.replay()
        assert len(entries) == 1
        assert entries[0].pd == {"mode": "pd-decode", "peers": [url]}
        sched2 = Scheduler(
            RemotePrefillEngine(_engine(world), peer_urls=[url]),
            overlap=True, journal=j2)
        assert sched2.resume_from_journal() == 1
        resumed = sched2.pending.queue[0]
        assert resumed.prompt_ids == [9, 8, 7] + got_before
        sched2.start()
        assert resumed.done.wait(60)
        assert resumed.finish_reason == "length"
        sched2.stop()
        j2.close()
        assert resumed.output_ids == ref.output_ids  # byte-identical
    finally:
        faults.reset()
        pre.stop()


def test_remote_prefill_failure_fails_request_not_server(world):
    """A dead prefill peer fails the in-flight request but leaves the
    decode node HEALTHY (transient_prefill_errors contract): a peer
    restarting mid-rollout must not kill every stream on this node."""
    decode_engine = RemotePrefillEngine(_engine(world),
                                        "http://127.0.0.1:1",  # nothing
                                        timeout=2.0)
    sched = Scheduler(decode_engine, overlap=True)
    sched.start()
    try:
        from ome_tpu.engine import Request
        req = sched.submit(Request(prompt_ids=[1, 2, 3],
                                   max_new_tokens=4))
        assert req.done.wait(60)
        assert req.finish_reason == "error"
        assert sched.healthy  # transient: the node keeps serving
        req2 = sched.submit(Request(prompt_ids=[4, 5],
                                    max_new_tokens=2))
        assert req2.done.wait(60)
        assert req2.finish_reason == "error"
    finally:
        sched.stop()
