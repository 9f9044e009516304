"""Multi-host serving: jax.distributed rendezvous + op replication.

Honors the LWS contract the operator stamps out
(controllers/reconcilers/multinode.py:53-58): every pod in the group
gets JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID, the
engine joins the cross-host rendezvous at startup, and the compiled
prefill/insert/decode programs run SPMD over a mesh spanning every
host's chips. This is the role the reference's runtimes fill with
`--dist-init-addr $(LWS_LEADER_ADDRESS):5757 --nnodes ... --node-rank`
(config/runtimes/srt/deepseek-rdma-pd-rt.yaml:108-115 in
/root/reference) — redesigned for XLA's execution model:

  * SPMD means every process must enqueue the SAME compiled programs
    in the SAME order (collectives rendezvous across hosts). Only the
    leader (process 0) sees HTTP traffic, so the leader REPLICATES its
    op stream (prefill/insert/decode + host args) to followers over a
    TCP control channel, and followers replay it. Device results never
    cross the channel — each process computes identical values from
    identical programs (sampling keys derive from a shared fold_in
    counter), so the only bytes on the wire are op headers and token
    ids. This is JetStream/Pathways-style leader-driven serving.
  * Worker loss fails FAST: a dropped control socket kills the whole
    group (followers exit nonzero, the leader marks itself unhealthy),
    and the LeaderWorkerSet recreates the group — the same crash-and-
    recreate discipline the reference's multinode runtimes rely on.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import socket
import struct
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from .. import constants

log = logging.getLogger("ome.engine.multihost")

# leader's op-replication channel; distinct from the jax.distributed
# coordinator port (JAX_COORDINATOR_PORT in controllers/reconcilers)
CONTROL_PORT = 5858


@dataclasses.dataclass(frozen=True)
class DistContext:
    coordinator: str          # host:port of the jax.distributed service
    num_processes: int
    process_id: int

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0

    @property
    def coordinator_host(self) -> str:
        return self.coordinator.rsplit(":", 1)[0]


def init_from_env(env=None) -> Optional[DistContext]:
    """Join the cross-host rendezvous if the operator injected one.

    Reads the env contract from controllers/reconcilers/multinode.py;
    returns None (single-host mode) when JAX_COORDINATOR_ADDRESS is
    absent. MUST run before any other JAX call — jax.distributed can
    only initialize ahead of backend creation.
    """
    env = env if env is not None else os.environ
    coord = env.get(constants.JAX_COORDINATOR_ENV)
    if not coord:
        return None
    num = int(env.get(constants.JAX_NUM_PROCESSES_ENV, "1"))
    pid = int(env.get(constants.JAX_PROCESS_ID_ENV, "0"))
    if num <= 1:
        return None
    import jax
    if env.get("JAX_PLATFORMS", "").strip() == "cpu":
        # a multi-process CPU group (dev/CI topologies) needs an
        # explicit collectives implementation; the default "none"
        # rejects every cross-process computation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=num, process_id=pid)
    log.info("joined jax.distributed rendezvous %s as process %d/%d "
             "(%d global devices)", coord, pid, num, jax.device_count())
    return DistContext(coordinator=coord, num_processes=num,
                       process_id=pid)


def host_value(x) -> np.ndarray:
    """Fetch a (replicated) device value to host, multi-host safe.

    np.asarray on an array spanning non-addressable devices raises;
    the local shard of a replicated value is the whole value.
    """
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return np.asarray(x.addressable_shards[0].data)
    return np.asarray(x)


# -- control channel -------------------------------------------------------


def _send_msg(sock: socket.socket, msg: dict) -> None:
    data = json.dumps(msg).encode()
    sock.sendall(struct.pack("<I", len(data)) + data)


def _recv_msg(sock: socket.socket) -> Optional[dict]:
    hdr = _recv_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = struct.unpack("<I", hdr)
    body = _recv_exact(sock, n)
    if body is None:
        return None
    return json.loads(body)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


class OpPublisher:
    """Leader side: accepts every follower, then fans ops out in order.

    TCP per-connection ordering + one sender thread per send() caller
    (the scheduler thread) gives all followers the identical op
    sequence. A send failure means a follower died — the caller (the
    scheduler step) propagates, flipping the leader unhealthy so the
    LWS group restarts together.
    """

    def __init__(self, n_followers: int, port: int = CONTROL_PORT,
                 host: str = "0.0.0.0", accept_timeout: float = 600.0):
        self._server = socket.create_server((host, port))
        self._server.settimeout(accept_timeout)
        self._socks: List[socket.socket] = []
        for _ in range(n_followers):
            conn, addr = self._server.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._socks.append(conn)
            log.info("follower joined from %s (%d/%d)", addr,
                     len(self._socks), n_followers)

    def send(self, msg: dict) -> None:
        for sock in self._socks:
            _send_msg(sock, msg)

    def close(self) -> None:
        try:
            self.send({"op": "stop"})
        except OSError:
            pass
        for s in self._socks:
            s.close()
        self._server.close()


class OpSubscriber:
    """Follower side: connect (with retry — the leader pod may still be
    loading weights) and stream ops."""

    def __init__(self, host: str, port: int = CONTROL_PORT,
                 connect_timeout: float = 600.0):
        deadline = time.monotonic() + connect_timeout
        while True:
            try:
                self._sock = socket.create_connection((host, port),
                                                      timeout=10)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(1.0)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.settimeout(None)

    def recv(self) -> Optional[dict]:
        return _recv_msg(self._sock)

    def close(self) -> None:
        self._sock.close()


# -- leader / follower engine drivers --------------------------------------


class ReplicatedEngine:
    """Wraps an InferenceEngine so every device-touching op is
    published to the followers before the leader runs it. Drop-in for
    the Scheduler: same prefill/insert/decode surface.

    All ops publish AND execute under one lock: the scheduler thread
    drives prefill/insert/decode, but adapter registration arrives on
    an HTTP handler thread — without the lock, two sendall()s could
    interleave framed bytes, and the leader could apply a param swap
    at a different op-stream position than its followers (divergent
    SPMD state)."""

    # multi-token decode IS in the replicated op vocabulary:
    # decode_multi / verify / commit_spec below publish before
    # executing, so every plan kind the scheduler can build (chunk,
    # spec-verify, masked, pipelined) replays identically on the
    # followers. Without these explicit methods __getattr__ would leak
    # the wrapped engine's programs through unpublished (divergent
    # SPMD state) — which is why the attr used to be False.
    supports_multi_step = True

    def __init__(self, engine, publisher: OpPublisher):
        self._engine = engine
        self._pub = publisher
        self._oplock = threading.Lock()
        # honest per-instance capability: replication only helps if
        # the wrapped engine actually has the multi-step program
        self.supports_multi_step = bool(
            callable(getattr(engine, "decode_multi", None))
            and getattr(engine, "supports_multi_step", False))

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def new_state(self):
        return self._engine.new_state()

    def prefill(self, prompt_ids, temperature: float = 0.0,
                top_k: int = 0, top_p: float = 1.0, first_mask=None,
                adapter=None, deadline=None, trace=None):
        from .structured import pack_mask
        kw = {}
        if first_mask is not None:
            kw["first_mask"] = first_mask
        if adapter is not None:
            kw["adapter"] = adapter
        with self._oplock:
            blob_fn = getattr(self._engine, "prefill_blob", None)
            if blob_fn is not None:
                # PD decode group: the leader fetches the KV wire blob
                # ONCE and ships the bytes to followers — a follower
                # re-fetching could draw a different sampled token on
                # the prefill node (its RNG advances per request).
                # deadline/trace stay leader-side: followers only see
                # the replicated bytes, never the network. Forwarded
                # only when set, so blob providers predating the pool
                # (no deadline/trace kwargs) keep working.
                import base64
                if deadline is not None:
                    kw["deadline"] = deadline
                if trace is not None:
                    kw["trace"] = trace
                blob = blob_fn(prompt_ids, temperature, top_k, top_p,
                               **kw)
                self._pub.send({"op": "prefill_blob",
                                "blob": base64.b64encode(blob).decode()})
                from .pd import deserialize_kv
                token, k, v, true_len, bucket = deserialize_kv(blob)
                return token, (k, v), true_len, bucket
            self._pub.send({"op": "prefill",
                            "ids": list(map(int, prompt_ids)),
                            "temperature": float(temperature),
                            "top_k": int(top_k), "top_p": float(top_p),
                            # omelint: disable=lock-discipline -- the host-built mask IS the op payload; _oplock serializes whole ops by design
                            "first_mask": pack_mask(first_mask),
                            "adapter": adapter})
            return self._engine.prefill(prompt_ids, temperature, top_k,
                                        top_p, **kw)

    def insert(self, state, kv, slot: int, true_len: int, token: int,
               bucket: int, adapter=None):
        with self._oplock:
            self._pub.send({"op": "insert", "slot": int(slot),
                            "true_len": int(true_len),
                            "token": int(token),
                            "bucket": int(bucket), "adapter": adapter})
            kw = {} if adapter is None else {"adapter": adapter}
            return self._engine.insert(state, kv, slot, true_len,
                                       token, bucket, **kw)

    def register_adapter(self, name: str, adapter_dir: str) -> int:
        """Replicated hot adapter load: the staged dir must exist on
        every host (shared PVC / serving-agent staging on each).
        Local call FIRST: if it raises (bad dir, no free slot), no op
        is published and followers stay consistent."""
        with self._oplock:
            idx = self._engine.register_adapter(name, adapter_dir)
            self._pub.send({"op": "register_adapter", "name": name,
                            "path": adapter_dir})
            return idx

    def unregister_adapter(self, name: str) -> None:
        with self._oplock:
            # local first: an in-flight-adapter refusal
            # (core.unregister_adapter ValueError) must not reach
            # followers — their slot refs clear via the free_slot op,
            # so a leader success replays cleanly
            self._engine.unregister_adapter(name)
            self._pub.send({"op": "unregister_adapter", "name": name})

    def free_slot(self, slot: int) -> None:
        """Replicated slot release (adapter refs + paged KV blocks) —
        keeps follower allocators and the unregister guard in
        lockstep with the leader's scheduler."""
        with self._oplock:
            self._pub.send({"op": "free_slot", "slot": int(slot)})
            self._engine.free_slot(slot)

    def set_mask_row(self, row: int, bits) -> None:
        """Replicated grammar mask-table upload: the leader's
        scheduler installs a compiled automaton-state mask; followers
        must install the IDENTICAL row before any plan references its
        index, which op-stream ordering guarantees (uploads publish
        before the decode/verify ops that gather them)."""
        from .structured import pack_mask
        with self._oplock:
            self._pub.send({"op": "set_mask_row", "row": int(row),
                            # omelint: disable=lock-discipline -- the host-built mask row IS the op payload; _oplock serializes whole ops by design
                            "bits": pack_mask(np.asarray(bits, bool))})
            self._engine.set_mask_row(row, bits)

    def decode(self, state, temperature, top_k, top_p, mask=None,
               mask_idx=None):
        from .structured import pack_mask
        # grammar mask-table row indices (ints on the wire, vs ~V/8
        # bytes per packed row) — converted before taking the op lock
        midx = None if mask_idx is None \
            else np.asarray(mask_idx, np.int32).tolist()
        with self._oplock:
            self._pub.send({"op": "decode",
                            "mask_idx": midx,
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "temperature": np.asarray(
                                temperature, np.float32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "top_k": np.asarray(top_k,
                                                np.int32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "top_p": np.asarray(top_p,
                                                np.float32).tolist(),
                            # structured outputs: the leader's host-
                            # built mask ships in the op (packbits
                            # ~V/8 bytes per constrained slot) so
                            # followers run the IDENTICAL masked
                            # program — no recompute drift
                            # omelint: disable=lock-discipline -- the host-built mask IS the op payload; _oplock serializes whole ops by design
                            "mask": pack_mask(mask)})
            if mask_idx is not None:
                state, toks = self._engine.decode(
                    state, temperature, top_k, top_p,
                    mask_idx=mask_idx)
            elif mask is not None:
                state, toks = self._engine.decode(
                    state, temperature, top_k, top_p, mask=mask)
            else:
                state, toks = self._engine.decode(state, temperature,
                                                  top_k, top_p)
            # omelint: disable=lock-discipline -- the local-replica fetch completes the op; _oplock serializes whole ops by design
            return state, host_value(toks)

    def decode_multi(self, state, temperature, top_k, top_p,
                     steps: int, budget, stop_ids,
                     lookahead_rows=None, mask=None, mask_idx=None):
        """Replicated multi-token chunk: the whole StepPlan payload
        (sampling, per-slot budget, stop table, paged lookahead, the
        [B, steps, V] mask stack OR its [B, steps] mask-table row
        indices) ships in the op, so followers run the IDENTICAL
        K-step device loop."""
        from .structured import pack_mask
        # mask-table row indices converted before taking the op lock
        midx = None if mask_idx is None \
            else np.asarray(mask_idx, np.int32).tolist()
        with self._oplock:
            self._pub.send({"op": "decode_multi",
                            "steps": int(steps),
                            "mask_idx": midx,
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "temperature": np.asarray(
                                temperature, np.float32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "top_k": np.asarray(top_k,
                                                np.int32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "top_p": np.asarray(top_p,
                                                np.float32).tolist(),
                            # omelint: disable=lock-discipline -- plan payloads ship host-side in the op; _oplock serializes whole ops by design
                            "budget": np.asarray(
                                budget, np.int32).tolist(),
                            # omelint: disable=lock-discipline -- plan payloads ship host-side in the op; _oplock serializes whole ops by design
                            "stop_ids": np.asarray(
                                stop_ids, np.int32).tolist(),
                            "lookahead_rows": None
                            if lookahead_rows is None
                            else int(lookahead_rows),
                            # omelint: disable=lock-discipline -- the host-built mask stack IS the op payload; _oplock serializes whole ops by design
                            "mask": pack_mask(mask)})
            kw = {}
            if lookahead_rows is not None:
                kw["lookahead_rows"] = lookahead_rows
            if mask_idx is not None:
                kw["mask_idx"] = mask_idx
            elif mask is not None:
                kw["mask"] = mask
            state, out, adv = self._engine.decode_multi(
                state, temperature, top_k, top_p, steps=steps,
                budget=budget, stop_ids=stop_ids, **kw)
            # omelint: disable=lock-discipline -- the local-replica fetch completes the op; _oplock serializes whole ops by design
            return state, host_value(out), host_value(adv)

    def verify(self, state, drafts, draft_len, temperature, top_k,
               top_p, lookahead_rows=None, mask=None, mask_idx=None):
        """Replicated spec-verify: the leader's host-built drafts (and
        the position-0 mask, or per-position mask-table row indices,
        for masked slots) ship in the op — followers never run the
        drafter, they replay its output."""
        from .structured import pack_mask
        # mask-table row indices converted before taking the op lock
        midx = None if mask_idx is None \
            else np.asarray(mask_idx, np.int32).tolist()
        with self._oplock:
            self._pub.send({"op": "verify",
                            "mask_idx": midx,
                            # omelint: disable=lock-discipline -- plan payloads ship host-side in the op; _oplock serializes whole ops by design
                            "drafts": np.asarray(
                                drafts, np.int32).tolist(),
                            # omelint: disable=lock-discipline -- plan payloads ship host-side in the op; _oplock serializes whole ops by design
                            "draft_len": np.asarray(
                                draft_len, np.int32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "temperature": np.asarray(
                                temperature, np.float32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "top_k": np.asarray(top_k,
                                                np.int32).tolist(),
                            # omelint: disable=lock-discipline -- sampling params ship host-side in the op; _oplock serializes whole ops by design
                            "top_p": np.asarray(top_p,
                                                np.float32).tolist(),
                            "lookahead_rows": None
                            if lookahead_rows is None
                            else int(lookahead_rows),
                            # omelint: disable=lock-discipline -- the host-built mask IS the op payload; _oplock serializes whole ops by design
                            "mask": pack_mask(mask)})
            kw = {}
            if lookahead_rows is not None:
                kw["lookahead_rows"] = lookahead_rows
            if mask_idx is not None:
                kw["mask_idx"] = mask_idx
            elif mask is not None:
                kw["mask"] = mask
            state, out, acc = self._engine.verify(
                state, drafts, draft_len, temperature, top_k, top_p,
                **kw)
            # omelint: disable=lock-discipline -- the local-replica fetch completes the op; _oplock serializes whole ops by design
            return state, host_value(out), host_value(acc)

    def commit_spec(self, slot: int, advance: int,
                    reserve: int = 0) -> None:
        """Replicated spec/chunk commit: pure host bookkeeping, but it
        trims speculative paged-KV blocks — followers must replay it
        or their block tables drift from the leader's and the next
        compiled program sees different allocations."""
        with self._oplock:
            self._pub.send({"op": "commit_spec", "slot": int(slot),
                            "advance": int(advance),
                            "reserve": int(reserve)})
            self._engine.commit_spec(slot, advance, reserve=reserve)


def _unknown_adapter(e: Exception) -> bool:
    try:
        from .core import UnknownAdapterError
    except Exception:  # pragma: no cover
        return False
    return isinstance(e, UnknownAdapterError)


def follower_loop(engine, sub: OpSubscriber,
                  pd_export: bool = False) -> int:
    """Replay the leader's op stream against the local engine.

    Every value the replay needs beyond the op headers (prefill KV,
    sampled tokens) is recomputed locally — identical programs +
    identical inputs + shared RNG counters give identical results, so
    insert() can consume the follower's OWN last prefill output.
    Structured-output masks arrive IN the ops (leader-built, packed) so
    masked sampling is bit-identical across the group.
    `pd_export`: this is a PD prefill-pool follower — after each
    prefill replay, join the leader's process_allgather collective
    (pd.gather_kv) that exports the KV to the wire.
    Returns an exit code: 0 on orderly stop, 1 on a dropped leader.
    """
    from .structured import unpack_mask
    state = engine.new_state()
    last_prefill: Optional[Tuple] = None
    while True:
        msg = sub.recv()
        if msg is None:
            log.error("control channel dropped; exiting for group "
                      "restart")
            return 1
        op = msg["op"]
        if op == "stop":
            return 0
        if op == "prefill":
            fm = unpack_mask(msg.get("first_mask"))
            kwargs = {} if fm is None else {"first_mask": fm}
            if msg.get("adapter") is not None:
                kwargs["adapter"] = msg["adapter"]
            try:
                last_prefill = engine.prefill(
                    msg["ids"], msg["temperature"], msg["top_k"],
                    msg["top_p"], **kwargs)
            except Exception as e:
                if not _unknown_adapter(e):
                    raise
                # the leader hit the IDENTICAL per-request error before
                # any device op ran on either side (it publishes, then
                # executes) — skip in lockstep instead of dying
                last_prefill = None
                continue
            if pd_export:
                from .pd import gather_kv
                _, (k, v), _, _ = last_prefill
                gather_kv(k)
                gather_kv(v)
        elif op == "prefill_blob":
            # PD decode group: the leader shipped the prefill pool's
            # KV bytes; deserialize locally — no fetch, no compute
            import base64
            from .pd import deserialize_kv
            token, k, v, true_len, bucket = deserialize_kv(
                base64.b64decode(msg["blob"]))
            last_prefill = (token, (k, v), true_len, bucket)
        elif op == "insert":
            if last_prefill is None:
                continue  # its prefill failed in lockstep (adapter)
            tok, kv, _true_len, _bucket = last_prefill
            ikw = {} if msg.get("adapter") is None \
                else {"adapter": msg["adapter"]}
            try:
                state = engine.insert(state, kv, msg["slot"],
                                      msg["true_len"], tok,
                                      msg["bucket"], **ikw)
            except Exception as e:
                if not _unknown_adapter(e):
                    raise
        elif op == "register_adapter":
            engine.register_adapter(msg["name"], msg["path"])
        elif op == "unregister_adapter":
            try:
                engine.unregister_adapter(msg["name"])
            except ValueError:
                # the leader only publishes after ITS unload succeeded;
                # a local refusal means this follower's adapter refs
                # drifted (e.g. a missed free_slot) — clear ONLY the
                # refused adapter's slot refs (other adapters' in-
                # flight sequences are not drifted, and zeroing them
                # would let a racing unregister of a busy adapter slip
                # through), NOT the KV blocks: active sequences still
                # own those. Then follow the leader rather than
                # killing the group.
                log.warning("unregister %r refused locally; clearing "
                            "its stale adapter refs to follow the "
                            "leader", msg["name"])
                idx = engine.adapter_id(msg["name"])
                refs = engine._slot_adapters
                refs[refs == idx] = 0
                engine.unregister_adapter(msg["name"])
        elif op == "free_slot":
            engine.free_slot(msg["slot"])
        elif op == "set_mask_row":
            # grammar mask-table upload: install the leader's row
            # before any subsequent op gathers its index (op-stream
            # order guarantees the happens-before)
            engine.set_mask_row(msg["row"],
                                unpack_mask(msg["bits"]))
        elif op == "decode":
            mask = unpack_mask(msg.get("mask"))
            kwargs = {} if mask is None else {"mask": mask}
            if msg.get("mask_idx") is not None:
                kwargs = {"mask_idx": np.asarray(msg["mask_idx"],
                                                 np.int32)}
            state, _ = engine.decode(
                state,
                np.asarray(msg["temperature"], np.float32),
                np.asarray(msg["top_k"], np.int32),
                np.asarray(msg["top_p"], np.float32), **kwargs)
        elif op == "decode_multi":
            kwargs = {}
            if msg.get("lookahead_rows") is not None:
                kwargs["lookahead_rows"] = msg["lookahead_rows"]
            mask = unpack_mask(msg.get("mask"))
            if msg.get("mask_idx") is not None:
                kwargs["mask_idx"] = np.asarray(msg["mask_idx"],
                                                np.int32)
            elif mask is not None:
                kwargs["mask"] = mask
            state, _, _ = engine.decode_multi(
                state,
                np.asarray(msg["temperature"], np.float32),
                np.asarray(msg["top_k"], np.int32),
                np.asarray(msg["top_p"], np.float32),
                steps=msg["steps"],
                budget=np.asarray(msg["budget"], np.int32),
                stop_ids=np.asarray(msg["stop_ids"], np.int32),
                **kwargs)
        elif op == "verify":
            kwargs = {}
            if msg.get("lookahead_rows") is not None:
                kwargs["lookahead_rows"] = msg["lookahead_rows"]
            mask = unpack_mask(msg.get("mask"))
            if msg.get("mask_idx") is not None:
                kwargs["mask_idx"] = np.asarray(msg["mask_idx"],
                                                np.int32)
            elif mask is not None:
                kwargs["mask"] = mask
            state, _, _ = engine.verify(
                state,
                np.asarray(msg["drafts"], np.int32),
                np.asarray(msg["draft_len"], np.int32),
                np.asarray(msg["temperature"], np.float32),
                np.asarray(msg["top_k"], np.int32),
                np.asarray(msg["top_p"], np.float32), **kwargs)
        elif op == "commit_spec":
            engine.commit_spec(msg["slot"], msg["advance"],
                               reserve=msg["reserve"])
        else:
            log.error("unknown op %r from leader", op)
            return 1
