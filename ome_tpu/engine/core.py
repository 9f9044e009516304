"""Inference engine core: slot-based continuous batching primitives.

TPU-first re-design of what the reference delegates to SGLang/vLLM
(SURVEY.md L0 — external engines, out of its repo): here the engine is
in-repo and JAX-native, structured like JetStream for XLA's compilation
model:

  * fixed decode batch of `max_slots` slots, one sequence each — every
    decode step is ONE compiled program with static shapes, whatever
    mix of requests is in flight;
  * prefill runs per-request at bucketed lengths (few compilations),
    producing a KV prefix that is *inserted* into a slot;
  * per-slot cache write positions (KVCache.index as a [B] vector) let
    every slot sit at a different sequence length;
  * sampling params are [B] vectors so one program serves all requests.

The three jitted programs (prefill / insert / decode) donate their
state buffers, so cache updates are in-place in HBM.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import device
from ..models import llama, mla
from ..models.config import ModelConfig
from ..ops.attention import latent_prefill_block_kinds, prefill_block_kinds
from ..ops.paged import TRASH_BLOCK
from ..telemetry.scopes import scoped
from . import sampling

Params = llama.Params

# every sampling call site of the programs below traces under the
# `sample` phase (telemetry/scopes.py)
sample = scoped("sample")(sampling.sample)
spec_verify = scoped("sample")(sampling.spec_verify)

log = logging.getLogger("ome.engine.core")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """Device-resident state of the decode batch."""

    # the dense slab: merged rows [L, B, Smax, K * Dh], a row's K
    # heads side by side in the lanes, as `flash_decode` reads them
    # (llama.KVCache); the paged pool: [L, N, block, K, Dh]
    k: jax.Array
    v: jax.Array
    lengths: jax.Array  # [B] int32 — valid kv rows / next write index
    tokens: jax.Array   # [B] int32 — last sampled token per slot
    # [B] int32 — LoRA adapter slot per sequence (0 = base model);
    # selects the per-slot low-rank delta inside the decode matmuls
    adapters: jax.Array = None
    # int8 paged pools only ([L, N, K, block] f32): per-(row, head)
    # dequant scales riding next to the quantized pools; None for
    # bf16 pools and the dense cache
    k_scale: jax.Array = None
    v_scale: jax.Array = None
    # hybrid models only (cfg.is_hybrid), the second kind of per-slot
    # state: what a slot carries through the Gated DeltaNet layers,
    # {"S": [Ll, B, Hv, dk, dv] float32, "conv": [Ll, B, W-1, C]}
    # (llama.recurrent_state); k and v then hold the full-attention
    # layers only. A slot's insert overwrites its rows of both; there
    # is nothing to free
    rec: dict = None
    # models served with a share of their experts only: [3] uint32
    # counters the decode programs add to (llama.KVCache.stats)
    moe_stats: jax.Array = None
    # periodic window / global models only (cfg.window_layers), the
    # third kind: the window layers' ring, [Lw, B, W, K * Dh], position
    # p in row p % W (llama.KVCache.wk, docs/window-cache.md); k and v
    # then hold the global layers only. A slot's insert overwrites its
    # rows; there is nothing to free
    wk: jax.Array = None
    wv: jax.Array = None


def _cache_of(st: DecodeState, table: Optional[jax.Array] = None):
    """A decode state as the model's forward takes it: the dense slab
    (and a hybrid model's recurrent state), or, with a block `table`,
    the paged pool."""
    if table is None:
        return llama.KVCache(k=st.k, v=st.v, index=st.lengths,
                             rec=st.rec, stats=st.moe_stats,
                             wk=st.wk, wv=st.wv)
    return llama.PagedKVCache(k=st.k, v=st.v, index=st.lengths,
                              table=table, k_scale=st.k_scale,
                              v_scale=st.v_scale)


def _state_of(nc, toks: jax.Array, st: DecodeState,
              lengths: Optional[jax.Array] = None) -> DecodeState:
    """The decode state after a forward left the cache `nc`, of
    either kind; `lengths` where they are not the cache's own index.
    The one place a program body builds its next state: a field the
    model's caches grow is carried on here."""
    return DecodeState(k=nc.k, v=nc.v,
                       lengths=nc.index if lengths is None else lengths,
                       tokens=toks, adapters=st.adapters,
                       k_scale=getattr(nc, "k_scale", None),
                       v_scale=getattr(nc, "v_scale", None),
                       rec=getattr(nc, "rec", None),
                       moe_stats=getattr(nc, "stats", None),
                       wk=getattr(nc, "wk", None),
                       wv=getattr(nc, "wv", None))


# how a structured-output mask reaches a program, as the suffix of the
# program's name and what it takes after its other arguments
# (docs/structured-outputs.md): nothing, so that an unconstrained
# batch never pays a mask transfer; a dense bool array the host
# ships; the device-resident mask table and int32 row indices into
# it, a few ints per slot on the wire instead of a vocabulary of bools
MASK_KINDS = ("", "_masked", "_masked_idx")


def _mask_bits(mask: tuple) -> Optional[jax.Array]:
    """The allowed-token bits of a program's trailing mask arguments:
    () none, (bits,) as given, (table, idx) the table's rows gathered
    here, inside the program. Row 0 of the table is all-True, so an
    unmasked slot's index 0 masks nothing."""
    if not mask:
        return None
    if len(mask) == 1:
        return mask[0]
    table, idx = mask
    return table[idx]


def _masked(logits: jax.Array, bits: Optional[jax.Array]) -> jax.Array:
    """`logits` with every token outside `bits` made unsampleable."""
    return logits if bits is None else jnp.where(bits, logits, -jnp.inf)


def _counts_of(st: DecodeState) -> tuple:
    """What a dense decode program returns after its other results:
    the expert counters as an output of their own, which no later
    step donates (InferenceEngine.moe_counters reads it), or nothing
    for a model that does not count."""
    return () if st.moe_stats is None else (st.moe_stats + 0,)


class UnknownAdapterError(ValueError):
    """Request names a LoRA adapter the engine doesn't have loaded —
    a PER-REQUEST error (e.g. racing a hot unload), never a scheduler
    fault."""


class KVPoolExhausted(RuntimeError):
    """Paged-KV insert could not allocate blocks for a new sequence —
    BACKPRESSURE, not a fault: the scheduler requeues the request
    until streams finish and free blocks (decode-time growth instead
    preempts a victim sequence, which re-enters the queue)."""


def _bucketize(n: int, buckets: List[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _sampling_array(x, dtype) -> np.ndarray:
    """Per-step sampling params: convert host inputs, but pass
    device-resident jax.Arrays through untouched — converting those
    back with np.asarray would force a device->host sync in the middle
    of the decode loop (exactly the bubble the pipelined scheduler
    removes by caching them on device)."""
    if isinstance(x, jax.Array):
        return x
    return np.asarray(x, dtype)


class PrefixCache:
    """Radix (token-block trie) cache of prompt-prefix KV with an HBM
    byte budget.

    Prompts are split into fixed token BLOCKS; each trie node owns one
    block's KV slice ([L, 1, block, K, Dh] device buffers; with
    `merged_rows`, a slab engine's, [L, 1, block, K * Dh]). Sibling
    prompts therefore share every common leading block — a prompt that
    diverges halfway through a cached entry still reuses the shared
    half (the sharing the sglang-router's cache-aware steering relies
    on, round-2 review weak #5). Eviction is byte-accounted LRU over
    leaf nodes: total device bytes never exceed `capacity_bytes`
    regardless of entry count or sequence lengths.

    A hit returns the concatenated leading blocks, so suffix-prefill
    `keep` lengths are block multiples (bounded recompilation:
    max_seq/block variants).
    """

    def __init__(self, capacity_bytes: int = 0, block: int = 32,
                 min_prefix: int = 16, host_capacity_bytes: int = 0,
                 merged_rows: bool = False):
        self.capacity_bytes = capacity_bytes
        self.merged_rows = merged_rows
        self.block = block
        self.min_prefix = min_prefix
        self._root: Dict[tuple, dict] = {}
        self._tick = 0
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # host-DRAM tier (--prefix-cache-host-mb): LRU of evicted
        # block KV as host numpy copies, keyed by the block's full
        # token path. A device hit that continues into host-resident
        # blocks only ENQUEUES an async swap-in — the admitting
        # request recomputes the remainder locally (suffix prefill is
        # the correctness fallback), the NEXT same-prefix request
        # hits the swapped-in device blocks. 0 disables the tier.
        self.host_capacity_bytes = host_capacity_bytes
        self.host_bytes = 0
        self.host_hits = 0
        self.host_swapins = 0
        self.host_recomputes = 0
        # path -> (np_k, np_v, nbytes); insertion order = LRU order
        import collections
        self._host: "collections.OrderedDict" = \
            collections.OrderedDict()
        import queue
        import threading
        self._tier_lock = threading.Lock()
        self._swap_q = queue.Queue()
        self._swap_thread: Optional[object] = None

    def _leaf_bytes(self, k, v) -> int:
        return k.nbytes + v.nbytes

    def put(self, ids, k, v, true_len: int, bucket: int):
        """Store the KV of `ids[:true_len]` block by block. k/v:
        [L, 1, S>=true_len, K, D*] device arrays (rows past true_len
        are padding and never stored)."""
        if self.capacity_bytes <= 0 or true_len < self.min_prefix:
            return
        if self.merged_rows and k.ndim == 5:
            # KV a peer sent: the wire's rows lie heads apart
            # (engine/pd.py), this engine's merged; one trie, one rank
            k, v = (x.reshape(x.shape[:3] + (-1,)) for x in (k, v))
        with self._tier_lock:
            node_map = self._root
            self._tick += 1
            for off in range(0, (true_len // self.block) * self.block,
                             self.block):
                key = tuple(ids[off:off + self.block])
                node = node_map.get(key)
                if node is None:
                    ks = k[:, :, off:off + self.block]
                    vs = v[:, :, off:off + self.block]
                    node = {"kv": (ks, vs), "children": {},
                            "last": self._tick}
                    node_map[key] = node
                    self.bytes += self._leaf_bytes(ks, vs)
                    # device copy is authoritative again: a stale
                    # host-tier copy of the same path just wastes
                    # host budget
                    ent = self._host.pop(tuple(ids[:off + self.block]),
                                         None)
                    if ent is not None:
                        self.host_bytes -= ent[2]
                node["last"] = self._tick
                node_map = node["children"]
            spills = self._evict_locked()
        self._spill(spills)

    def _evict_locked(self):
        """Drop least-recently-used LEAF nodes until within budget
        (parents stay useful for the prompts that still share them).
        One DFS collects every current leaf; evicting a leaf can
        expose its parent as a new leaf, so loop (bounded by trie
        depth) only if a whole pass wasn't enough. With the host tier
        enabled, an evicted leaf's KV is returned as [(path, kv)] for
        the caller to spill to host DRAM AFTER releasing _tier_lock —
        the device->host copy blocks, and a lock region must never
        reach a blocking fetch."""
        spills = []
        while self.bytes > self.capacity_bytes:
            leaves = []
            stack = [(self._root, ())]
            while stack:
                node_map, path = stack.pop()
                for key, node in node_map.items():
                    if node["children"]:
                        stack.append((node["children"], path + key))
                    else:
                        leaves.append((node["last"], node_map, key,
                                       node, path + key))
            if not leaves:
                return spills
            leaves.sort(key=lambda t: t[0])
            for _, parent_map, key, node, path in leaves:
                if self.bytes <= self.capacity_bytes:
                    return spills
                self.bytes -= self._leaf_bytes(*node["kv"])
                self.evictions += 1
                if self.host_capacity_bytes > 0:
                    spills.append((path, node["kv"]))
                del parent_map[key]
        return spills

    def _device_resident_locked(self, path: tuple) -> bool:
        node_map = self._root
        for off in range(0, len(path), self.block):
            node = node_map.get(path[off:off + self.block])
            if node is None:
                return False
            node_map = node["children"]
        return True

    def _spill(self, spills) -> None:
        """Copy evicted blocks' KV to the host tier. Runs OUTSIDE
        _tier_lock (the jax arrays are immutable, so the fetch needs
        no guard; admission path, never the step path), re-acquiring
        only for the dict edits. A put() that re-created the same
        path while the copy ran wins — its device copy is
        authoritative, so the stale spill is dropped."""
        for path, kv in spills:
            ks = np.asarray(kv[0])
            vs = np.asarray(kv[1])
            nbytes = ks.nbytes + vs.nbytes
            with self._tier_lock:
                if self._device_resident_locked(path):
                    continue
                old = self._host.pop(path, None)
                if old is not None:
                    self.host_bytes -= old[2]
                self._host[path] = (ks, vs, nbytes)
                self.host_bytes += nbytes
                while self.host_bytes > self.host_capacity_bytes \
                        and self._host:
                    _, (_, _, nb) = self._host.popitem(last=False)
                    self.host_bytes -= nb

    def _request_swapin(self, ids, eff: int) -> bool:
        """Queue every consecutive host-resident continuation block
        past the device hit for async swap-in. Called under
        _tier_lock; the actual device upload happens on the swap
        thread so admission never waits on it. Returns whether
        anything was queued — the caller starts the swap thread
        AFTER releasing the lock."""
        paths = []
        while eff + self.block <= len(ids) - 1:
            path = tuple(ids[:eff + self.block])
            if path not in self._host:
                break
            self._host.move_to_end(path)  # refresh host LRU
            paths.append(path)
            eff += self.block
        if not paths:
            return False
        self.host_hits += len(paths)
        # this request cannot use host blocks (the swap must never
        # gate admission): it recomputes the remainder locally
        self.host_recomputes += 1
        for path in paths:
            self._swap_q.put(path)
        return True

    def _ensure_swap_thread(self) -> None:
        import threading
        if self._swap_thread is not None and \
                self._swap_thread.is_alive():
            return
        self._swap_thread = threading.Thread(
            target=self._swap_loop, name="prefix-swap", daemon=True)
        self._swap_thread.start()

    def _swap_loop(self) -> None:
        """Swap-in worker: re-attach host-tier blocks to the device
        trie. Each upload is an async host->device transfer; trie
        surgery holds _tier_lock only for the dict edits. A block
        whose parent chain was evicted in the meantime stays in the
        host tier (a later deeper hit re-queues it)."""
        while True:
            path = self._swap_q.get()
            try:
                if path is None:  # shutdown sentinel (tests)
                    return
                self._swapin_one(path)
            except Exception:  # pragma: no cover — a failed swap
                pass           # only costs a future recompute
            finally:
                self._swap_q.task_done()

    def _swapin_one(self, path: tuple) -> None:
        with self._tier_lock:
            ent = self._host.get(path)
            if ent is None:
                return
            # the parent chain must be device-resident for the block
            # to be reachable by match(); otherwise leave it hosted
            node_map = self._root
            ok = True
            for off in range(0, len(path) - self.block, self.block):
                node = node_map.get(path[off:off + self.block])
                if node is None:
                    ok = False
                    break
                node_map = node["children"]
            key = path[-self.block:]
            if not ok or key in node_map:
                return
            ks, vs, nbytes = self._host.pop(path)
            self.host_bytes -= nbytes
            kd, vd = jnp.asarray(ks), jnp.asarray(vs)
            self._tick += 1
            node_map[key] = {"kv": (kd, vd), "children": {},
                             "last": self._tick}
            self.bytes += self._leaf_bytes(kd, vd)
            self.host_swapins += 1
            spills = self._evict_locked()
        self._spill(spills)

    def drain_swapins(self, timeout: float = 5.0) -> None:
        """Block until every queued swap-in has been applied — test
        and chaos-harness hook, never called from the serving path."""
        import time as _time
        q = self._swap_q
        deadline = _time.monotonic() + timeout
        # unfinished_tasks (not empty()): a popped path still being
        # applied must count — queue-empty races the apply
        while q.unfinished_tasks:
            if _time.monotonic() >= deadline:
                return
            _time.sleep(0.005)

    def tier_conservation(self) -> Tuple[bool, int, int]:
        """Two-tier accounting check: recounted device-trie bytes and
        host-tier bytes must equal the running counters, no block may
        be resident in both tiers, and the host tier must respect its
        budget. Returns (ok, device_blocks, host_blocks) — chaos
        asserts this alongside the pool's kv_conservation."""
        with self._tier_lock:
            dev_bytes = 0
            dev_blocks = 0
            overlap = False
            stack = [(self._root, ())]
            while stack:
                node_map, path = stack.pop()
                for key, node in node_map.items():
                    dev_blocks += 1
                    dev_bytes += self._leaf_bytes(*node["kv"])
                    if path + key in self._host:
                        overlap = True
                    stack.append((node["children"], path + key))
            host_bytes = sum(e[2] for e in self._host.values())
            ok = (dev_bytes == self.bytes
                  and host_bytes == self.host_bytes
                  and not overlap
                  and host_bytes <= max(self.host_capacity_bytes, 0))
            return ok, dev_blocks, len(self._host)

    def match(self, ids, usable=None) -> Optional[tuple]:
        """Longest cached STRICT prefix of `ids` in whole blocks (the
        last prompt token must re-run so its logits exist for
        sampling). Returns (k, v, eff, eff) with k/v concatenated over
        the matched blocks.

        `usable(eff) -> bool` lets the caller veto prefix lengths its
        downstream budget cannot use (e.g. prefix + suffix bucket
        overflowing the largest prefill bucket) BEFORE the hit is
        counted and recency refreshed — shorter candidates are tried
        block by block.

        Host-tier blocks NEVER serve the current request: a match
        that continues into the host tier queues an async swap-in and
        returns only the device-resident prefix (possibly None) — the
        caller recomputes the rest, the next same-prefix request hits
        on device."""
        if self.capacity_bytes <= 0:
            return None
        queued = False
        try:
            with self._tier_lock:
                limit = len(ids) - 1
                node_map = self._root
                slices = []
                eff = 0
                self._tick += 1
                while eff + self.block <= limit:
                    key = tuple(ids[eff:eff + self.block])
                    node = node_map.get(key)
                    if node is None:
                        break
                    node["last"] = self._tick
                    slices.append(node["kv"])
                    eff += self.block
                    node_map = node["children"]
                if self.host_capacity_bytes > 0:
                    queued = self._request_swapin(ids, eff)
                while slices and usable is not None \
                        and not usable(eff):
                    slices.pop()
                    eff -= self.block
                if eff < self.min_prefix:
                    self.misses += 1
                    return None
                self.hits += 1
                if len(slices) == 1:
                    k, v = slices[0]
                else:
                    k = jnp.concatenate([s[0] for s in slices],
                                        axis=2)
                    v = jnp.concatenate([s[1] for s in slices],
                                        axis=2)
                return (k, v, eff, eff)
        finally:
            # thread start stays OUTSIDE the lock region (it is the
            # edge to the swap loop, whose uploads block)
            if queued:
                self._ensure_swap_thread()


# what cannot be had with a model whose slots own state that is not
# full-length KV rows, and why: feature -> {kind of state -> reason}.
# "recurrent": a hybrid model's DeltaNet layers carry a state matrix
# and a conv tail per slot (docs/recurrent-state.md); "ring": a
# periodic window / global model's window layers keep the last
# `sliding_window` rows only, position p in row p % W
# (docs/window-cache.md). The engine refuses what reaches its
# constructor, serve.py all of it at start-up
SLOT_STATE_KINDS = {
    "recurrent": "this model's linear-attention layers carry recurrent "
                 "state per slot, not KV rows (docs/recurrent-state.md)",
    "ring": "this model's window layers keep a ring of their last "
            "`sliding_window` KV rows per slot, not every row "
            "(docs/window-cache.md)",
}
SLOT_STATE_REFUSALS = {
    "kv_block": {
        "recurrent": "--kv-block: the paged pool holds KV rows by "
                     "block; a slot's recurrent state is not rows and "
                     "has no place in it",
        "ring": "--kv-block: the paged pool has ONE block table for "
                "all layers, so every layer would keep every row; a "
                "ring of `sliding_window` rows has no table of its own",
    },
    "prefix_cache": {
        "recurrent": "--prefix-cache-mb / --prefix-cache-host-mb: a "
                     "cached prefix holds the KV rows of the full-"
                     "attention layers without the recurrent state at "
                     "the prefix's end, so a suffix prefill cannot "
                     "resume from it; pass --prefix-cache-mb 0",
        "ring": "--prefix-cache-mb / --prefix-cache-host-mb: the ring "
                "holds a prompt's LAST rows, and a shorter prefix's "
                "window rows are overwritten by then, so a suffix "
                "prefill cannot resume from a cached prefix; pass "
                "--prefix-cache-mb 0",
    },
    "lora": {
        "recurrent": "--adapter name=dir / --lora-slots: the adapter "
                     "stacks cover the attention and MLP projections "
                     "of one block kind, not the DeltaNet mixer's",
    },
    "tp": {
        "recurrent": "--tp: the sharded engine has no rules for the "
                     "DeltaNet leaves or the recurrent state",
        "ring": "--tp: the sharded engine has no sharding rule for "
                "the window layers' ring",
    },
    "spec_tokens": {
        "recurrent": "--spec-tokens: rejecting drafted tokens needs "
                     "the recurrent state rolled back to the last "
                     "accepted one, and a verify step keeps only the "
                     "state after all of them",
        "ring": "--spec-tokens: a drafted token's row overwrites the "
                "ring's oldest row, which the step after a rejected "
                "draft still needs; a ring cannot be rolled back",
    },
    "pd": {
        "recurrent": "--disaggregation-mode: the PD transfer ships KV "
                     "rows only",
        "ring": "--disaggregation-mode: the PD transfer ships "
                "full-length KV rows only, not a ring",
    },
    "journal": {
        "recurrent": "--journal: resume re-enters requests through "
                     "the prefix-seeded path, which has no recurrent "
                     "state to seed",
        "ring": "--journal: resume re-enters requests through the "
                "prefix-seeded path, which has no ring to seed",
    },
}


def slot_state_kind(cfg: ModelConfig) -> Optional[str]:
    """Which kind of state beside full-length KV rows a slot of this
    model owns (a key of SLOT_STATE_KINDS), or None."""
    if cfg.is_hybrid:
        return "recurrent"
    return "ring" if cfg.window_layers else None


def slot_state_refusals(cfg: ModelConfig, **asked) -> List[str]:
    """Reasons, for the features in `asked` that are truthy, why this
    model cannot be served with them; [] for a model whose slots own
    full-length KV rows and nothing else."""
    kind = slot_state_kind(cfg)
    return [SLOT_STATE_REFUSALS[k][kind] for k, v in asked.items()
            if v and kind in SLOT_STATE_REFUSALS[k]]


def prefill_attn_block_kinds(cfg: ModelConfig, rows: int, kv_rows: int,
                             base: int = 0, valid: Optional[int] = None
                             ) -> Dict[str, int]:
    """Grid steps by kind (ops/flash.py: none / whole / edge) of the
    attention kernel calls in ONE prefill of `rows` prompt rows, the
    first `valid` of them real (None: all; the rest a bucket's
    padding), at positions `base` on over `kv_rows` cache rows: summed
    over the model's layers by their window, or over a latent model's
    layers and the groups of heads its prompt materialises at a time
    (`latent_prefill`'s steps, each a group of heads of its own); all
    zero where the prompt takes XLA's attention (off the chip, or
    float32 logits under `_XLA_PREFILL_CAP`) or the kernel declines.
    Host arithmetic on shapes: nothing is read from the device."""
    total = {"none": 0, "whole": 0, "edge": 0}
    if cfg.attn_sinks:           # sinks take XLA's attention
        return total
    calls = [(layers, prefill_block_kinds(
        rows, kv_rows, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        base, window, valid)) for window, layers in cfg.attn_layer_windows]
    if cfg.mla:
        G = mla._prefill_head_group(kv_rows, cfg.num_heads,
                                    cfg.qk_nope_head_dim)
        calls.append((cfg.num_layers * (cfg.num_heads // G),
                      latent_prefill_block_kinds(
                          rows, kv_rows, G, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim, cfg.v_head_dim, base,
                          valid)))
    for n_calls, kinds in calls:
        for kind, n in (kinds or {}).items():
            total[kind] += n_calls * n
    return total


class InferenceEngine:
    """Compiled prefill/insert/decode over one model + one mesh."""

    # multi-token device decode (decode_multi) is available: wrappers
    # that delegate per-attribute (ReplicatedEngine) override this to
    # False so the scheduler degrades to K=1 instead of dispatching a
    # program their op stream cannot replicate
    supports_multi_step = True

    def __init__(self, params: Params, cfg: ModelConfig,
                 max_slots: int = 8, max_seq: Optional[int] = None,
                 prefill_buckets: Optional[List[int]] = None,
                 prefix_cache_bytes: int = 0,
                 prefix_host_bytes: int = 0,
                 lora_slots: int = 0, lora_rank: int = 16,
                 kv_block: int = 0, kv_blocks: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 mask_table_rows: int = 64,
                 ledger=None):
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq or cfg.max_seq_len
        # rows of a window layer's ring (0: the model has none)
        self.ring_rows = llama.ring_rows(cfg, self.max_seq) \
            if cfg.window_layers else 0
        # paged KV (kv_block > 0): the decode cache is a POOL of
        # `kv_blocks` fixed-size blocks + a per-slot block table
        # instead of the dense [L, B, Smax, ...] worst-case slab —
        # HBM sized by tokens in flight, so the same budget serves
        # more slots with mixed-length sequences (vLLM/SGLang
        # PagedAttention, TPU-static: ops/paged.py; r4 verdict #2)
        self.kv_block = int(kv_block)
        # how KV rows lie, decided here once from what reads them
        # (llama.KVCache): the slab, which `flash_decode` reads, and
        # the prefills that fill it have a row's heads merged in the
        # lanes; a paged engine's prefill keeps [L, 1, bucket, K, D],
        # which is what `_insert_paged` and the pool's blocks take
        self.kv_rows_merged = not self.kv_block
        refused = slot_state_refusals(
            cfg, kv_block=kv_block,
            prefix_cache=prefix_cache_bytes or prefix_host_bytes,
            lora=lora_slots)
        if refused:
            raise ValueError(
                SLOT_STATE_KINDS[slot_state_kind(cfg)] + "; refused: "
                + "; ".join(refused))
        # int8-quantized paged pools (--kv-dtype int8): KV rows are
        # stored as int8 + a per-(row, head) f32 scale plane, halving
        # block-pool HBM per cached token — the same budget holds ~2x
        # the sequences (docs/kv-hierarchy.md). Quantization happens
        # on append inside the compiled decode/insert programs;
        # dequantization inside the paged attention kernel.
        kv_dtype = (kv_dtype or "").replace("bfloat16", "bf16")
        if kv_dtype not in ("", "bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be bf16 or int8, got {kv_dtype!r}")
        self.kv_quantized = kv_dtype == "int8"
        if self.kv_quantized and not self.kv_block:
            raise ValueError(
                "--kv-dtype int8 quantizes the paged block pool; "
                "enable paged KV (--kv-block) to use it")
        if self.kv_block:
            outside = [what for what, has in (
                ("latent attention (MLA)", cfg.mla),
                ("sparse experts", cfg.is_moe),
                ("leading dense layers", cfg.first_k_dense),
                ("a sliding window", cfg.sliding_window
                 or cfg.alt_sliding_window),
                (f"{cfg.norm_type} norms", cfg.norm_type != "rmsnorm"),
                ("a parallel block", cfg.parallel_block),
                ("attention sinks", cfg.attn_sinks)) if has]
            if outside:
                # (a hybrid or ring model was refused above, with the
                # reason its state gives)
                raise ValueError(
                    "paged KV (--kv-block) serves standard rmsnorm GQA "
                    "models through llama.forward_paged, which has no "
                    f"path for {', '.join(outside)}; this model has: "
                    "drop --kv-block / --kv-blocks to serve it on the "
                    "dense cache")
            if device.on_tpu() and (
                    self.kv_block % 128 or cfg.head_dim % 128
                    or cfg.num_heads < 8):
                # outside the Pallas kernel's coverage every layer
                # would silently fall back to the XLA gather, which
                # materializes the dense-equivalent KV per step —
                # defeating the feature; refuse loudly instead
                raise ValueError(
                    f"paged KV on TPU needs --kv-block % 128 == 0, "
                    f"head_dim % 128 == 0 and >= 8 heads for the "
                    f"Pallas kernel (got kv_block={self.kv_block}, "
                    f"head_dim={cfg.head_dim}, heads={cfg.num_heads})")
            self.max_blocks = -(-self.max_seq // self.kv_block)
            # default pool = dense-equivalent capacity (+1: block
            # TRASH_BLOCK is reserved, never allocated, never read —
            # the attention kernel ends a slot's walk at a row that
            # starts there, ops/paged.py)
            self.kv_blocks = kv_blocks or (
                max_slots * self.max_blocks + 1)
            self._table = np.full((max_slots, self.max_blocks),
                                  TRASH_BLOCK, np.int32)
            self._owned: List[List[int]] = [[] for _ in
                                            range(max_slots)]
            self._free_blocks = list(range(self.kv_blocks - 1,
                                           TRASH_BLOCK, -1))
            self._host_len = np.zeros(max_slots, np.int64)
            self._preempted: List[int] = []
            # device-resident copy of the block table, re-uploaded
            # only when the host table actually changed (insert /
            # free_slot / a _grow_blocks block append) — most decode
            # steps append no block, so they reuse the previous upload
            self._table_dirty = True
            self._table_dev: Optional[jax.Array] = None
        if prefill_buckets is None:
            prefill_buckets, b = [], 64
            while b < self.max_seq:
                prefill_buckets.append(b)
                b *= 2
            prefill_buckets.append(self.max_seq)
        self.prefill_buckets = prefill_buckets
        # an expert layer that holds a share of its experts counts
        # what it is hit by (llama.ragged_experts), on the device
        self._counts_experts = llama.counts_experts(cfg)
        self._moe_stats_dev: Optional[jax.Array] = None
        self._moe_seen = [0, 0, 0]
        self._moe_totals = {"layer_steps": 0, "experts_hit": 0,
                            "pairs": 0}
        import threading
        self._moe_lock = threading.Lock()
        # grid steps of the prefill attention kernel by kind, summed
        # over the prefills run (`_count_attn_blocks`); plain ints the
        # scheduler mirrors at scrape, as it does the prefix cache's
        self.prefill_attn_blocks = {"none": 0, "whole": 0, "edge": 0}
        self.prefix_cache = PrefixCache(
            prefix_cache_bytes,
            host_capacity_bytes=prefix_host_bytes,
            merged_rows=self.kv_rows_merged)

        # multi-LoRA serving: preallocate `lora_slots` zeroed factor
        # stacks as extra scanned layer leaves ([L, slots+1, r, K]).
        # Slot 0 is the all-zero base; register_adapter hot-writes a
        # slot IN PLACE of the zeros — shapes never change, so no
        # recompilation on adapter load (the punica idea, TPU-shaped).
        self.lora_slots = lora_slots
        self.lora_rank = lora_rank
        self._lora_names: Dict[str, int] = {}
        # which adapter id each DECODE slot currently decodes with —
        # unregister_adapter refuses while any slot references it
        # (r4 advisor: a freed slot id reused mid-stream silently
        # flips in-flight sequences to another adapter)
        self._slot_adapters = np.zeros(max_slots, np.int32)
        import threading as _threading
        self._lora_lock = _threading.Lock()
        if lora_slots > 0:
            if cfg.is_moe and cfg.first_k_dense:
                raise ValueError("multi-LoRA does not support "
                                 "first_k_dense models yet")
            from ..models.lora import _target_dims
            layers = dict(params["layers"])
            n, r, L = lora_slots + 1, lora_rank, cfg.num_layers
            for leaf, (K, N) in _target_dims(cfg).items():
                if leaf not in layers:
                    continue  # MoE models: attention targets only
                layers[leaf + "_lora_a"] = jnp.zeros((L, n, r, K),
                                                     cfg.dtype)
                layers[leaf + "_lora_b"] = jnp.zeros((L, n, r, N),
                                                     cfg.dtype)
            self.params = dict(params, layers=layers)

        cfg_ = cfg
        merged = self.kv_rows_merged

        @scoped("prefill")
        def _prefill(params, padded: jax.Array, true_len: jax.Array,
                     temperature, top_k, top_p, key, *mask_adapter,
                     bucket: int):
            """Bucketed prefill of one prompt. `mask_adapter` is the
            adapter id, after a [1, V] mask of the dense kind where
            the FIRST sampled token honors a structured-output
            grammar."""
            *mask, adapter = mask_adapter
            cache = llama.KVCache.create(cfg_, 1, bucket, merged=merged)
            # last REAL token's logits only (right padding occupies
            # the tail): the head runs on that one row. KV rows hide
            # the padded tail behind the slot's length; a hybrid
            # model's recurrent state is handed back as it stood at
            # true_len (valid_len)
            logits, new_cache = llama.forward(params, cfg_, padded,
                                              cache=cache,
                                              adapter_ids=adapter,
                                              logits_at=true_len - 1,
                                              valid_len=true_len)
            tok = sample(_masked(logits[:, 0], _mask_bits(mask)), key,
                         temperature, top_k, top_p)
            # a slot's other state rides third, as insert takes it
            other = new_cache.rec if cfg_.is_hybrid else (
                {"wk": new_cache.wk, "wv": new_cache.wv}
                if cfg_.window_layers else None)
            return (tok[0], new_cache.k, new_cache.v) + (
                () if other is None else (other,))

        @functools.partial(jax.jit,
                           static_argnames=("total_bucket", "keep"))
        @scoped("prefill")
        def _prefill_suffix(params, prefix_k, prefix_v,
                            prefix_len: jax.Array, padded: jax.Array,
                            suffix_len: jax.Array, temperature, top_k,
                            top_p, key, total_bucket: int, keep: int):
            """Chunked prefill atop a cached prefix: seed a
            total_bucket cache with the prefix KV, run only the suffix
            (positions continue at prefix_len). Rows past the valid
            lengths hold stale data — kv_len masking makes them
            unreachable."""
            shapes = llama.kv_rows_shapes(
                cfg_, (cfg_.num_layers, 1, total_bucket), merged)
            k0, v0 = (
                lax.dynamic_update_slice(
                    jnp.zeros(shape, cfg_.dtype), prefix[:, :, :keep],
                    (0,) * len(shape))
                for shape, prefix in zip(shapes, (prefix_k, prefix_v)))
            cache = llama.KVCache(k=k0, v=v0, index=prefix_len)
            logits, new_cache = llama.forward(params, cfg_, padded,
                                              cache=cache,
                                              logits_at=suffix_len - 1,
                                              valid_len=suffix_len)
            tok = sample(logits[:, 0], key, temperature, top_k, top_p)
            # (suffix prefill stays base-model-only: adapter requests
            # bypass the prefix cache — their KV depends on the
            # adapter, so shared-prefix reuse would be wrong)
            return tok[0], new_cache.k, new_cache.v

        @functools.partial(jax.jit, donate_argnums=(0,),
                           static_argnames=("bucket",))
        @scoped("insert")
        def _insert(state: DecodeState, kv_k, kv_v, slot: jax.Array,
                    true_len: jax.Array, token: jax.Array,
                    adapter: jax.Array, other=None, *, bucket: int):
            def into(whole, one):
                # the slot's share, [Ll, 1, ...], into batch row
                # `slot` of [Ll, B, ...], laid as the state's rows lie
                # (KV off the wire arrives heads apart, engine/pd.py);
                # rows of a bucket wider than the state's are cut to
                # it (no position past max_seq is ever reached)
                one = one[:, :, :whole.shape[2]].astype(whole.dtype)
                return lax.dynamic_update_slice(
                    whole, one.reshape(one.shape[:3] + whole.shape[3:]),
                    (0, slot) + (0,) * (whole.ndim - 2))

            k, v = into(state.k, kv_k), into(state.v, kv_v)
            grown = {}
            if cfg_.is_hybrid:
                # the recurrent state at true_len, whole
                grown["rec"] = jax.tree.map(into, state.rec, other)
            elif cfg_.window_layers:
                # the ring as the prompt left it: position p of its
                # last `sliding_window` in row p % W already
                grown = {n: into(getattr(state, n), other[n])
                         for n in ("wk", "wv")}
            return dataclasses.replace(
                state, k=k, v=v,
                lengths=state.lengths.at[slot].set(true_len),
                tokens=state.tokens.at[slot].set(token),
                adapters=state.adapters.at[slot].set(adapter), **grown)

        kvb = self.kv_block
        kvq = self.kv_quantized

        @functools.partial(jax.jit, donate_argnums=(0,),
                           static_argnames=("bucket",))
        @scoped("insert")
        def _insert_paged(state: DecodeState, kv_k, kv_v,
                          block_ids: jax.Array, slot: jax.Array,
                          true_len: jax.Array, token: jax.Array,
                          adapter: jax.Array, bucket: int):
            """Scatter a prefilled [L, 1, bucket, K, D] KV slab into
            the pool blocks listed in `block_ids` (host-allocated;
            entries past the valid length point at the trash block).
            int8 pools quantize the slab per (layer, row, head) on the
            way in — prefill always computes at the model dtype, so
            the quantization cost rides the (rare) insert, never the
            decode loop."""
            k, v = state.k, state.v
            ksc, vsc = state.k_scale, state.v_scale
            if kvq:
                def quant(x):
                    amax = jnp.max(jnp.abs(x.astype(jnp.float32)),
                                   axis=-1)      # [L, 1, bucket, K]
                    s = jnp.maximum(amax, 1e-8) / 127.0
                    q = jnp.clip(
                        jnp.round(x.astype(jnp.float32)
                                  / s[..., None]),
                        -127, 127).astype(jnp.int8)
                    # scale slab S-minor: [L, 1, K, bucket]
                    return q, jnp.swapaxes(s, -1, -2)
                kv_k, ks = quant(kv_k)
                kv_v, vs = quant(kv_v)
            for i in range(-(-bucket // kvb)):
                ck = kv_k[:, 0, i * kvb:(i + 1) * kvb]
                cv = kv_v[:, 0, i * kvb:(i + 1) * kvb]
                k = lax.dynamic_update_slice(
                    k, ck[:, None], (0, block_ids[i], 0, 0, 0))
                v = lax.dynamic_update_slice(
                    v, cv[:, None], (0, block_ids[i], 0, 0, 0))
                if kvq:
                    csk = ks[:, :, :, i * kvb:(i + 1) * kvb]
                    csv = vs[:, :, :, i * kvb:(i + 1) * kvb]
                    ksc = lax.dynamic_update_slice(
                        ksc, csk, (0, block_ids[i], 0, 0))
                    vsc = lax.dynamic_update_slice(
                        vsc, csv, (0, block_ids[i], 0, 0))
            return dataclasses.replace(
                state, k=k, v=v,
                lengths=state.lengths.at[slot].set(true_len),
                tokens=state.tokens.at[slot].set(token),
                adapters=state.adapters.at[slot].set(adapter),
                k_scale=ksc, v_scale=vsc)

        def _forward(params, st: DecodeState, toks, table,
                     active=None):
            """The model over `toks` ([B, T]) for a decode state: the
            slab when there is no block `table`, else the paged pool
            read through it. `active` ([B] bool, the multi-step
            loop's) reaches a hybrid model's DeltaNet layers as the
            row's valid length, and a window layer's ring keeps a
            frozen slot's rows by it; full-length KV rows have no use
            for it."""
            if table is None:
                return llama.forward(
                    params, cfg_, toks, cache=_cache_of(st),
                    adapter_ids=st.adapters,
                    valid_len=(None if active is None
                               else active.astype(jnp.int32)))
            return llama.forward_paged(params, cfg_, toks,
                                       _cache_of(st, table),
                                       adapter_ids=st.adapters)

        def _decode_step(params, state: DecodeState, table,
                         temperature, top_k, top_p, key, *mask):
            """One token for every slot; `mask` is [B, V] bits or
            [B] rows of the mask table."""
            logits, nc = _forward(params, state,
                                  state.tokens[:, None], table)
            bits = _mask_bits(mask)
            toks = sample(_masked(logits[:, -1], bits), key,
                          temperature, top_k, top_p)
            new_state = _state_of(nc, toks, state)
            return (new_state, toks) + _counts_of(new_state)

        smax = self.max_seq

        def _multi_body(i, carry, key, temperature, top_k, top_p,
                        budget, stop_ids, forward_one, mask=None):
            """One fori_loop iteration of the multi-token decode
            program: forward the batch one position, sample on device,
            append KV, and feed the sampled token back as the next
            iteration's input. Per-slot freeze: a slot that sampled a
            stop-table token, spent its token budget, or reached cache
            capacity goes inactive — its token and length are held
            frozen (the re-written row sits past its committed length,
            so it is never readable), keeping every shape static.
            The freeze conditions are a conservative SUBSET of the
            host's finish rules: the device may run long (the host
            discards overshoot at the drain) but never stops a slot
            the host would have continued.

            `mask` ([B, n, V] bool, optional) constrains iteration i's
            sampling to mask[:, i] — the structured-output mask STACK a
            plan precomputed by walking each slot's grammar automaton
            through its forced token run (docs/step-plan.md). All-True
            rows leave a slot unconstrained."""
            st, done, acc, adv = carry
            active = (~done) & (i < budget) & (st.lengths < smax)
            # a frozen slot's recurrent state (hybrid models) must not
            # move either: `active` reaches the DeltaNet layers as the
            # row's valid length
            logits, nc = forward_one(st, active)
            last = _masked(logits[:, -1],
                           None if mask is None else mask[:, i])
            toks = sample(last, jax.random.fold_in(key, i),
                          temperature, top_k, top_p)
            toks = jnp.where(active, toks, st.tokens)
            done = done | jnp.any(toks[:, None] == stop_ids, axis=1)
            acc = acc.at[:, i].set(toks)
            adv = adv + active.astype(jnp.int32)
            st = _state_of(
                nc, toks, st,
                lengths=jnp.where(active, nc.index, st.lengths))
            return st, done, acc, adv

        def _multi_loop(state, key, temperature, top_k, top_p, budget,
                        stop_ids, forward_one, n: int, mask=None):
            B = state.tokens.shape[0]
            # a slot whose INPUT token is already a stop (the previous
            # chunk sampled it; the host finishes on every stop token)
            # freezes for the whole chunk instead of appending the
            # stop's KV and decoding past it
            done0 = (budget <= 0) | jnp.any(
                state.tokens[:, None] == stop_ids, axis=1)
            carry = (state, done0, jnp.zeros((B, n), jnp.int32),
                     jnp.zeros((B,), jnp.int32))
            state, _, acc, adv = lax.fori_loop(
                0, n, functools.partial(
                    _multi_body, key=key, temperature=temperature,
                    top_k=top_k, top_p=top_p, budget=budget,
                    stop_ids=stop_ids, forward_one=forward_one,
                    mask=mask),
                carry)
            return (state, acc, adv) + _counts_of(state)

        def _decode_chunk(params, state: DecodeState, table,
                          temperature, top_k, top_p, key, budget,
                          stop_ids, *mask, n: int):
            """n decode iterations inside ONE device program (ROADMAP
            item 2): a fori_loop over {forward → sample → KV append →
            next-token embed} with sampling fused as the loop epilogue
            (per-iteration keys folded from the chunk key), so the
            host syncs once per n tokens instead of once per token.
            budget: [B] int32 remaining-token cap per slot; stop_ids:
            [B, NS] int32 stop table (-1 padding); `mask`: [B, n, V]
            bits, one mask per iteration (structured outputs inside a
            fused chunk), or [B, n] rows of the mask table, gathered
            once before the loop. Returns (state, tokens [B, n],
            advanced [B]) — slot b's real output is
            tokens[b, :advanced[b]], the rest is frozen filler the
            host discards.

            Over the paged pool the block table is STATIC for the
            whole chunk: the host pre-allocates blocks covering every
            row the n iterations can write (_grow_blocks_spec, the
            spec-decode discipline) and commit_spec() reconciles
            lengths + returns the surplus once `advanced` is
            drained."""

            def forward_one(st, active):
                return _forward(params, st, st.tokens[:, None], table,
                                active)

            return _multi_loop(state, key, temperature, top_k, top_p,
                               budget, stop_ids, forward_one, n,
                               mask=_mask_bits(mask))

        def _verify_step(params, state: DecodeState, table, drafts,
                         draft_len, temperature, top_k, top_p, key,
                         *mask, k: int):
            """Speculative verify: one forward over [last_token,
            draft_0..draft_{k-1}] per slot scores all k+1 positions in
            a single weight pass. Draft K/V is written at the slot's
            cache index like any decode write; the ROLLBACK of
            rejected rows is just the per-slot index update below —
            rows past `lengths + accepted + 1` are unreachable
            (kv_len masking) and the next step overwrites them. Over
            the paged pool the engine pre-allocates blocks covering
            all k+1 speculative rows before dispatch
            (_grow_blocks_spec); commit_spec() returns the surplus to
            the pool after the accepted count is known."""
            toks = jnp.concatenate([state.tokens[:, None], drafts],
                                   axis=1)  # [B, k+1]
            logits, nc = _forward(params, state, toks, table)
            bits = _mask_bits(mask)
            if len(mask) == 1:
                # the dense kind is a [B, V] position-0 mask: masked
                # (structured-output) slots ride a verify plan at
                # draft_len 0 — their single sampled token honors the
                # grammar mask while drafting slots verify normally
                # (masked rows never draft, so positions past 0 are
                # only reached by unmasked slots). All-True rows are
                # a no-op.
                logits = logits.at[:, 0].set(
                    _masked(logits[:, 0], bits))
            else:
                # the index kind ([B, k+1] rows) masks ALL k+1
                # positions, because grammar-constrained slots then
                # DRAFT (spec-through-grammar): the token emitted at a
                # rejection position comes from that position's target
                # logits, which must honor that position's mask.
                # Unmasked slots point every position at row 0.
                logits = _masked(logits, bits)
            out, accepted = spec_verify(logits, drafts, draft_len, key,
                                        temperature, top_k, top_p)
            new_tok = jnp.take_along_axis(out, accepted[:, None],
                                          axis=1)[:, 0]
            return _state_of(
                nc, new_tok, state,
                lengths=state.lengths + accepted + 1), out, accepted

        # -- device-resident grammar mask table (docs/structured-
        # outputs.md): cached automaton-state masks live as rows of a
        # [S, V] device buffer; the *_idx programs gather each slot's
        # row in-program from int32 state indices, so a masked step
        # ships K ints per slot instead of K*V mask bools. Row 0 is
        # reserved all-True (the unmasked sentinel every idx array
        # defaults to); set_mask_row() refuses to write it.

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _mask_row_set(tab, row, bits):
            return tab.at[row].set(bits)

        def _jit_as(name: str, fn, **jit_kw):
            """`fn` jitted as `jit__<name>`, the name a trace, the
            benchmark and the ledger know the program by."""
            @functools.wraps(fn)
            def program(*args, **kw):
                return fn(*args, **kw)
            program.__name__ = program.__qualname__ = "_" + name
            return jax.jit(program, **jit_kw)

        def _over_slab(body):
            def slab(params, state, *rest, **kw):
                return body(params, state, None, *rest, **kw)
            return slab

        self._prefill_fn = _jit_as("prefill", _prefill,
                                   static_argnames=("bucket",))
        self._prefill_masked_fn = _jit_as("prefill_masked", _prefill,
                                          static_argnames=("bucket",))
        self._prefill_suffix_fn = _prefill_suffix
        self._insert_fn = _insert
        self._insert_paged_fn = _insert_paged
        self._mask_row_fn = _mask_row_set
        # the decode and verify programs by the name the ledger and
        # /debug/programs give them: family + mask kind, + "_paged"
        # over the pool. One body per family, under the family's
        # scope; a paged program takes the block table after the
        # state, a slab program takes none; the state is donated
        self.programs = {}
        for family, body, scope, static in (
                ("decode", _decode_step, "decode", ()),
                ("decode_multi", _decode_chunk, "decode", ("n",)),
                ("verify", _verify_step, "verify", ("k",))):
            body = scoped(scope)(body)
            slab = _over_slab(body)
            for kind in MASK_KINDS:
                for name, fn in ((family + kind, slab),
                                 (family + kind + "_paged", body)):
                    self.programs[name] = _jit_as(
                        name, fn, donate_argnums=(1,),
                        static_argnames=static)
        self.mask_table_rows = int(mask_table_rows)
        self._mask_table_dev = None  # lazy: [rows, V] bool, row 0 True
        self._step = 0
        self._root_key = jax.random.PRNGKey(0)
        # prefill (admission thread) and decode (scheduler thread) both
        # draw keys; the counter bump must be atomic for distinct keys
        import threading
        self._rng_lock = threading.Lock()
        # optional policy hook: the scheduler ranks preemption victims
        # (priority class, quota overage); None = least progress only
        self._preempt_rank_fn = None
        # the slot whose block growth triggered the current preemption
        # scan; excluded from victim candidates while alternatives
        # exist (a near-pool-size batch request must not livelock as
        # its own repeated victim)
        self._growing_slot: Optional[int] = None
        # program cost ledger (perf/ledger.py): every dispatch below
        # routes through _ledger_capture so each compiled program gets
        # one cost entry; the default is mode "auto" (introspect on
        # TPU, analytic model elsewhere)
        if ledger is None:
            from ..perf.ledger import ProgramLedger
            ledger = ProgramLedger()
        self.ledger = ledger
        if ledger.mode != "off":
            # an accelerator with no published peaks fails the engine
            # here, at start, not inside a swallowed capture
            ledger.device_spec()
        self._weight_bytes: Optional[int] = None
        self._param_count: Optional[int] = None

    # -- cost model (perf ledger fallback) -----------------------------

    def kv_row_bytes(self) -> int:
        """HBM bytes one cached KV row (all layers, all heads) costs —
        the single per-token byte model shared by the cost ledger and
        the HbmAccountant kv_cache tenant (perf/hbm.py) so they can't
        drift. int8 pools store 1 byte/element plus two f32 scales per
        (layer, head) row."""
        cfg = self.cfg
        if getattr(self, "kv_quantized", False):
            return cfg.kv_cache_layers * cfg.kv_cache_heads * (
                cfg.kv_cache_k_dim + cfg.kv_cache_v_dim + 2 * 4)
        return (cfg.kv_cache_layers * cfg.kv_cache_heads
                * (cfg.kv_cache_k_dim + cfg.kv_cache_v_dim)
                * jnp.dtype(cfg.dtype).itemsize)

    def ring_bytes(self) -> int:
        """HBM bytes of the window layers' rings of all slots together
        (a periodic window / global model); 0 for every other model.
        `kv_row_bytes` counts the global layers' full-length rows."""
        cfg = self.cfg
        return (cfg.window_layers * self.max_slots * self.ring_rows
                * cfg.kv_cache_heads
                * (cfg.kv_cache_k_dim + cfg.kv_cache_v_dim)
                * jnp.dtype(cfg.dtype).itemsize)

    def state_bytes(self) -> int:
        """HBM bytes of the recurrent state all slots hold together
        (a hybrid model's DeltaNet layers: the float32 state matrices
        and the conv tails); 0 for every other model. The second
        tenant beside `kv_row_bytes` (perf/hbm.py)."""
        cfg = self.cfg
        if not cfg.is_hybrid:
            return 0
        per_slot = cfg.linear_layers * (
            cfg.linear_num_value_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim * 4
            + (cfg.linear_conv_kernel - 1) * cfg.linear_conv_dim
            * jnp.dtype(cfg.dtype).itemsize)
        return self.max_slots * per_slot

    def _cost_model(self, tokens: int, kv_rows: int,
                    weight_passes: int = 1) -> Dict[str, float]:
        """Analytic {flops, bytes} for a program moving the whole
        weight set `weight_passes` times while processing `tokens`
        positions against `kv_rows` cached KV rows — the ledger's
        estimate when compiler introspection is unavailable. Shares
        the quantizer's byte model so ledger and checkpoint-size
        accounting can't drift."""
        if self._weight_bytes is None:
            from ..models.quant import quantized_bytes
            self._weight_bytes = quantized_bytes(self.params)
            self._param_count = sum(
                int(leaf.size) for leaf in jax.tree_util.tree_leaves(
                    self.params))
        row = self.kv_row_bytes()
        return {
            "bytes": float(weight_passes * self._weight_bytes
                           + kv_rows * row),
            "flops": 2.0 * self._param_count * max(tokens, 1),
        }

    def _kv_capacity_rows(self) -> int:
        """KV rows the decode cache can address — the bytes a decode
        step's attention streams in the worst case."""
        if self.kv_block:
            return self.kv_blocks * self.kv_block
        return self.max_slots * self.max_seq

    def _ledger_capture(self, name: str, static_desc: str, fn, args,
                        static_kwargs, *, tokens: int, kv_rows: int,
                        weight_passes: int = 1) -> None:
        """Record the program about to be dispatched. Never raises:
        observability must not take down a decode step."""
        led = self.ledger
        if led is None or led.mode == "off":
            return
        try:
            led.capture(name, static_desc, fn, args, static_kwargs,
                        self._cost_model(tokens, kv_rows,
                                         weight_passes))
        except Exception:  # pragma: no cover - defensive
            log.debug("ledger capture failed for %s", name,
                      exc_info=True)

    def _next_key(self):
        with self._rng_lock:
            self._step += 1
            return jax.random.fold_in(self._root_key, self._step)

    # -- state ---------------------------------------------------------

    def new_state(self) -> DecodeState:
        cfg = self.cfg
        L, B, S = cfg.kv_cache_layers, self.max_slots, self.max_seq
        if self.kv_block:
            # pool-shaped k/v; the block table stays host-side and is
            # passed to the decode program each step (tiny int32)
            self._table[:] = TRASH_BLOCK
            self._owned = [[] for _ in range(B)]
            self._free_blocks = list(range(self.kv_blocks - 1,
                                           TRASH_BLOCK, -1))
            self._host_len[:] = 0
            self._preempted = []
            self._table_dirty = True
            self._table_dev = None
            pool = (L, self.kv_blocks, self.kv_block,
                    cfg.kv_cache_heads)
            pool_dtype = jnp.int8 if self.kv_quantized else cfg.dtype
            # distinct scale buffers: the jitted programs donate the
            # whole state, and XLA refuses aliased donated arguments
            scale_shape = (L, self.kv_blocks, cfg.kv_cache_heads,
                           self.kv_block)
            return DecodeState(
                k=jnp.zeros(pool + (cfg.kv_cache_k_dim,), pool_dtype),
                v=jnp.zeros(pool + (cfg.kv_cache_v_dim,), pool_dtype),
                lengths=jnp.zeros((B,), jnp.int32),
                tokens=jnp.zeros((B,), jnp.int32),
                adapters=jnp.zeros((B,), jnp.int32),
                k_scale=(jnp.zeros(scale_shape, jnp.float32)
                         if self.kv_quantized else None),
                v_scale=(jnp.zeros(scale_shape, jnp.float32)
                         if self.kv_quantized else None))
        ks, vs = llama.kv_rows_shapes(cfg, (L, B, S),
                                      self.kv_rows_merged)
        return DecodeState(
            k=jnp.zeros(ks, cfg.dtype), v=jnp.zeros(vs, cfg.dtype),
            lengths=jnp.zeros((B,), jnp.int32),
            tokens=jnp.zeros((B,), jnp.int32),
            adapters=jnp.zeros((B,), jnp.int32),
            rec=llama.recurrent_state(cfg, B),
            moe_stats=(jnp.zeros((3,), jnp.uint32)
                       if self._counts_experts else None),
            **self._new_ring())

    def _new_ring(self) -> dict:
        """The window layers' zeroed rings, {} for a model with none."""
        cfg = self.cfg
        if not cfg.window_layers:
            return {}
        ks, vs = llama.kv_rows_shapes(
            cfg, (cfg.window_layers, self.max_slots, self.ring_rows),
            self.kv_rows_merged)
        return {"wk": jnp.zeros(ks, cfg.dtype),
                "wv": jnp.zeros(vs, cfg.dtype)}

    def _take_counts(self, outs: tuple) -> tuple:
        """Split off the expert counters that a counting engine's
        dense decode programs return last (`_counts_of`)."""
        if self._counts_experts:
            *outs, self._moe_stats_dev = outs
        return tuple(outs)

    def moe_counters(self) -> Optional[Dict[str, int]]:
        """Totals of the expert layers' device counters since start:
        layer-steps run by decode programs, held experts hit, routed
        pairs that landed on a held expert. None for a model that
        does not count. Reads the counters the LAST dispatched decode
        step returned (a fresh output, never donated), so it waits
        for that step at most and costs the step path nothing; call
        it from a scrape, not from the decode loop. The device adds
        in uint32 and the totals here follow its wrap-around, which
        holds as long as two reads are under 2**32 pairs apart
        (hours of decoding)."""
        if not self._counts_experts:
            return None
        dev = self._moe_stats_dev
        if dev is not None:
            now = [int(x) for x in np.asarray(dev)]  # may wait a step
            with self._moe_lock:
                grown = [(n - seen) % (1 << 32)
                         for n, seen in zip(now, self._moe_seen)]
                # a reading older than the last one applied (two
                # scrapes at once) would look like a wrap: drop it
                if max(grown) < (1 << 31):
                    for name, d in zip(self._moe_totals, grown):
                        self._moe_totals[name] += d
                    self._moe_seen = now
        return dict(self._moe_totals)

    # -- paged-pool block allocator ------------------------------------

    def free_slot(self, slot: int) -> None:
        """Release a finished slot: its adapter reference always, its
        KV blocks in paged mode (the scheduler calls this; insert()
        also frees implicitly on slot reuse)."""
        self._slot_adapters[slot] = 0
        if not self.kv_block:
            return
        self._free_blocks.extend(reversed(self._owned[slot]))
        self._owned[slot] = []
        # the whole row: the kernel reads entry 0 to know the chain is
        # empty, whatever the device's length still says
        self._table[slot] = TRASH_BLOCK
        self._table_dirty = True
        self._host_len[slot] = 0

    def take_preempted(self) -> List[int]:
        """Slots whose sequences were evicted by pool pressure since
        the last call; the scheduler requeues their requests (their
        generated-so-far tokens become part of the re-prefill
        prompt)."""
        if not self.kv_block:
            return []
        out, self._preempted = list(self._preempted), []
        return out

    def set_preempt_rank(self, fn) -> None:
        """Install a victim-ranking hook: fn(slot) -> sortable key,
        lower = preempt first. The scheduler uses it to rank by
        (quota overage, priority class); ties and the no-hook case
        fall back to least progress (cheapest to re-prefill)."""
        self._preempt_rank_fn = fn

    def _preempt_victim(self) -> bool:
        """Free the blocks of one active sequence to relieve pool
        pressure; False when none remain. Victim order: the installed
        rank hook first (class-aware), then least progress. The slot
        whose growth started the scan (`_growing_slot`) is only
        eligible when it is the sole candidate — otherwise a request
        near pool size could repeatedly evict itself (livelock)."""
        cands = [b for b in range(self.max_slots)
                 if self._owned[b] and b not in self._preempted]
        if not cands:
            return False
        if (self._growing_slot in cands and len(cands) > 1):
            cands = [b for b in cands if b != self._growing_slot]
        rank = self._preempt_rank_fn
        if rank is not None:
            victim = min(cands, key=lambda b: (rank(b),
                                               int(self._host_len[b])))
        else:
            victim = min(cands, key=lambda b: int(self._host_len[b]))
        self._preempted.append(victim)
        self.free_slot(victim)
        return True

    def _grow_blocks(self) -> None:
        """Pre-allocate the block each active slot's NEXT write needs
        (called before every paged decode step, which writes at
        index = length). Pool pressure preempts victims instead of
        failing the node (vLLM-style recompute preemption)."""
        for b in range(self.max_slots):
            if not self._owned[b]:
                continue
            w = int(self._host_len[b])
            if w >= self.max_seq:
                continue
            j = w // self.kv_block
            if j >= len(self._owned[b]) and j < self.max_blocks:
                self._growing_slot = b
                while not self._free_blocks:
                    if not self._preempt_victim():
                        break
                self._growing_slot = None
                if not self._owned[b]:
                    continue  # b itself was the victim
                if not self._free_blocks:
                    # nothing evictable and no block for b's next
                    # write: preempt b EXPLICITLY rather than letting
                    # its writes land in the trash block (a host/
                    # device length desync a future allocator change
                    # could silently re-enable). Defensively
                    # unreachable today — any _preempt_victim success
                    # above frees blocks — but cheap to keep honest.
                    self._preempted.append(b)
                    self.free_slot(b)
                    continue
                nid = self._free_blocks.pop()
                self._owned[b].append(nid)
                self._table[b, j] = nid
                self._table_dirty = True
            self._host_len[b] = w + 1  # mirror of the device +1

    def _grow_blocks_spec(self, rows: int) -> None:
        """Pre-allocate blocks covering each active slot's next `rows`
        writes (a verify step writes k+1 speculative rows at once) —
        WITHOUT advancing the host length mirror: how far the device
        actually advanced is only known after the accepted counts are
        drained, when commit_spec() reconciles and returns the
        surplus. Pool pressure preempts victims exactly like
        _grow_blocks."""
        for b in range(self.max_slots):
            if not self._owned[b]:
                continue
            w = int(self._host_len[b])
            top = min(w + rows, self.max_seq)  # write rows [w, top)
            need = min(-(-top // self.kv_block), self.max_blocks)
            while len(self._owned[b]) < need:
                j = len(self._owned[b])
                self._growing_slot = b
                while not self._free_blocks:
                    if not self._preempt_victim():
                        break
                self._growing_slot = None
                if not self._owned[b]:
                    break  # b itself was the victim
                if not self._free_blocks:
                    # same honesty guard as _grow_blocks: never let a
                    # live slot write into the trash block
                    self._preempted.append(b)
                    self.free_slot(b)
                    break
                nid = self._free_blocks.pop()
                self._owned[b].append(nid)
                self._table[b, j] = nid
                self._table_dirty = True

    def commit_spec(self, slot: int, advance: int,
                    reserve: int = 0) -> None:
        """Reconcile a slot's host length mirror after a drained
        verify (or multi-token decode) step advanced its device
        length by `advance`, and return speculatively-allocated
        blocks past the new length to the pool — the paged-KV
        rollback of rejected draft rows. `reserve` keeps blocks
        covering that many rows PAST the new length allocated:
        under chunk pipelining, later chunks already dispatched will
        write rows [len, len+reserve) — trimming those blocks here
        would let an insert re-allocate them before the in-flight
        writes execute."""
        if not self.kv_block or not self._owned[slot]:
            return
        self._host_len[slot] = min(
            int(self._host_len[slot]) + advance, self.max_seq)
        need = self.blocks_needed(min(
            int(self._host_len[slot]) + max(int(reserve), 0),
            self.max_seq))
        while len(self._owned[slot]) > need:
            nid = self._owned[slot].pop()
            self._table[slot, len(self._owned[slot])] = TRASH_BLOCK
            self._free_blocks.append(nid)
            self._table_dirty = True

    @property
    def kv_pool_stats(self) -> Dict[str, int]:
        return {"kv_blocks": getattr(self, "kv_blocks", 0),
                "kv_blocks_free": len(getattr(self, "_free_blocks",
                                              ())),
                "kv_block_tokens": self.kv_block}

    def kv_conservation(self) -> Tuple[bool, int]:
        """Block-pool conservation check (the PagedAttention
        discipline): free + owned must account for every allocatable
        block (kv_blocks − 1; block 0 is the reserved trash block), no
        block may appear twice, block 0 may never be owned, and the
        device block table must mirror the host owned lists. Returns
        (ok, owned_count). Authoritative at quiescence — the chaos
        harness asserts it between episodes; a concurrent insert can
        make a mid-step scrape read False transiently."""
        if not self.kv_block:
            return True, 0
        free = list(self._free_blocks)
        owned_all: List[int] = []
        for slot in range(self.max_slots):
            owned = [int(b) for b in self._owned[slot]]
            owned_all.extend(owned)
            row = [int(x) for x in
                   np.asarray(self._table[slot, :len(owned)])]
            if row != owned:
                return False, len(owned_all)
        blocks = [int(b) for b in free] + owned_all
        ok = (len(blocks) == self.kv_blocks - 1
              and len(set(blocks)) == len(blocks)
              and TRASH_BLOCK not in blocks)
        # hierarchical-KV extension: the prefix cache's two tiers
        # must also account exactly (device trie + host LRU sum, no
        # double residency) — one gauge covers the whole KV hierarchy
        tc = getattr(self.prefix_cache, "tier_conservation", None)
        if callable(tc):
            ok = ok and tc()[0]
        return ok, len(owned_all)

    # -- multi-LoRA registry -------------------------------------------

    @property
    def adapter_names(self) -> List[str]:
        return sorted(self._lora_names)

    def adapter_id(self, name: Optional[str]) -> int:
        """Resolve an adapter name to its slot id (0/None = base)."""
        if not name:
            return 0
        try:
            return self._lora_names[name]
        except KeyError:
            raise UnknownAdapterError(
                f"unknown adapter {name!r} (loaded: "
                f"{self.adapter_names or 'none'})")

    def register_adapter(self, name: str, adapter_dir: str) -> int:
        """Load a PEFT adapter dir into a free LoRA slot (hot, no
        recompilation: writes into the preallocated factor stacks).
        Re-registering a name overwrites its slot (adapter update)."""
        if self.lora_slots <= 0:
            raise ValueError("engine started without LoRA slots "
                             "(--lora-slots)")
        from ..models.lora import load_adapter_matrices
        mats = load_adapter_matrices(adapter_dir, self.cfg,
                                     rank_pad=self.lora_rank)
        with self._lora_lock:
            idx = self._lora_names.get(name)
            if idx is None:
                used = set(self._lora_names.values())
                free = [i for i in range(1, self.lora_slots + 1)
                        if i not in used]
                if not free:
                    raise ValueError(
                        f"all {self.lora_slots} LoRA slots in use")
                idx = free[0]
            layers = dict(self.params["layers"])
            for leaf, (A, B) in mats.items():
                ka, kb = leaf + "_lora_a", leaf + "_lora_b"
                if ka not in layers:
                    raise ValueError(f"model has no target {leaf}")
                layers[ka] = layers[ka].at[:, idx].set(
                    A.astype(self.cfg.dtype))
                layers[kb] = layers[kb].at[:, idx].set(
                    B.astype(self.cfg.dtype))
            # atomic reference swap: in-flight steps keep the old tree
            self.params = dict(self.params, layers=layers)
            self._lora_names[name] = idx
        return idx

    def unregister_adapter(self, name: str) -> None:
        with self._lora_lock:
            idx = self._lora_names.get(name)
            if idx is None:
                return
            if (self._slot_adapters == idx).any():
                raise ValueError(
                    f"adapter {name!r} is decoding in-flight "
                    f"sequences; retry after they finish")
            self._lora_names.pop(name)
            layers = dict(self.params["layers"])
            for key in list(layers):
                if key.endswith("_lora_a") or key.endswith("_lora_b"):
                    layers[key] = layers[key].at[:, idx].set(0.0)
            self.params = dict(self.params, layers=layers)

    # -- ops -----------------------------------------------------------

    def prefill(self, prompt_ids: List[int], temperature: float = 0.0,
                top_k: int = 0, top_p: float = 1.0,
                first_mask: Optional[np.ndarray] = None,
                adapter: Optional[str] = None):
        """Returns (first_token:int, kv pair, true_len, bucket).

        With a prefix cache enabled, a prompt whose leading tokens were
        prefetched by an earlier request runs only its suffix through
        the model (chunked prefill atop the cached KV). `first_mask`
        ([V] bool) constrains the first sampled token (structured
        outputs) and bypasses the prefix-cache suffix path (one shape
        fewer to compile; constrained prompts still seed the cache)."""
        # leave room for one generated token; cap at the largest bucket
        max_prompt = min(self.max_seq - 1, self.prefill_buckets[-1])
        ids = prompt_ids[-max_prompt:]
        if np.ndim(temperature) or np.ndim(top_k) or np.ndim(top_p):
            # a [1] array would reach the program as [1, 1] and fail
            # inside the sampling trace, far from its cause
            raise ValueError(
                "prefill serves one prompt: temperature, top_k and "
                "top_p are scalars (decode takes the per-slot arrays)")
        key = self._next_key()
        sampling = (np.asarray([temperature], np.float32),
                    np.asarray([top_k], np.int32),
                    np.asarray([top_p], np.float32))

        def _pow2_keep(plen: int) -> int:
            # quantize the reused prefix length to a power of two:
            # `keep` is a STATIC jit arg, so arbitrary block multiples
            # would compile a fresh _prefill_suffix program per length
            # (seconds each on TPU); powers of two bound the compile
            # space to ~log2(max_seq) x len(buckets) variants
            return 1 << (max(plen, 1).bit_length() - 1)

        def _usable(plen: int) -> bool:
            k = _pow2_keep(plen)
            # quantized prefix + bucketized suffix must fit the
            # largest bucket
            return (k >= self.prefix_cache.min_prefix
                    and k + _bucketize(len(ids) - k,
                                       self.prefill_buckets)
                    <= self.prefill_buckets[-1])

        aid = self.adapter_id(adapter)
        # adapter prefills bypass the prefix cache entirely: cached KV
        # was computed with (some) adapter's projections, so sharing
        # across adapters — or with the base — would be silently wrong
        hit = None if (first_mask is not None or aid != 0) \
            else self.prefix_cache.match(ids, usable=_usable)
        if hit is not None:
            pk, pv, plen, _pbucket = hit
            plen = _pow2_keep(plen)  # discard the ragged tail blocks
            # slice to the quantized length HOST-side: the arrays'
            # shapes are part of the jit compile key too
            pk, pv = pk[:, :, :plen], pv[:, :, :plen]
            suffix = ids[plen:]
            sbucket = _bucketize(len(suffix), self.prefill_buckets)
            bucket = _bucketize(plen + sbucket, self.prefill_buckets)
            padded = np.asarray(
                [suffix + [0] * (sbucket - len(suffix))], np.int32)
            args = (self.params, pk, pv, np.asarray(plen, np.int32),
                    padded, np.asarray([len(suffix)], np.int32),
                    *sampling, key)
            kw = dict(total_bucket=bucket, keep=min(plen, bucket))
            self._ledger_capture(
                "prefill_suffix", f"total={bucket},keep={kw['keep']}",
                self._prefill_suffix_fn, args, kw,
                tokens=sbucket, kv_rows=bucket)
            tok, k, v = self._prefill_suffix_fn(*args, **kw)
            rec = []
            self._count_attn_blocks(sbucket, bucket, plen, len(suffix))
        else:
            bucket = _bucketize(len(ids), self.prefill_buckets)
            padded = np.asarray(
                [ids + [0] * (bucket - len(ids))], np.int32)
            name, fn, mask = "prefill", self._prefill_fn, ()
            if first_mask is not None:
                name, fn = "prefill_masked", self._prefill_masked_fn
                mask = (np.asarray(first_mask, bool)[None, :],)
            args = (self.params, padded,
                    np.asarray([len(ids)], np.int32), *sampling, key,
                    *mask, np.asarray([aid], np.int32))
            self._ledger_capture(
                name, f"bucket={bucket}", fn, args,
                dict(bucket=bucket), tokens=bucket, kv_rows=bucket)
            tok, k, v, *rec = fn(*args, bucket=bucket)
            self._count_attn_blocks(bucket, bucket, 0, len(ids))
        if aid == 0:
            self.prefix_cache.put(ids, k, v, len(ids), bucket)
        # multi-host: int() on an array spanning non-addressable
        # devices raises; fetch the local replica instead
        from .multihost import host_value
        # a hybrid model's prefill hands its recurrent state at
        # true_len as a third element; insert() takes the tuple whole
        return int(host_value(tok)), (k, v, *rec), len(ids), bucket

    def _count_attn_blocks(self, rows: int, kv_rows: int, base: int,
                           valid: int):
        """A prefill's attention grid steps onto the tallies, by the
        prompt's true length `valid` (a quarter of a millisecond of
        host arithmetic, after the program's dispatch and before its
        token is waited for)."""
        kinds = prefill_attn_block_kinds(self.cfg, rows, kv_rows, base,
                                         valid)
        for kind, n in kinds.items():
            self.prefill_attn_blocks[kind] += n

    def blocks_needed(self, n_tokens: int) -> int:
        """Pool blocks covering `n_tokens` KV rows + the next write —
        the single accounting used by insert() AND the scheduler's
        pre-prefill pool check (they must not drift)."""
        return min(-(-(n_tokens + 1) // self.kv_block),
                   self.max_blocks)

    def insert(self, state: DecodeState, kv, slot: int, true_len: int,
               token: int, bucket: int,
               adapter: Optional[str] = None) -> DecodeState:
        if self.kv_block:
            with self._lora_lock:
                # fail fast BEFORE the allocator touches any blocks;
                # the dense path's only resolve is the locked one below
                self.adapter_id(adapter)
            bs = self.kv_block
            self.free_slot(slot)  # BEFORE recording the adapter ref
            need = self.blocks_needed(true_len)
            if len(self._free_blocks) < need:
                # backpressure, not a fault: the scheduler requeues
                # this request until running streams free blocks
                raise KVPoolExhausted(
                    f"need {need} KV blocks, {len(self._free_blocks)} "
                    f"free (pool {self.kv_blocks} x {bs} tokens)")
            ids = [self._free_blocks.pop() for _ in range(need)]
            self._owned[slot] = ids
            self._table[slot, :need] = ids
            self._table_dirty = True
            self._host_len[slot] = true_len
        # re-resolve + record under the adapter lock: an unregister
        # between resolution and recording would zero the stacks this
        # sequence is about to decode with (review TOCTOU); if it
        # slipped into the window above, return the freshly allocated
        # blocks instead of orphaning them on a live slot
        try:
            with self._lora_lock:
                aid_i = self.adapter_id(adapter)
                self._slot_adapters[slot] = aid_i
        except UnknownAdapterError:
            if self.kv_block:
                self.free_slot(slot)
            raise
        aid = np.asarray(aid_i, np.int32)
        if self.kv_block:
            nb_write = -(-bucket // bs)
            # blocks past the valid length land in the trash block
            block_ids = np.full(nb_write, TRASH_BLOCK, np.int32)
            nw = min(need, nb_write)
            block_ids[:nw] = ids[:nw]
            return self._insert_paged_fn(
                state, kv[0], kv[1], block_ids,
                np.asarray(slot, np.int32),
                np.asarray(true_len, np.int32),
                np.asarray(token, np.int32), aid, bucket=bucket)
        if slot_state_kind(self.cfg) and len(kv) < 3:
            raise ValueError(
                "insert: this model's slots own state beside their "
                "full-length KV rows (recurrent state, or a window "
                "ring); the prefill result holds none")
        return self._insert_fn(
            state, kv[0], kv[1], np.asarray(slot, np.int32),
            np.asarray(true_len, np.int32),
            np.asarray(token, np.int32), aid, *kv[2:],
            bucket=bucket)

    def _mask_table(self) -> jax.Array:
        """The device-resident [mask_table_rows, V] grammar mask
        table, created all-True on first touch (all-True rows are
        safe: they mask nothing). Row 0 stays all-True forever — the
        sentinel unmasked slots index."""
        if self._mask_table_dev is None:
            self._mask_table_dev = jnp.ones(
                (self.mask_table_rows, self.cfg.vocab_size), bool)
        return self._mask_table_dev

    def set_mask_row(self, row: int, bits: np.ndarray) -> None:
        """Upload one grammar-state mask as row `row` (>= 1; row 0 is
        the reserved all-True sentinel) of the device mask table.
        Called by the scheduler's GrammarMaskCache on cache miss;
        eviction is just the next upload overwriting the row. The
        update is an ordinary device computation, so it serializes
        with in-flight decode dispatches — a row can be rewritten
        while the plan that referenced it is still executing only
        after that plan's gather has been issued."""
        row = int(row)
        if not 1 <= row < self.mask_table_rows:
            raise ValueError(f"mask row {row} out of range "
                             f"[1, {self.mask_table_rows})")
        tab = self._mask_table()
        self._mask_table_dev = self._mask_row_fn(
            tab, np.asarray(row, np.int32), np.asarray(bits, bool))

    def _sampling(self, temperature, top_k, top_p) -> tuple:
        """A step's per-slot sampling arguments and its fresh key, in
        the order every program takes them."""
        return (_sampling_array(temperature, np.float32),
                _sampling_array(top_k, np.int32),
                _sampling_array(top_p, np.float32), self._next_key())

    def _dispatch(self, family: str, state: DecodeState, args: tuple,
                  mask, mask_idx, static: dict, *, tokens: int,
                  kv_rows: int, weight_passes: int = 1) -> tuple:
        """Run the program of `family` that the given mask kind and
        this engine's cache kind name (`self.programs`): `args` go
        between the state (and the block table, over the pool) and
        the mask. Returns the program's results, the state first,
        with host copies of the others already in flight."""
        name = family
        if mask_idx is not None:
            name += "_masked_idx"
            args += (self._mask_table(), np.asarray(mask_idx, np.int32))
        elif mask is not None:
            name += "_masked"
            args += (np.asarray(mask, bool),)
        if self.kv_block:
            name += "_paged"
            if self._table_dirty or self._table_dev is None:
                # upload once per table CHANGE, not once per step; the
                # copy keeps the device table stable while steps run
                self._table_dev = jnp.asarray(self._table.copy())
                self._table_dirty = False
            args = (self._table_dev, *args)
        args = (self.params, state, *args)
        fn = self.programs[name]
        self._ledger_capture(
            name, ",".join(f"{k}={v}" for k, v in static.items()), fn,
            args, static, tokens=tokens, kv_rows=kv_rows,
            weight_passes=weight_passes)
        outs = self._take_counts(fn(*args, **static))
        for arr in outs[1:]:
            copy = getattr(arr, "copy_to_host_async", None)
            if copy is not None:  # sharded/global arrays may not have it
                copy()
        return outs

    def decode(self, state: DecodeState, temperature, top_k, top_p,
               mask: Optional[np.ndarray] = None,
               mask_idx: Optional[np.ndarray] = None,
               ) -> Tuple[DecodeState, jax.Array]:
        """One decode step for ALL slots. Sampling params: [B] arrays
        — host arrays are converted; already-device-resident
        jax.Arrays (the scheduler's sampling cache) pass straight
        through. `mask` ([B, V] bool) routes through the masked
        program (structured outputs); None keeps the maskless one.
        `mask_idx` ([B] int32, wins over `mask`) instead gathers each
        slot's mask row from the device-resident mask table — B ints
        of transfer instead of B*V bools; unmasked slots pass 0 (the
        reserved all-True row).

        The returned tokens stay device-resident with a host copy
        already in flight (`copy_to_host_async`), so a pipelined
        caller can dispatch the next step before reading them; the
        eventual `np.asarray(toks)` then completes an overlapped copy
        instead of starting a blocking one."""
        if self.kv_block:
            self._grow_blocks()
        return self._dispatch(
            "decode", state, self._sampling(temperature, top_k, top_p),
            mask, mask_idx, {}, tokens=self.max_slots,
            kv_rows=self._kv_capacity_rows())

    def decode_multi(self, state: DecodeState, temperature, top_k,
                     top_p, steps: int, budget, stop_ids,
                     lookahead_rows: Optional[int] = None,
                     mask: Optional[np.ndarray] = None,
                     mask_idx: Optional[np.ndarray] = None,
                     ) -> Tuple[DecodeState, jax.Array, jax.Array]:
        """`steps` decode iterations for ALL slots in ONE device
        program — the host pays one dispatch and one sync per chunk
        instead of per token (docs/multi-step-decode.md).

        budget: [B] int32 per-slot remaining-token cap (0 freezes the
        slot for the chunk); stop_ids: [B, NS] int32 per-slot stop
        table, -1 padding (sampled tokens are non-negative, so -1
        never matches). Both may be host numpy or device-cached
        jax.Arrays, like the sampling params. lookahead_rows (paged
        only): KV rows to pre-allocate per slot before dispatch —
        pipelined callers pass the summed rows of every plan in
        flight plus this one so each dispatch's writes land in owned
        blocks; defaults to `steps`. mask ([B, steps, V] bool,
        optional) applies a per-iteration structured-output mask
        stack (docs/step-plan.md) through the masked program
        variants; mask_idx ([B, steps] int32, wins over mask) gathers
        the stack from the device-resident mask table instead —
        steps ints per slot on the wire, 0 = the all-True row.

        Returns (state, tokens [B, steps], advanced [B]) with host
        copies of the outputs already in flight (mirroring decode()):
        slot b really produced tokens[b, :advanced[b]] — columns past
        that are frozen filler the caller must discard. Paged callers
        reconcile each drained chunk with commit_spec(slot, advanced,
        reserve=...)."""
        n = int(steps)
        if self.kv_block:
            self._grow_blocks_spec(
                n if lookahead_rows is None else int(lookahead_rows))
        return self._dispatch(
            "decode_multi", state,
            (*self._sampling(temperature, top_k, top_p),
             _sampling_array(budget, np.int32),
             _sampling_array(stop_ids, np.int32)),
            mask, mask_idx, dict(n=n), tokens=self.max_slots * n,
            kv_rows=n * self._kv_capacity_rows(), weight_passes=n)

    def verify(self, state: DecodeState, drafts: np.ndarray,
               draft_len: np.ndarray, temperature, top_k, top_p,
               lookahead_rows: Optional[int] = None,
               mask: Optional[np.ndarray] = None,
               mask_idx: Optional[np.ndarray] = None,
               ) -> Tuple[DecodeState, jax.Array, jax.Array]:
        """One speculative verify step for ALL slots: score the k
        drafted tokens plus one bonus position in a single weight
        pass and accept per slot the longest valid prefix
        (sampling.spec_verify). A slot with draft_len 0 degenerates
        to a plain decode step — same logits, same sampling rule.

        drafts: [B, k] int32 host array (garbage past draft_len);
        draft_len: [B] int32 in [0, k]. Sampling params as decode().
        mask ([B, V] bool, optional) constrains position-0 sampling —
        how masked (structured-output) slots ride a verify plan at
        draft_len 0. mask_idx ([B, k+1] int32, wins over mask)
        gathers a full per-position mask from the device mask table —
        the spec-through-grammar path, where masked slots DRAFT and
        every scored position honors its own grammar mask (0 = the
        all-True row). Returns (state, out_tokens [B, k+1], accepted
        [B]) with host copies of the outputs already in flight,
        mirroring decode(): slot b emits out_tokens[b, :accepted[b]+1].

        Verify steps pipeline like decode steps; paged callers pass
        lookahead_rows (summed rows of every plan in flight plus this
        one's k+1, defaulting to k+1) so the block pre-allocation
        covers in-flight plans, and reconcile each drained step with
        commit_spec(slot, accepted+1, reserve=...) — the same surplus
        discipline as decode_multi."""
        refused = slot_state_refusals(self.cfg, spec_tokens=True)
        if refused:
            raise ValueError(refused[0])
        drafts = np.asarray(drafts, np.int32)
        k = int(drafts.shape[1])
        if self.kv_block:
            self._grow_blocks_spec(
                k + 1 if lookahead_rows is None
                else int(lookahead_rows))
        return self._dispatch(
            "verify", state,
            (drafts, np.asarray(draft_len, np.int32),
             *self._sampling(temperature, top_k, top_p)),
            mask, mask_idx, dict(k=k),
            tokens=self.max_slots * (k + 1),
            kv_rows=self._kv_capacity_rows()
            + self.max_slots * (k + 1))
