"""Live HBM accounting: who owns the device memory right now.

`device.memory_stats()` gives the allocator's truth (bytes in use,
peak, limit); the engine knows its own tenants — weights (the
quantizer's byte model), KV cache (pool capacity in paged mode, the
dense slab otherwise), recurrent state (what a hybrid model's slots
carry through its linear-attention layers), the window ring (the last
`sliding_window` rows a slot keeps for each window layer of a periodic
window / global model), prefix cache (its own byte counter). The
residual is workspace: XLA temp buffers, collectives scratch,
fragmentation. Partitioning the allocator number against the tenants
turns "HBM is 93% full" into "weights 41%, KV 38%, prefix 6%,
workspace 8%" — the first question of every OOM post-mortem.

A new allocator peak records an `hbm_peak` watermark event in the
flight ring, carrying the partition at that moment — so after an
OOM kill the flight dump (or GET /debug/events) shows what grew.

Off-TPU `memory_stats()` is unavailable; the gauges then carry the
tenant model alone (in_use = sum of known tenants, workspace 0) so
dashboards keep a consistent shape in dev environments.
"""

from __future__ import annotations

from typing import Dict, Optional

# fixed tenant enum: gauge children are pre-created for exactly this
# set, so label cardinality is bounded by construction (the
# metrics-label-cardinality lint pattern)
HBM_TENANTS = ("weights", "kv_cache", "recurrent_state", "window_ring",
               "latent_rows", "prefix_cache", "workspace")


def kv_capacity_bytes(engine) -> int:
    """Device bytes of the engine's KV allocation: the paged pool
    (kv_blocks x kv_block rows) or the dense [L, B, S] slab. Uses
    the same per-row arithmetic as the engine's cache shapes."""
    import jax.numpy as jnp
    cfg = getattr(engine, "cfg", None)
    if cfg is None:
        return 0
    row_fn = getattr(engine, "kv_row_bytes", None)
    if callable(row_fn):
        # the engine's own byte model — int8-pool aware (quantized
        # rows store 1 byte/element + two f32 scales per head)
        row = int(row_fn())
    else:
        itemsize = jnp.dtype(cfg.dtype).itemsize
        row = (cfg.num_layers * cfg.kv_cache_heads
               * (cfg.kv_cache_k_dim + cfg.kv_cache_v_dim) * itemsize)
    if getattr(engine, "kv_block", 0):
        return int(engine.kv_blocks * engine.kv_block * row)
    return int(engine.max_slots * engine.max_seq * row)


class HbmAccountant:
    """Scrape-time HBM gauges partitioned against the known tenants.

    `stats_fn` overrides the `device.memory_stats()` read (tests
    inject allocator numbers; None falls back to the first jax
    device, degrading gracefully when the platform has no stats).
    """

    def __init__(self, registry, weight_bytes: int = 0, device=None,
                 flight=None, stats_fn=None):
        self.weight_bytes = int(weight_bytes)
        self.flight = flight
        self._stats_fn = stats_fn
        self._device = device
        self._last_peak = 0.0
        self._g_in_use = registry.gauge(
            "ome_engine_hbm_bytes_in_use",
            "Device bytes in use (allocator truth on TPU; the tenant "
            "model's sum off-TPU)")
        self._g_limit = registry.gauge(
            "ome_engine_hbm_bytes_limit",
            "Device memory limit reported by the allocator (0 when "
            "unavailable)")
        self._g_peak = registry.gauge(
            "ome_engine_hbm_peak_bytes",
            "Allocator high-water mark; a new peak also records an "
            "hbm_peak flight event with the tenant partition")
        fam = registry.gauge(
            "ome_engine_hbm_tenant_bytes",
            "Device bytes attributed per tenant: weights (quantizer "
            "byte model), kv_cache (pool/slab capacity), "
            "recurrent_state (a hybrid model's per-slot DeltaNet "
            "state), window_ring (a periodic window / global model's "
            "per-slot rings), latent_rows (a latent-attention model's "
            "slab of one row a token), prefix_cache (its byte counter), "
            "workspace (the residual)",
            labelnames=("tenant",))
        self._tenants = {t: fam.labels(tenant=t) for t in HBM_TENANTS}

    @classmethod
    def for_engine(cls, engine, registry, flight=None
                   ) -> Optional["HbmAccountant"]:
        """Build an accountant for a real engine; None for fakes and
        wrappers without params/cfg (scheduler tests)."""
        params = getattr(engine, "params", None)
        if params is None or getattr(engine, "cfg", None) is None:
            return None
        try:
            from ..models.quant import quantized_bytes
            wb = quantized_bytes(params)
        except Exception:
            return None
        return cls(registry, weight_bytes=wb, flight=flight)

    def _read_stats(self) -> Optional[Dict[str, float]]:
        if self._stats_fn is not None:
            try:
                return self._stats_fn()
            except Exception:
                return None
        dev = self._device
        if dev is None:
            try:
                import jax
                dev = self._device = jax.devices()[0]
            except Exception:
                return None
        fn = getattr(dev, "memory_stats", None)
        if fn is None:
            return None
        try:
            return fn()
        except Exception:
            return None

    def update(self, engine=None) -> Dict[str, float]:
        """Refresh the gauges (one /metrics scrape). Returns the
        partition dict (tests assert the arithmetic on it)."""
        kv = kv_capacity_bytes(engine) if engine is not None else 0
        # a latent-attention model's slab holds one row `[c | k_pe]` a
        # token for all heads, not keys and values: a tenant of its own
        latent = 0
        if getattr(getattr(engine, "cfg", None), "mla", False):
            kv, latent = 0, kv
        # the slots' other state: a hybrid model's DeltaNet layers
        # hold a float32 matrix a head and a conv tail, not rows
        state_fn = getattr(engine, "state_bytes", None)
        rs = int(state_fn()) if callable(state_fn) else 0
        ring_fn = getattr(engine, "ring_bytes", None)
        ring = int(ring_fn()) if callable(ring_fn) else 0
        pc = getattr(engine, "prefix_cache", None)
        pcb = int(getattr(pc, "bytes", 0) or 0)
        stats = self._read_stats()
        tenant_sum = self.weight_bytes + kv + rs + ring + latent + pcb
        if stats:
            in_use = float(stats.get("bytes_in_use", tenant_sum))
            limit = float(stats.get("bytes_limit", 0) or 0)
            peak = float(stats.get("peak_bytes_in_use", in_use))
        else:
            in_use, limit, peak = float(tenant_sum), 0.0, 0.0
        workspace = max(in_use - tenant_sum, 0.0)
        part = {"bytes_in_use": in_use, "bytes_limit": limit,
                "peak_bytes": peak, "weights": float(self.weight_bytes),
                "kv_cache": float(kv), "recurrent_state": float(rs),
                "window_ring": float(ring), "latent_rows": float(latent),
                "prefix_cache": float(pcb), "workspace": workspace}
        self._g_in_use.set(in_use)
        self._g_limit.set(limit)
        self._g_peak.set(peak)
        for t in HBM_TENANTS:
            self._tenants[t].set(part[t])
        if peak > self._last_peak:
            # first observation just seats the watermark; every later
            # climb is a real event worth a post-mortem breadcrumb
            if self._last_peak and self.flight is not None:
                self.flight.record(
                    "hbm_peak",
                    peak_bytes=int(peak), bytes_in_use=int(in_use),
                    bytes_limit=int(limit),
                    weights=int(self.weight_bytes), kv_cache=int(kv),
                    recurrent_state=rs, window_ring=ring,
                    latent_rows=latent,
                    prefix_cache=pcb,
                    workspace=int(workspace))
            self._last_peak = peak
        return part
