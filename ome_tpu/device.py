"""The accelerator this process runs on: one predicate, one identity,
one compile-cache rule.

Every gate that chooses between a Pallas kernel and its XLA path asks
`on_tpu()`; everything that reports where a number came from prints
`identity()`; every entry point that compiles engine programs calls
`enable_compile_cache()` first. Keeping the three here means a test
(or an ahead-of-time compile for a described chip) steers all the
gates by patching one function, and no module grows a second opinion
about what the device is.
"""

from __future__ import annotations

import os
from typing import Dict, List

# <repo>/.jax_cache — fixed, because the directory is part of the
# cache key: a temp name, pid or timestamp would never hit
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def on_tpu() -> bool:
    """True when the default device is a TPU. Raises (does not answer
    False) when JAX has no backend at all."""
    import jax
    return jax.devices()[0].platform == "tpu"


def identity() -> Dict[str, object]:
    """{platform, kind, count} as JAX reports them — the triple every
    result line names its device with."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_gb(key: str = "bytes_in_use") -> List[float]:
    """`memory_stats()[key]` of every local device, in GB (0.0 where
    the backend keeps no stats, as the CPU does): after a tp load this
    is equal shares, not one full chip."""
    import jax
    return [round((d.memory_stats() or {}).get(key, 0) / 1e9, 3)
            for d in jax.local_devices()]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. `JAX_COMPILATION_CACHE_DIR`, when set, is used as is
    (JAX reads it itself; no other directory is set in code);
    otherwise the fixed `<repo>/.jax_cache`. Thresholds are zeroed so
    every engine program is stored, the sub-second ones (insert,
    mask-row set) included — a warm start then compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
