"""The comparison that decides `correct`, run as a child that holds
the chip after the server has gone.

Input (a JSON file): the configuration's file, and one or more groups
(a run has one; calibrate.py one a window) of greedy requests that a
window finished: each prompt with the tokens that were served for it. For every served token the plain reference
(`benchmark/reference/<family>.py`, float32, its own weights) is run
over the prompt and the served tokens before it, and the number
compared is the GAP: how far the served token's reference logit lies
below the reference's best logit at that position, in units of that
position's logit standard deviation. A sound engine serves the
reference's best token or, where rounding flipped two near-equal
logits, one a hair below it.

Output (last line of stdout, JSON), for each group: tokens compared,
mean and widest gap, share of positions where the served token is the reference's
best; with `"control": true` also the same numbers for the token the
int8 reference puts first at each position (the control that the
limits must fail).
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import modeldir  # noqa: E402

ROWS = 256          # rows of logits scored per sequence (>= any max_tokens)
PAD_TO = 256        # sequence lengths are padded up to a multiple of this


def enable_compile_cache() -> str:
    """`JAX_COMPILATION_CACHE_DIR` as is when set, else the fixed
    `<checkout>/.jax_cache`: the program's own rule, so both share one
    directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(HERE), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def layout(prompt_len: int, n_tokens: int):
    """(padded length, first scored row, offset of the first served
    token's row inside the scored rows)."""
    if not 1 <= n_tokens <= ROWS:
        raise ValueError(f"{n_tokens} served tokens; 1..{ROWS} can be scored")
    seq = prompt_len + n_tokens - 1            # inputs: prompt + tokens[:-1]
    padded = max(-(-seq // PAD_TO) * PAD_TO, ROWS)
    first = max(min(prompt_len - 1, padded - ROWS), 0)
    return padded, first, prompt_len - 1 - first


def main(argv=None) -> int:
    spec_path = (argv or sys.argv[1:])[0]
    with open(spec_path) as f:
        spec = json.load(f)
    with open(spec["config_file"]) as f:
        cfg_file = json.load(f)
    bench = cfg_file["benchmark"]
    cfg = modeldir.model_config(cfg_file)
    t0 = time.time()
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = importlib.import_module("reference." + bench["reference"])

    devs = jax.devices()
    out = {"device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}}
    shardings = None
    if len(devs) > 1:
        shardings = functools.partial(spread_over, devs)
    w = ref.init_weights(cfg, shardings=shardings)
    jax.block_until_ready(w)
    out["param_count"] = int(sum(x.size for x in jax.tree.leaves(w)))
    out["weights_s"] = round(time.time() - t0, 2)

    @jax.jit
    def score(lg, served, valid):
        best = lg.max(-1)
        std = lg.std(-1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        gap = jnp.where(valid, (best - got) / std, 0.0)
        return gap, jnp.isfinite(lg).all(), lg.argmax(-1)

    control = bool(spec.get("control"))

    def summary(parts):
        g = np.concatenate(parts) if parts else np.zeros(0)
        if not g.size:
            return {"tokens": 0}
        return {"tokens": int(g.size), "gap_mean": float(g.mean()),
                "gap_max": float(g.max()),
                "gap_p99": float(np.percentile(g, 99)),
                "best_share": float((g == 0).mean())}

    def compare(samples):
        gaps, gaps_c, finite = [], [], True
        for s in samples:
            g, g_c, fin = compare_one(s)
            gaps.append(g)
            finite = finite and fin
            if control:
                gaps_c.append(g_c)
        res = summary(gaps)
        res.update(finite=finite, sequences=len(samples))
        if control:
            res["control_int8"] = summary(gaps_c)
        return res

    def compare_one(s):
        prompt, toks = s["prompt_ids"], s["token_ids"]
        padded, first, off = layout(len(prompt), len(toks))
        seq = np.zeros(padded, np.int32)
        seq[:len(prompt) + len(toks) - 1] = prompt + toks[:-1]
        served = np.zeros(ROWS, np.int32)
        served[off:off + len(toks)] = toks
        valid = np.zeros(ROWS, bool)
        valid[off:off + len(toks)] = True
        lg = ref.logits(w, cfg, seq, first, ROWS)
        gap, fin, _ = score(lg, jnp.asarray(served), jnp.asarray(valid))
        gap_c = None
        if control:
            lg_c = ref.logits(w, cfg, seq, first, ROWS, int8=True)
            gap_c, _, _ = score(lg, lg_c.argmax(-1).astype(jnp.int32),
                                jnp.asarray(valid))
            gap_c = np.asarray(gap_c)[valid]
        return np.asarray(gap)[valid], gap_c, bool(fin)

    out["groups"] = [compare(g) for g in spec["groups"]]
    out["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    out["check_s"] = round(time.time() - t0, 2)
    print(json.dumps(out), flush=True)
    return 0


def spread_over(devs, shapes):
    """Shardings that split every large leaf over all devices along
    its last axis that divides evenly (the values do not depend on the
    layout: threefry is partitionable), so a model that needs the host
    fits the reference too."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devs), ("x",))
    n = len(devs)

    def one(s):
        if s.size < (1 << 20):
            return NamedSharding(mesh, P())
        for axis in range(len(s.shape) - 1, 0, -1):
            if s.shape[axis] % n == 0:
                spec = [None] * len(s.shape)
                spec[axis] = "x"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, shapes)


if __name__ == "__main__":
    sys.exit(main())
