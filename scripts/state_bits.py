#!/usr/bin/env python
"""Is a tree's slot state another tree's to the bit, and from where not?

    python scripts/state_bits.py <tree> <out.json> [--prompt 5000]
        [--config benchmark/configs/smallthinker-21b-a3b-ep4.json]
        [--max-seq 16384] [--bucket 8192]

builds the engine of a benchmark configuration from `<tree>` (seeded
weights, 8 slots), prefills one seeded prompt, decodes 96 greedy tokens
and writes sha256 hashes of what the slot holds: a prompt's rows a
layer (`k_layers`, `v_layers`, and a ring model's `wk_layers`,
`wv_layers`), the tokens, and the rows the decode steps wrote
(`k_after`, `wk_after`). Rows are hashed as bytes, so a layout that
keeps the bytes (heads apart or merged, `llama.KVCache`) keeps the
hash. Run it on the parent (a `git archive` copy) and on the change
through the chip tool, one process a tree, and compare the files: the
first layer whose rows differ says where two trees part.

PR 38 learned with it that the parent is deterministic, that a
prefill's rows were the parent's in all 24 layers, and that
`flash_decode` over merged rows, equal to the parent's kernel to the
bit when interpreted on the CPU, differs from it in low bits on the
chip from the second layer on (`PERF.md` section 6). On the CPU
(`JAX_PLATFORMS=cpu`) it runs at a fixture's size (`--config tests/
benchmark/fixture_preroute/benchmark/configs/tiny-smallthinker.json
--prompt 40 --max-seq 256 --bucket 64`) and says nothing of the chip.
"""

import argparse
import hashlib
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("tree")
    ap.add_argument("out")
    ap.add_argument("--prompt", type=int, default=5000)
    ap.add_argument("--config", default="benchmark/configs/"
                                        "smallthinker-21b-a3b-ep4.json")
    ap.add_argument("--max-seq", type=int, default=16384)
    ap.add_argument("--bucket", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=96)
    args = ap.parse_args()
    sys.path[:0] = [args.tree, os.path.join(args.tree, "benchmark")]
    import jax
    import modeldir
    import numpy as np
    from ome_tpu.engine.core import InferenceEngine
    from ome_tpu.models import llama
    from ome_tpu.models.config import ModelConfig

    with open(os.path.join(args.tree, args.config)) as f:
        cfg = ModelConfig.from_hf_config(modeldir.model_config(json.load(f)))
    if cfg.is_moe:
        cfg = cfg.replace(moe_impl="ragged")
    params = jax.jit(lambda k: llama.init_params(k, cfg))(
        jax.random.PRNGKey(0))
    slots, slot = 8, 1
    eng = InferenceEngine(params, cfg, max_slots=slots,
                          max_seq=args.max_seq,
                          prefill_buckets=[args.bucket])
    ids = [int(t) for t in np.random.RandomState(7).randint(
        1, cfg.vocab_size, args.prompt)]

    def hashes(x, rows):
        """A hash a layer of the first `rows` rows of x[layer, batch]."""
        return [hashlib.sha256(np.asarray(x[layer, :, :rows])
                               .view(np.uint16).tobytes()).hexdigest()[:16]
                for layer in range(x.shape[0])]

    tok, kv, n, bucket = eng.prefill(ids)
    rings = kv[2] if len(kv) > 2 and "wk" in kv[2] else {}
    res = {"device": str(jax.devices()[0]), "tok": int(tok),
           "k_layers": hashes(kv[0], n), "v_layers": hashes(kv[1], n)}
    for name, ring in rings.items():
        res[name + "_layers"] = hashes(ring, min(n, ring.shape[2]))
    state = eng.insert(eng.new_state(), kv, slot, n, tok, bucket)
    greedy = (np.zeros(slots, np.float32), np.zeros(slots, np.int32),
              np.ones(slots, np.float32))
    res["tokens"] = []
    for _ in range(args.steps):
        state, toks = eng.decode(state, *greedy)
        res["tokens"].append(int(np.asarray(toks)[slot]))
    one = slice(slot, slot + 1)
    res["k_after"] = hashes(state.k[:, one, n:n + args.steps], args.steps)
    if state.wk is not None:
        res["wk_after"] = hashes(state.wk[:, one], state.wk.shape[2])
    with open(args.out, "w") as f:
        json.dump(res, f)
    print(json.dumps({k: v if isinstance(v, (int, str)) else v[:3]
                      for k, v in res.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
