"""Programs: share of the decode family's device time spent on the KV
pool rather than on the model: the `kv_write` scope (a layer's rows
written into the pool) plus the self time of `layers` (what sits on
the layer scan but in none of its phases: the pool-sized copies and
write-backs around the loop), from phases.py, %."""

import phases


def read(ctx):
    return phases.decode_share(ctx, "kv_write", "layers")
