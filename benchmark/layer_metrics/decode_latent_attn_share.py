"""Programs: share of the decode family's device time under a latent
layer's attention (`attn_latent`, around a layer's `kv_write` and
`attn`: the slot's row written in place, the kernel `latent_decode`
over every row the slot holds), from latent_kinds.py, %."""

import latent_kinds


def read(ctx):
    return latent_kinds.share(ctx, "decode")
