"""Programs: share of the prefill family's device time under a latent
layer's attention (`attn_latent`: the prompt's rows written, the kernel
`latent_prefill` over the materialised heads), from latent_kinds.py,
%. Where a cell's tail gap is a decode step plus a prefill, this is
the attention's part of that prefill."""

import latent_kinds


def read(ctx):
    return latent_kinds.share(ctx, "prefill")
