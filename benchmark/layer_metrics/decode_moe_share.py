"""Programs: share of the decode family's device time under the expert
layer's scopes (`moe_router`, `moe_experts`, `moe_shared`, each inside
`mlp`), from subphases.py, %."""

import subphases


def read(ctx):
    return subphases.decode_share(ctx, subphases.MOE)
