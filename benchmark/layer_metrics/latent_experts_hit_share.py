"""Programs: held experts that a routed pair of the decode batch
reached, a layer-step, over the experts held (`n_routed_experts`, the
latent-attention sparse family's key), %. From the program's counters
(`ome_engine_moe_experts_hit_total` over
`ome_engine_moe_layer_steps_total`, counted on the device), as they
moved over the window. What the decode step's expert bytes scale
with: an expert that no token reached need not be read.
(`moe_experts_hit_share` divides by `num_experts`, a key this family's
config.json does not have.)"""

import subphases


def read(ctx):
    hit = subphases.experts_hit_a_layer(ctx)
    held = ctx["config"].get("n_routed_experts")
    if hit is None or not held:
        return None
    return 100.0 * hit / held
