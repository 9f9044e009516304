"""Kernels: the routed experts' share of their memory roofline in a
decode step of the latent-attention sparse family. Bytes they must
read (`cost_latent_moe.moe_step_bytes`: in every expert layer each
HELD routed expert the step's tokens reached, by the program's
counter) over the chip's peak HBM bytes/s, divided by the device time
a decode step spends under `moe_experts` (subphases.py: the gathers,
the grouped matmuls and the combine; the router and the shared expert
run under scopes of their own and are counted on neither side), %.
Memory-bound side: under a token an expert a step."""

import cost_latent_moe
import subphases


def read(ctx):
    hit = subphases.experts_hit_a_layer(ctx)
    spent = subphases.step_seconds(ctx, ("moe_experts",))
    if hit is None or spent is None or not ctx["peaks"]:
        return None
    least = (cost_latent_moe.moe_step_bytes(ctx["config"], hit)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
