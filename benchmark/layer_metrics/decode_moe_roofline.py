"""Kernels: the expert layers' share of their memory roofline in a
decode step. Bytes they must read (`cost_hybrid.moe_step_bytes`: in
every layer the router, the shared expert and its gate once, and each
routed expert the step's tokens reached, by the program's counter)
over the chip's peak HBM bytes/s, divided by the device time a decode
step spends under the expert layer's scopes (subphases.py), %.
Memory-bound side: under a token an expert a step."""

import cost_hybrid
import subphases


def read(ctx):
    hit = subphases.experts_hit_a_layer(ctx)
    spent = subphases.step_seconds(ctx, subphases.MOE)
    if hit is None or spent is None or not ctx["peaks"]:
        return None
    least = (cost_hybrid.moe_step_bytes(ctx["config"], hit)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
