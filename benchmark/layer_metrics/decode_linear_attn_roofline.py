"""Kernels: the Gated DeltaNet mixers' share of their memory roofline
in a decode step. Bytes they must move
(`cost_hybrid.linear_attn_step_bytes`: every mixer's weights once, and
the float32 state and conv tail of each LIVE slot read and written)
over the chip's peak HBM bytes/s, divided by the device time a decode
step spends under the mixer's scopes (subphases.py), %. Memory-bound
side: the state update is two passes over 2 MiB a slot a layer."""

import cost_hybrid
import subphases


def read(ctx):
    live = subphases.live_slots(ctx)
    spent = subphases.step_seconds(ctx, subphases.LINEAR_ATTN)
    if live is None or spent is None or not ctx["peaks"]:
        return None
    least = (cost_hybrid.linear_attn_step_bytes(ctx["config"], live)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
