"""Kernels: the prefill's latent attention's share of the chip's peak
operations. Operations it must do
(`cost_latent_moe.latent_prefill_flops`: the causal triangle of every
layer at the prompt's TRUE length, not the bucket's, at the
materialised widths: nope + rope for a score, v_head_dim for the
weighted sum) over the chip's peak bf16 FLOP/s, divided by the device
time under `attn_latent`, over the prefills the capture holds whole,
each with the prompt length its `admit.prefill` span carries
(latent_kinds.whole_prefills), %. Compute-bound side: 512 operations a
byte of a head's keys and values at a query block of 512."""

import cost_latent_moe
import latent_kinds


def read(ctx):
    pairs = latent_kinds.whole_prefills(ctx)
    spent = sum(s for _, s in pairs)
    if spent <= 0 or not ctx["peaks"]:
        return None
    flops = sum(cost_latent_moe.latent_prefill_flops(ctx["config"], n)
                for n, _ in pairs)
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / spent
