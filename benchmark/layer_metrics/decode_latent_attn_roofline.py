"""Kernels: the decode step's latent attention's share of its
roofline. The kernel `latent_decode` sits on the chip's ridge (128
heads share one cached row: 242 operations a byte where a v5e turns at
240), so the least time is the LARGER of the bytes it must read over
the chip's peak HBM bytes/s and the operations it must do over its
peak bf16 FLOP/s (`cost_latent_moe.latent_decode_step`: for each live
slot its len rows of kv_lora_rank + qk_rope_head_dim numbers a layer,
each scored and weighed by every head; the lengths from the client's
records at the middle of the trace), divided by the device time a
decode step spends in the kernel (latent_kinds.py), %. One side alone
would flatter it; both are printed."""

import json

import cost_latent_moe
import latent_kinds


def read(ctx):
    spent = latent_kinds.decode_kernel_seconds(ctx)
    lengths = latent_kinds.live_lengths(ctx)
    if spent is None or not lengths or not ctx["peaks"]:
        return None
    nbytes, flops = cost_latent_moe.latent_decode_step(ctx["config"],
                                                       lengths)
    by_bytes = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    print(json.dumps({
        "phase": "latent_decode_roofline", "live_slots": len(lengths),
        "live_rows": sum(lengths), "kernel_ms": 1e3 * spent,
        "bytes_ms": 1e3 * by_bytes, "flops_ms": 1e3 * by_flops,
        "bound": "memory" if by_bytes >= by_flops else "compute"}),
        flush=True)
    return 100.0 * max(by_bytes, by_flops) / spent
