"""Kernels: device time of the operations named by the kernel
`paged_attention` in the decode family, per decode step (all layers),
from phases.py, ms."""

import phases


def read(ctx):
    total = phases.load(ctx)
    if not total or not total.get("decode_steps"):
        return None
    spent = total["kernels_s"].get("decode", {}).get("paged_attention")
    if spent is None:
        return None
    return 1e3 * spent / total["decode_steps"]
