"""Kernels: share of the decode family's device time spent under the
`sample` scope (sampling.sample and what it calls), from the profiler
capture reduced by phases.py, %."""

import phases


def read(ctx):
    return phases.decode_share(ctx, "sample")
