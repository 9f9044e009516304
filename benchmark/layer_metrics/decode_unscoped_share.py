"""Programs: share of the decode family's device time on no phase of
the vocabulary (operations whose op_name path the compiler dropped or
that sit outside every scope), from phases.py, %. The `decode_phases`
line names each such operation over 1 % of the step."""

import phases


def read(ctx):
    return phases.decode_share(ctx, phases.UNSCOPED)
