"""Programs: share of the decode family's device time under the Gated
DeltaNet mixer (`gdn_mixer`: projections, conv, recurrence, gated norm,
output projection) and its state update (`gdn_state`, inside it), from
subphases.py, %."""

import subphases


def read(ctx):
    return subphases.decode_share(ctx, subphases.LINEAR_ATTN)
