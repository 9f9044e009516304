"""Programs: device time in the `prefill` and `insert` families over
the device's busy time in the traced stretch, from phases.py, %: what
admissions take from the decode streams."""

import phases


def read(ctx):
    total = phases.load(ctx)
    if not total or not total.get("busy_s"):
        return None
    fam = total["families_s"]
    if not any(f in fam for f in phases.FAMILIES):
        return None         # a program that names no family
    return 100.0 * (fam.get("prefill", 0.0) + fam.get("insert", 0.0)) \
        / total["busy_s"]
