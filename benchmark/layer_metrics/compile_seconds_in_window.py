"""Engine: seconds of compiling or loading programs inside the
window: `ome_engine_compile_seconds_total` summed over stages, the
scrape after the window less the one before it. Expected 0, as
`compiles_in_window`, which counts entries and files from outside.
None where the program has no such counter."""

import re


_LABEL = re.compile(r'(\w+)="([^"]*)"')


def family(samples, name):
    """[(labels, value)] of one metric family of a scrape, whose keys
    are `name{labels}` with the labels verbatim."""
    out = []
    for key, value in samples.items():
        base, _, rest = key.partition("{")
        if base == name:
            out.append((dict(_LABEL.findall(rest)), value))
    return out


def read(ctx):
    name = "ome_engine_compile_seconds_total"
    before = family(ctx["metrics_before"], name)
    after = family(ctx["metrics_after"], name)
    if not before or not after:
        return None
    return max(sum(v for _, v in after) - sum(v for _, v in before), 0.0)
