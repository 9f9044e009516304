"""Engine: the share of `setup_s` the program's own clock does not
cover, %: 100 x (1 - (`ome_engine_startup_seconds` +
`ome_engine_compile_seconds_total{when="serving"}` over all stages) /
`setup_s`), not under 0, from the scrape taken after warm-up. What is
left is the model directory's write, the router's start, the health
polls and the warm-up requests' own device and HTTP time. None where
the program publishes neither or the run measured no set-up."""

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
    setup_s = ctx.get("setup_s")
    before = ctx["metrics_before"]
    started = before.get("ome_engine_startup_seconds")
    rows = family(before, "ome_engine_compile_seconds_total")
    if not setup_s or started is None or not rows:
        return None
    serving = sum(value for labels, value in rows
                  if labels.get("when") == "serving")
    return max(100.0 * (1.0 - (started + serving) / setup_s), 0.0)
