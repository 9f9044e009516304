"""Device: idle time inside the traced stretch whose gap no `sched.*`
span of the scheduler covers, over all idle time there, from phases.py,
%. Near 0 proves the host's spans share the device trace's clock; it
earns its keep when a change makes the device wait."""

import phases


def read(ctx):
    total = phases.load(ctx)
    if not total or not total.get("sched_spans"):
        return None         # a program that writes no spans
    idle = total.get("idle_s", {})
    whole = sum(idle.values())
    if whole <= 0:
        return None
    return 100.0 * idle.get(phases.NONE, 0.0) / whole
