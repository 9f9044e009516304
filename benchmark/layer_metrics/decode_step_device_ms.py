"""Programs: median duration of the decode program's XLA module on the
device, from the profiler trace, ms. The module is the one the
configuration names (`benchmark.decode_module`)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    want = ctx["config"]["benchmark"]["decode_module"]
    for name, m in trace["modules"].items():
        if name.split("(")[0] == want:
            return 1e3 * m["median_s"]
    return None
