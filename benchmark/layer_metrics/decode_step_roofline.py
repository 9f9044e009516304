"""Kernels / programs: the decode step's share of its memory roofline.
Bytes one step must move (every weight once plus the KV of the live
context, `cost.decode_step_bytes`, a chip's share under tensor
parallelism) over the chip's peak HBM bytes/s, divided by the median
device time of the decode module, %. Memory-bound side: at 16 slots a
decode step does 2 FLOP a weight byte, far under the chip's 240."""

import cost


def read(ctx):
    trace = ctx["trace"]
    if not trace or not ctx["peaks"]:
        return None
    want = ctx["config"]["benchmark"]["decode_module"]
    step = [m["median_s"] for name, m in trace["modules"].items()
            if name.split("(")[0] == want]
    if not step or step[0] <= 0:
        return None
    # live context over the traced stretch of the window, from the
    # client's records: prompt plus tokens received so far, summed
    # over the requests in flight at the middle of the trace
    mid = ctx["t0"] + 0.4 * ctx["seconds"] + 0.5 * trace["window_s"]
    live = 0
    for a in ctx["answers"]:
        if a.arrivals and a.arrivals[0] <= mid and not (
                a.done and a.arrivals[-1] < mid):
            live += len(a.prompt_ids) + sum(1 for t in a.arrivals if t <= mid)
    chips = ctx["config"]["benchmark"]["chips"]
    least = (cost.decode_step_bytes(ctx["config"], live) / chips
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / step[0]
