"""Device: 1 minus the union of device-operation intervals over the
traced window, on the device that idles most, %."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - min(trace["busy_by_device"]) / trace["window_s"])
