"""Output tokens that reached the client inside the window, over its
length."""


def read(ctx):
    n = sum(1 for a in ctx["answers"] for t in a.arrivals
            if ctx["t0"] <= t <= ctx["t1"])
    return n / ctx["seconds"]
