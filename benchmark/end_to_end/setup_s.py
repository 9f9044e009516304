"""Model directory, server and router start, weights, warm-up of every
shape the cell uses (and, in a first run, compilation), host clock."""


def read(ctx):
    return ctx["setup_s"]
