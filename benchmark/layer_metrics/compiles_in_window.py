"""Engine: programs compiled inside the window: new entries of the
program ledger (`/debug/programs`) plus new files in the persistent
compile cache, before against after the window. Expected 0."""


def read(ctx):
    return float(
        max(ctx["programs_after"]["count"] - ctx["programs_before"]["count"], 0)
        + max(ctx["cache_after"] - ctx["cache_before"], 0))
