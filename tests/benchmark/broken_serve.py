"""The program with its timed path broken underneath, for
test_run_end_to_end.py: every token a decode step produces is altered
on its way to the scheduler (the device state keeps the true one, so
the next step runs on the real context and only what is SERVED is
wrong). Started in the server's place:
`python -m tests.benchmark.broken_serve <serve arguments>`."""

import sys

from ome_tpu.engine import core, serve

_decode = core.InferenceEngine.decode


def decode(self, *args, **kwargs):
    state, toks = _decode(self, *args, **kwargs)
    return state, (toks + 1) % self.cfg.vocab_size


core.InferenceEngine.decode = decode

if __name__ == "__main__":
    sys.exit(serve.main())
