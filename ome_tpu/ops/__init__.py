"""Pallas TPU kernels and their XLA reference paths.

A kernel may decline a shape it does not cover (its entry returns
None and the dispatcher takes the XLA path). That stays legal, but it
is never silent: the dispatcher calls `note_decline`, which logs the
kernel and the reason — once per program, because dispatch runs only
while a program is traced — and hands it to whoever is collecting
(the program ledger collects around the lowering of each engine
program, so /debug/programs lists what each program fell back on).
"""

from __future__ import annotations

import contextlib
import logging
from contextvars import ContextVar
from typing import Iterator, List, Optional

log = logging.getLogger("ome.ops")

_collector: ContextVar[Optional[List[str]]] = ContextVar(
    "ome_kernel_declines", default=None)


def note_decline(kernel: str, reason: str) -> None:
    """Record that `kernel` declined while a program was traced."""
    log.warning("kernel decline: %s -> XLA path (%s)", kernel, reason)
    sink = _collector.get()
    if sink is not None:
        sink.append(f"{kernel}: {reason}")


@contextlib.contextmanager
def collect_declines() -> Iterator[List[str]]:
    """Collect the declines noted while the body traces a program."""
    sink: List[str] = []
    token = _collector.set(sink)
    try:
        yield sink
    finally:
        _collector.reset(token)
