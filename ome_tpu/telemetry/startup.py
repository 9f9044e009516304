"""A process's start on its own clock: named phases that tile the time
from process creation to ready.

`python -m ome_tpu.engine.serve` and `python -m ome_tpu.router` each
keep one `StartupTimeline` in memory: a list of `(name, start, end)`
on `time.monotonic()`. A phase runs from where the one before it
ended to the end of its own block, so the phases cannot overlap and
leave no hole; the first, `interpreter`, runs from the creation of the
process (its start time in `/proc/self/stat` against `/proc/uptime`)
to the first statement of `main()`, and is left out where there is no
`/proc`. The one list is published three ways (docs/observability.md):
gauges on `/metrics`, set once; a block in the engine's `/health`;
and, with `--span-log`, an `engine.startup` span with a child a phase
(docs/tracing-timeline.md).

This module imports nothing heavy: the router, which never imports
JAX, uses it too. `telemetry/scopes.py` names `STARTUP_PHASES` beside
the program's other vocabularies.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import List, Optional, Tuple

# in order; the engine runs all six, the router the first and the last
#   interpreter  process creation -> first statement of main(): Python
#                itself, the import of the entry module and of what it
#                pulls in
#   device       -> the accelerator named: arguments, `import jax`, the
#                compile cache, the cross-host rendezvous, the runtime
#   weights      -> checkpoint read (or the one-program random init),
#                quantisation, LoRA merge, the transfer to the device
#   engine       -> the scheduler constructed: slab / pool / ring /
#                recurrent-state allocation, the start-up refusals
#   tokenizer    -> `load_tokenizer` returned
#   listen       -> `server.start()` returned: the weight plane's
#                manifest, the HTTP server, journal resume, the socket
STARTUP_PHASES = ("interpreter", "device", "weights", "engine",
                  "tokenizer", "listen")


def process_created_mono(now: Optional[float] = None) -> Optional[float]:
    """When this process was created, on `time.monotonic()`'s scale
    (so usually before `now`): the kernel's start time of the process
    (field 22 of `/proc/self/stat`, clock ticks since boot) against
    `/proc/uptime`. None where `/proc` is missing or unreadable."""
    now = time.monotonic() if now is None else now
    try:
        with open("/proc/self/stat") as f:
            # the command name may hold spaces and parentheses
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return now - age if age >= 0 else None


class StartupTimeline:
    """The phases of one process's start. Construct it as the first
    statement of `main()` (that closes `interpreter`), wrap each later
    stretch in `phase(name)`, call `ready()` once the listener is up.
    Offsets in `health()` count from process creation, or from the
    first statement of `main()` where creation is not known."""

    def __init__(self):
        now = time.monotonic()
        created = process_created_mono(now)
        self.origin = now if created is None else created
        self.phases: List[Tuple[str, float, float]] = []
        if created is not None:
            self.phases.append((STARTUP_PHASES[0], created, now))
        self._cursor = now
        self.ready_at: Optional[float] = None
        self.first_request_at: Optional[float] = None

    @contextlib.contextmanager
    def phase(self, name: str):
        """One phase: from the end of the phase before it to the end
        of this block (a phase closed inside the block, by a callee,
        takes its part out of this one). Two clock reads; nothing is
        published before `ready()`."""
        try:
            yield
        finally:
            start, self._cursor = self._cursor, time.monotonic()
            self.phases.append((name, start, self._cursor))

    def ready(self) -> float:
        """The listener is up: returns creation-to-ready seconds."""
        self.ready_at = self._cursor
        return self.ready_at - self.origin

    def mark_first_request(self) -> None:
        """Called at every admitted request; keeps the first."""
        if self.first_request_at is None:
            self.first_request_at = time.monotonic()

    def seconds(self) -> dict:
        """{phase: seconds}; a name used twice adds up."""
        out: dict = {}
        for name, start, end in self.phases:
            out[name] = out.get(name, 0.0) + (end - start)
        return out

    def publish(self, g_phase, g_total) -> None:
        """Set the caller's two gauges, once, after `ready()`:
        `g_phase` (label `phase`) to each phase's seconds, `g_total`
        to creation-to-ready. The callers declare them, under their
        own literal names (scripts/check_metrics.py reads those)."""
        took = self.seconds()
        for phase in STARTUP_PHASES:
            if phase in took:
                g_phase.labels(phase=phase).set(took[phase])
        g_total.set(self.ready_at - self.origin)

    def health(self) -> dict:
        """The `startup` block of `/health`."""
        def off(t):
            return None if t is None else round(t - self.origin, 6)
        return {"phases": [{"name": name, "start_s": off(start),
                            "end_s": off(end)}
                           for name, start, end in self.phases],
                "ready_s": off(self.ready_at),
                "first_request_s": off(self.first_request_at)}

    def write_spans(self, span_log, ctx=None) -> None:
        """`engine.startup` with one child a phase, on the span log's
        wall clock (each start is the wall time now less its distance
        on the monotonic clock)."""
        if span_log is None or not span_log.enabled or not self.phases:
            return
        from .tracing import Span
        wall_off = time.time() - time.monotonic()
        end = self.ready_at if self.ready_at is not None else self._cursor
        root = Span.begin("engine.startup", ctx=ctx,
                          start_mono=self.origin,
                          start_wall=self.origin + wall_off)
        root.set(phases=len(self.phases)).end(end)
        span_log.write(root)
        for name, start, stop in self.phases:
            child = Span("engine.startup." + name,
                         trace_id=root.trace_id,
                         parent_id=root.span_id, start_mono=start,
                         start_wall=start + wall_off)
            span_log.write(child.end(stop))
