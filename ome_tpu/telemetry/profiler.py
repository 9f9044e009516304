"""On-demand jax.profiler capture behind `POST /debug/profile`.

The SRE move when a TPU slice serves slow: grab an N-second device
trace from the LIVE replica (no restart, no redeploy) and open it in
TensorBoard/XProf. The capture holds the program's own names: phase
scopes and kernel names on the device operations, the scheduler's
`sched.*` spans on the host plane of the same clock
(telemetry/scopes.py, docs/tracing-timeline.md). The endpoint is
guarded twice — it only exists when the operator launched with
`--profile-dir`, and captures are serialized (a second concurrent request gets 409 instead of
corrupting the active trace). Off-TPU the capture is a structured
no-op: the endpoint answers with `captured: false` and the platform
name rather than burning seconds tracing a CPU fallback nobody asked
to profile.
"""

from __future__ import annotations

import threading
import time

MAX_SECONDS = 60.0

_capture_lock = threading.Lock()


class ProfileInProgress(RuntimeError):
    """Another capture is running; the caller should retry later."""


def capture(out_dir: str, seconds: float = 1.0, ledger=None) -> dict:
    """Blocking N-second device trace into `out_dir`.

    Returns a summary dict (the HTTP response body). When the engine
    carries a program cost ledger (perf/ledger.py), its per-program
    summary rides along under "programs" — the trace viewer shows
    WHERE time went, the ledger says what each program SHOULD cost.
    Raises ProfileInProgress when a capture is already active,
    ValueError for an unusable duration.
    """
    seconds = float(seconds)
    if not (0 < seconds <= MAX_SECONDS):
        raise ValueError(
            f"seconds must be in (0, {MAX_SECONDS:g}], got {seconds}")
    import jax

    from .. import device
    platform = jax.devices()[0].platform
    if not device.on_tpu():
        result = {"captured": False, "platform": platform,
                  "note": "profiler capture is a no-op off-TPU"}
        if ledger is not None:
            result["programs"] = ledger.summary()
        return result
    if not _capture_lock.acquire(blocking=False):
        raise ProfileInProgress("a profile capture is already running")
    try:
        t0 = time.monotonic()
        # the capture holds the program's own names (telemetry/
        # scopes.py): scope paths and kernel names on the device
        # planes, the scheduler's `sched.*` / `admit.*` annotations
        # on the host plane. Those are TraceMe events (host tracer);
        # the Python tracer, which records every Python call, is off:
        # a smaller capture, and a traced run closer to an untraced
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 2
        options.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        result = {"captured": True, "platform": platform,
                  "dir": out_dir,
                  "seconds": round(time.monotonic() - t0, 3)}
        if ledger is not None:
            result["programs"] = ledger.summary()
        return result
    finally:
        _capture_lock.release()
