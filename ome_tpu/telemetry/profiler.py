"""On-demand jax.profiler capture behind `POST /debug/profile`.

The SRE move when a TPU slice serves slow: grab an N-second device
trace from the LIVE replica (no restart, no redeploy) and open it in
TensorBoard/XProf. The endpoint is guarded twice — it only exists
when the operator launched with `--profile-dir`, and captures are
serialized (a second concurrent request gets 409 instead of
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
        jax.profiler.start_trace(out_dir)
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
