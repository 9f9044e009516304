"""The arithmetic of the yardstick: percentiles, spreads, lateness.
Stdlib only; copied in spirit from `ome_tpu/autoscale/replay.py`
(`_pct`), with interpolation, so that the program may change and the
yardstick does not."""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Linear interpolation between closest ranks; None when empty."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * p / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)`: the number a bound
    is set from."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def lateness(due: Sequence[float], sent: Sequence[float]) -> Dict[str, float]:
    """How late the generator ran: send time minus due time, seconds."""
    late = [max(s - d, 0.0) for d, s in zip(due, sent)]
    if not late:
        return {"mean_ms": 0.0, "p95_ms": 0.0, "max_ms": 0.0}
    return {"mean_ms": 1e3 * sum(late) / len(late),
            "p95_ms": 1e3 * percentile(late, 95),
            "max_ms": 1e3 * max(late)}


def gaps(arrivals: Sequence[float], until: Optional[float] = None
         ) -> List[float]:
    """Gaps between consecutive chunk arrivals of one request; a gap
    counts when it ended by `until`."""
    return [b - a for a, b in zip(arrivals, arrivals[1:])
            if until is None or b <= until]
