"""One general traffic generator, driven by a data file.

A traffic file (`benchmark/traffic/<name>.json`) gives the loop (open
or closed), the length distributions, the share of greedy requests and
the sampling temperature. A cell's own file
(`benchmark/cells/<cell>.json`, optional) overrides single keys, the
rate above all. Nothing here names a cell or a model.

Steadiness by construction: every seed sends THE SAME multiset of
prompt lengths, output lengths and (open loop) inter-arrival gaps,
namely the stratified quantiles of the stated distributions, in an order
the seed decides. An open loop sends exactly floor(rate x seconds)
requests; a closed loop draws its lengths in waves of `stratum`. Two
seeds then differ by order and by the words of the prompts, not by how
much work the window holds. The marginal distribution of each is the
stated one; arrivals have exponential gaps. A mix that gives
`schedule_seed` fixes the order too, and the run's seed then decides
only the words of the prompts.
"""

from __future__ import annotations

import dataclasses
import math
import random
import statistics
from typing import Dict, List, Optional

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Planned:
    index: int
    due_s: Optional[float]      # offset from window start; None = closed loop
    client: int                 # closed loop: which client sends it
    prompt_tokens: int
    max_tokens: int
    temperature: float
    prompt_seed: int


def _quantiles(dist: Dict, n: int) -> List[int]:
    """n stratified draws ((i + 0.5) / n quantiles) of a length
    distribution, clipped to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        elif dist["dist"] == "uniform":
            x = lo + (hi - lo) * u
        elif dist["dist"] == "fixed":
            x = dist["value"]
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(int(min(max(round(x), lo), hi)))
    return out


def _shuffled(values: List, rng: random.Random) -> List:
    values = list(values)
    rng.shuffle(values)
    return values


def _lengths(spec: Dict, rng: random.Random, n: int, stratum: int):
    """n (prompt, output, greedy) triples: whole strata of `stratum`
    quantiles, each stratum shuffled by the seed."""
    prompts, outs, greedy = [], [], []
    every = int(spec.get("greedy_every", 0))
    while len(prompts) < n:
        prompts += _shuffled(_quantiles(spec["prompt_tokens"], stratum), rng)
        outs += _shuffled(_quantiles(spec["max_tokens"], stratum), rng)
        greedy += _shuffled([every > 0 and i % every == 0
                             for i in range(stratum)], rng)
    return prompts[:n], outs[:n], greedy[:n]


def plan(spec: Dict, seed: int, seconds: float) -> List[Planned]:
    """The requests of one window. Open loop: every request due before
    `seconds`. Closed loop: `clients` queues, each long enough that no
    client runs dry inside the window."""
    words = random.Random(seed)         # prompt words, always the seed's
    # `schedule_seed` in the traffic file pins the ORDER of gaps and
    # lengths for every seed: near the knee the order alone decides
    # whether a queue forms (PERF.md, PR 23: the same multiset gave a
    # TTFT p95 of 539 ms in one order and 1574 ms in another)
    rng = random.Random(spec["schedule_seed"]) \
        if "schedule_seed" in spec else words
    temp = float(spec.get("temperature", 0.0))
    stratum = int(spec.get("stratum", 40))      # closed loop: a wave
    if spec["loop"] == "open":
        # exactly n = floor(rate x seconds) requests: one stratum of
        # exponential gaps in the seed's order. Their sum does not
        # depend on the order (and is under n / rate <= seconds), so
        # every seed has all n due inside the window
        rate = float(spec["rate_rps"])
        n = stratum = max(int(rate * seconds), 1)
        dues, t = [], 0.0
        for g in _shuffled([-math.log(1.0 - (i + 0.5) / n) / rate
                            for i in range(n)], rng):
            t += g
            dues.append(t)
        clients = [0] * n
    elif spec["loop"] == "closed":
        n_clients = int(spec["clients"])
        per_client = int(spec["requests_per_client"])
        n = n_clients * per_client
        dues = [None] * n
        clients = [i % n_clients for i in range(n)]
    else:
        raise ValueError(f"unknown loop {spec['loop']!r}")
    prompts, outs, greedy = _lengths(spec, rng, n, stratum)
    return [Planned(index=i, due_s=dues[i], client=clients[i],
                    prompt_tokens=prompts[i], max_tokens=outs[i],
                    temperature=0.0 if greedy[i] else temp,
                    prompt_seed=words.getrandbits(48))
            for i in range(n)]


def prefill_lengths(spec: Dict) -> List[int]:
    """The smallest and largest prompt this traffic can send: warm-up
    covers every prefill bucket between them."""
    d = spec["prompt_tokens"]
    if d["dist"] == "fixed":
        return [int(d["value"])] * 2
    return [int(d["min"]), int(d["max"])]
