"""The one general generator: a traffic file's parameters + a seed -> inputs.

Every seed gets the SAME set of sizes and the SAME set of gaps between
arrivals, each in another order drawn freely from the seed: the sizes are the
distribution's quantiles at (i + 0.5) / n and the gaps the exponential
distribution's, so two seeds differ in what arrives when, in which long
prompts meet and in the tokens, not in how much work there is. The count of
arrivals is therefore fixed at round(rate x horizon): the process is a
Poisson process but for its count (index of dispersion of arrivals in 2 s
bins 1.0, as an independent draw gives; a free count and free sizes alone
would move tokens/s by 10 % between seeds, generator arithmetic). Nothing else is evened out: bursts,
and long prompts or long answers that fall together, come as the seed
deals them. Token ids are drawn from the seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """n whole sizes at the quantiles (i + 0.5) / n of the named distribution,
    clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif spec["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def arrival_offsets(spec: dict, n: int, horizon_s: float, rng: np.random.Generator) -> np.ndarray:
    """n arrival times in [0, horizon_s): the exponential distribution's n
    quantile gaps (mean 1 / rate), in an order drawn from the seed."""
    if spec["process"] != "exponential_gaps":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    t = np.cumsum(gaps) - gaps[0] * float(rng.random())
    return t * (horizon_s / (t[-1] + gaps.mean()))


def open_loop_requests(traffic: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    """-> {"ramp": [...], "window": [...]}; a request is (offset_s, prompt ids,
    output tokens). Ramp offsets count from the ramp's start, window offsets
    from the window's start."""
    rate = float(traffic["arrivals"]["rate_per_s"])
    rng = np.random.default_rng([int(seed), 0x5EED])
    out = {}
    for phase, horizon in (("ramp", float(traffic.get("ramp_s", 0.0))), ("window", float(seconds))):
        n = int(round(rate * horizon))
        if n == 0:
            out[phase] = []
            continue
        prompts = rng.permutation(quantile_sizes(traffic["prompt_tokens"], n))
        outputs = rng.permutation(quantile_sizes(traffic["output_tokens"], n))
        offsets = arrival_offsets(traffic["arrivals"], n, horizon, rng)
        reqs = []
        for off, p, o in zip(offsets, prompts, outputs):
            ids = rng.integers(3, vocab_size, size=int(p))
            reqs.append((float(off), ids.astype(np.int32).tolist(), int(o)))
        out[phase] = reqs
    return out


def lateness(due: list[float], sent: list[float]) -> dict:
    late = np.asarray(sent) - np.asarray(due)
    if late.size == 0:
        return {"n": 0}
    return {"n": int(late.size), "p50_s": float(np.percentile(late, 50)),
            "p95_s": float(np.percentile(late, 95)), "max_s": float(late.max())}


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile over ALL values given (inf counts as worst)."""
    if not values:
        return math.nan
    v = sorted(values)
    return float(v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))])
