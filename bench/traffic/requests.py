"""The general request generator: every traffic mix of kind "requests".

A mix file gives, on the engine's step clock (one step = one decode step
of the batch):

    {"kind": "requests",
     "n_requests": 1200,
     "arrivals": {"process": "poisson", "rate_per_step": 0.1},
     "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                "min": 64, "max": 1536},
     "output": {"dist": "lognormal", "median": 160, "sigma": 0.7,
                "min": 32, "max": 512}}

Arrivals are a Poisson process: the gaps between them are drawn
independently from the exponential distribution at the rate, so the
count of arrivals in a stretch of steps varies as it does in real
traffic, bursts included.  The draw is one sample path, the same for
every seed (``ARRIVAL_KEY``).  Drawn anew for each seed, the arrivals in
a 51 s chat window varied by ~29% between seeds, and the tails read from
them by 15-25% on a TPU v5e, while two runs of one seed agreed within
0.1%: the seed changed the amount of work.  With one path it changes
the order of the work only.

Lengths are stratified: within each run of ``STRATUM`` consecutive
requests the prompt lengths (and, apart, the output lengths) are the
distribution's quantiles at (i + 1/2) / STRATUM, i = 0 .. STRATUM - 1,
once each, in an order the seed shuffles.  Every stretch of traffic then
holds the same mix of short and long requests (a window of ~40 chat
requests reads its p90 off the same few long prompts).  Token ids are
uniform over the vocabulary.

``generate`` returns plain dicts: ``rid``, ``prompt`` (int32 array),
``max_new``, ``arrival_step``.  All requests decode greedily and carry no
stop tokens, so each emits exactly ``max_new`` tokens.
"""
from __future__ import annotations

import numpy as np

STRATUM = 16      # requests over which each length distribution is spread
ARRIVAL_KEY = (0x5EED, 0xA771)   # generator of the one arrival path


def _stratified_u(rng: np.random.Generator, n: int) -> np.ndarray:
    """n quantile levels in (0, 1): each run of ``STRATUM`` holds the
    midpoints of the ``STRATUM`` equal slices once each, shuffled."""
    out = np.empty(n)
    for s in range(0, n, STRATUM):
        m = min(STRATUM, n - s)
        out[s:s + m] = rng.permutation((np.arange(m) + 0.5) / m)
    return out


def _norm_ppf(u: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    u = np.asarray(u, np.float64)
    x = np.empty_like(u)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(u[lo]))
    x[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
             + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - u[hi]))
    x[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
              + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = u[mid] - 0.5
    r = q * q
    x[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
              + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                              + b[4]) * r + 1)
    return x


def lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at quantiles `u` of the lognormal distribution `spec`,
    clipped to its ``min`` and ``max``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(u))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_steps(spec: dict, n: int) -> np.ndarray:
    """Arrival steps of `n` requests: the Poisson path at
    ``rate_per_step``."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    gaps = np.random.default_rng(ARRIVAL_KEY).exponential(
        1.0 / float(spec["rate_per_step"]), n)
    return np.floor(np.cumsum(gaps)).astype(np.int64)


def generate(mix: dict, seed: int, vocab: int) -> list[dict]:
    """The requests of `mix` for `seed`, as plain dicts."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x5EED])
    n = int(mix["n_requests"])
    p_len = lengths(mix["prompt"], _stratified_u(rng, n))
    o_len = lengths(mix["output"], _stratified_u(rng, n))
    arr = arrival_steps(mix["arrivals"], n)
    return [{"rid": i, "prompt": rng.integers(0, vocab, int(p_len[i]),
                                              np.int32),
             "max_new": int(o_len[i]), "arrival_step": int(arr[i])}
            for i in range(n)]


def max_tokens(mix: dict) -> int:
    """The longest prompt plus output the mix can draw."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])
