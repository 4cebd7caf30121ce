"""The one traffic generator: turns a mix file into the requests of a run.

A run's timeline is ``[-ramp_s, window_s)`` seconds around the window's
open.  Arrival instants, priority classes, request sizes and which size
goes to which arrival come from the mix's own ``arrival_seed`` and so are
the same in every run of a cell; ``--seed`` only draws the prompts' token
ids.  Runs on different seeds then offer the same work, so their spread is
the system's and not the sampler's: with a window of some tens of requests,
the order of their sizes alone moved tokens per second far more than two
runs of one order differ.

Mix keys (all numbers in seconds, tokens or requests per second):

* ``classes``: ``[{priority, weight, share, phase}]``;
* ``kinds``: ``[{share, prompt: {mean, sigma, lo, hi}, output: {...}}]``,
  lognormal lengths with the given mean and sigma, clipped to [lo, hi];
* ``arrivals``: ``{"process": "sinusoid", "amplitude": A, "period_s": P}``
  (each class a non-homogeneous Poisson process with intensity
  ``rate*share*(1 + A sin(2 pi t/P + phase))``, drawn by thinning: the
  shape of ``repro.sim.workloads.industrial``, with a period of its own so
  that the window's length only cuts the same process) or
  ``{"process": "gamma", "shape": k}`` (gamma(k) gaps at the mean rate,
  the burstiness of ``repro.sim.workloads.azure``);
* ``rate_rps``, ``ramp_s``, ``slo``, ``w_p``, ``w_d``, ``arrival_seed``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
from statistics import NormalDist


@dataclass
class Req:
    due: float             # seconds from the window's open (< 0: ramp)
    prompt_len: int
    output_len: int
    priority: int
    weight: float
    prompt: np.ndarray     # int32 token ids, built before the ramp starts


def seed_words(seed: int) -> list[int]:
    """Any whole number as 32-bit words for numpy / JAX key data."""
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return [int(w) for w in state]


def _lognormal_quantiles(mean, sigma, lo, hi, n) -> np.ndarray:
    """n lengths at evenly spaced quantiles of the lognormal with this
    mean: the sample every run shares (stratified, not drawn)."""
    if n <= 0:
        return np.zeros(0, int)
    mu = math.log(mean) - sigma * sigma / 2.0
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.exp(mu + sigma * z), lo, hi).astype(int)


def _sinusoid_arrivals(seed, mix, rate, t0, t1):
    """Per class, a Poisson process at ``lam_max = rate*share*(1 + A)``
    thinned to the sinusoid's intensity.  Each class walks forward from
    ``t0`` on a generator of its own, so a longer window only adds
    arrivals after the shorter one's end."""
    arr = mix["arrivals"]
    amp, period = float(arr["amplitude"]), float(arr["period_s"])
    out = []
    for i, c in enumerate(mix["classes"]):
        rng = np.random.default_rng([int(seed), i])
        lam_max = rate * c["share"] * (1.0 + amp)
        t = t0
        while True:
            t += rng.exponential(1.0 / lam_max)
            if t >= t1:
                break
            if rng.uniform() * (1.0 + amp) < 1.0 + amp * math.sin(
                    2 * math.pi * t / period + c["phase"]):
                out.append((t, c))
    out.sort(key=lambda x: x[0])
    return out


def _gamma_arrivals(rng, mix, rate, t0, t1):
    k = mix["arrivals"]["shape"]
    times = []
    t = t0
    while True:
        t += rng.gamma(k, 1.0 / (k * rate))
        if t >= t1:
            break
        times.append(t)
    classes = mix["classes"]
    counts = _split(len(times), [c["share"] for c in classes])
    labels = np.repeat(np.arange(len(classes)), counts)
    rng.shuffle(labels)
    return [(t, classes[int(i)]) for t, i in zip(times, labels)]


def _split(n: int, shares) -> list[int]:
    """n items split by shares, the remainder to the largest fractions."""
    raw = [n * s / sum(shares) for s in shares]
    counts = [int(x) for x in raw]
    for i in sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])[
            :n - sum(counts)]:
        counts[i] += 1
    return counts


def build(mix: dict, window_s: float, seed: int, vocab: int,
          rate: float | None = None) -> list[Req]:
    """All requests due in ``[-ramp_s, window_s)``, sorted by due instant,
    with their prompts built."""
    rate = float(rate if rate is not None else mix["rate_rps"])
    ramp = float(mix["ramp_s"])
    fixed = np.random.default_rng(int(mix["arrival_seed"]))
    proc = mix["arrivals"]["process"]
    if proc == "sinusoid":
        slots = _sinusoid_arrivals(mix["arrival_seed"], mix, rate, -ramp,
                                   window_s)
    elif proc == "gamma":
        slots = _gamma_arrivals(fixed, mix, rate, -ramp, window_s)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    n = len(slots)
    sizes = []
    for kind, m in zip(mix["kinds"],
                       _split(n, [k["share"] for k in mix["kinds"]])):
        p, o = kind["prompt"], kind["output"]
        pl = _lognormal_quantiles(p["mean"], p["sigma"], p["lo"], p["hi"], m)
        ol = _lognormal_quantiles(o["mean"], o["sigma"], o["lo"], o["hi"], m)
        fixed.shuffle(ol)              # pair prompt and output sizes once
        sizes += list(zip(pl.tolist(), ol.tolist()))
    order = fixed.permutation(n)
    rng = np.random.default_rng(seed_words(seed))
    reqs = []
    for (due, c), j in zip(slots, order):
        pl, ol = sizes[j]
        reqs.append(Req(float(due), int(pl), max(1, int(ol)),
                        int(c["priority"]), float(c["weight"]),
                        rng.integers(1, vocab, pl).astype(np.int32)))
    return reqs
