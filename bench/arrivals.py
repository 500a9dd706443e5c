"""Arrival processes of the serving mixes, chosen by a mix's ``arrivals``.

``poisson``: the due times of a Poisson process conditioned on ``n``
arrivals in the window (``n`` sorted uniform draws).  The set of gaps is
drawn once, from a fixed generator, and the run's seed only orders it:
every seed offers the same gaps, so the tail a seed reads does not hang
on how bursty its own draw happened to be.

``bursty``: the Markov-modulated Poisson process of
``repro.serve.traffic.bursty_arrivals`` (copied here so the yardstick
stays with the benchmark): the rate is ``burst_factor`` times the mean
for a ``duty`` share of each ``period_s`` and a compensating low rate
otherwise; a gap drawn past a phase boundary is redrawn from the
boundary (exact for exponential gaps).  Its ``n`` arrivals are scaled
onto the window.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def due_times(mix: Dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    kind = mix["arrivals"]
    if kind == "poisson":
        u = np.sort(np.random.default_rng(0).uniform(0.0, seconds, size=n))
        return np.cumsum(rng.permutation(np.diff(u, prepend=0.0)))
    if kind == "bursty":
        t = _bursty(mix["rate_hz"], n, rng, burst_factor=mix["burst_factor"],
                    period_s=mix["period_s"], duty=mix["duty"])
        return t * (seconds / max(t[-1], 1e-9))
    raise ValueError(f"unknown arrivals {kind!r}; known: poisson, bursty")


def _bursty(rate_hz: float, n: int, rng: np.random.Generator, *,
            burst_factor: float, period_s: float, duty: float) -> np.ndarray:
    if not 0.0 < duty < 1.0:
        raise ValueError(f"duty must be in (0, 1): {duty}")
    lo_factor = max(1e-3, (1.0 - duty * burst_factor) / (1.0 - duty))
    times, t = [], 0.0
    while len(times) < n:
        phase = t % period_s
        on = phase < duty * period_s
        lam = rate_hz * (burst_factor if on else lo_factor)
        to_boundary = (duty * period_s if on else period_s) - phase
        gap = rng.exponential(1.0 / lam)
        if gap >= to_boundary:
            t += to_boundary
            continue
        t += gap
        times.append(t)
    return np.asarray(times)
