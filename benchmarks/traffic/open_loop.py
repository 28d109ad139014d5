"""Serving traffic: an open loop of independent requests on a schedule.

A corrected copy of ``paddle_tpu/serving/loadgen.py`` (which stays as it
is): every request has a time at which it is DUE, latency is counted from
that time and not from when the generator got round to sending it, how late
each was sent is reported, lengths are heavy-tailed, and nothing reaches
into a router.

Parameters (a traffic file with ``"generator": "open_loop"``):
``rate_rps``; ``prompt`` and ``output`` as ``{"median", "sigma", "min",
"max"}`` of a log-normal clipped to the range; ``temperature``, ``top_k``,
``top_p``; ``drain`` (whether the run waits for the judged requests to
finish) and ``drain_cap_s`` (how long at most).

Every seed gets the same work in another order.  The ``n = rate x seconds``
requests due in the window take the n mid-quantiles of the exponential gap
and of each length distribution, each list shuffled by the seed: the
arrival process looks Poisson and the lengths log-normal, but no seed draws
a heavier window than another.  After the window arrivals go on at the same
rate, uncounted, for ``drain_cap_s``, so the judged requests finish under
the load they started under.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass

import numpy as np


@dataclass
class Arrival:
    rid: str
    due_s: float
    prompt_ids: np.ndarray
    max_new_tokens: int
    judged: bool


def _mid_quantiles(n):
    return (np.arange(n) + 0.5) / n


def _lengths(spec, n, rng):
    z = np.array([statistics.NormalDist().inv_cdf(u)
                  for u in _mid_quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    rng.shuffle(x)
    return x


def _arrivals(rate, n, start, span, rng):
    gaps = -np.log1p(-_mid_quantiles(n)) / rate
    gaps *= (span - 0.5 / rate) / gaps.sum()
    rng.shuffle(gaps)
    return start + np.cumsum(gaps)


def make(params, vocab_size, seed, seconds):
    """The arrivals of one run, sorted by due time: those due inside
    ``seconds`` are judged, those after it keep the load up."""
    rng = np.random.default_rng(seed)
    rate = float(params["rate_rps"])
    out = []
    parts = [(max(1, round(rate * seconds)), 0.0, seconds, True)]
    cap = float(params.get("drain_cap_s", 0.0)) if params.get("drain") else 0.0
    if cap > 0:
        parts.append((max(1, round(rate * cap)), seconds, cap, False))
    for n, start, span, judged in parts:
        due = _arrivals(rate, n, start, span, rng)
        prompts = _lengths(params["prompt"], n, rng)
        outputs = _lengths(params["output"], n, rng)
        for j in range(n):
            out.append(Arrival(
                rid=f"{'j' if judged else 'u'}{j}", due_s=float(due[j]),
                prompt_ids=rng.integers(1, vocab_size, size=int(prompts[j]),
                                        dtype=np.int32),
                max_new_tokens=int(outputs[j]), judged=judged))
    return out
