"""Seeded open-loop traffic for the chip benchmark.

One general generator reads a mix file (`chipbench/traffic/<mix>.json`).
The length distributions follow the program's `serving/workload.py`
(`sharegpt_like`: lognormal prompt and output lengths clipped to a range);
this copy adds a uniform distribution for long documents and real token
ids, and draws everything without importing the program.

Every seed serves the same work: the arrival times and the multiset of
(prompt, output) lengths come from the mix's own `sizes_seed`, so they
are fixed for the mix. The run's `--seed` only permutes which lengths go
to which arrival slot (separately inside the warm-in and inside the
window, so the window always holds the same lengths) and draws the token
ids. Runs on different seeds then differ by order, not by amount of work.

A mix file holds:
  rate_rps      Poisson arrival rate, requests per second
  warm_in_s     seconds of traffic before the measured window (set-up)
  tail_s        seconds after the window that counted requests may take
                to get their first token before they count as failed
  prompt        {"dist": "lognormal", "mu", "sigma", "min", "max"} or
                {"dist": "uniform", "min", "max"} (tokens)
  output        the same, for output tokens
"""
from __future__ import annotations

import dataclasses
import random
from typing import List

import numpy as np


@dataclasses.dataclass
class TrafficRequest:
    rid: str
    due: float          # seconds after the warm-in starts
    prompt: List[int]
    output_len: int
    in_window: bool     # due inside the measured window


def _draw_len(rng: random.Random, spec: dict) -> int:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormvariate(float(spec["mu"]), float(spec["sigma"]))
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(max(x, lo), hi))


def _arrivals(rng: random.Random, rate: float, t0: float,
              t1: float) -> List[float]:
    out, t = [], t0
    while True:
        t += rng.expovariate(rate)
        if t >= t1:
            return out
        out.append(t)


def generate(mix: dict, seed: int, seconds: float,
             vocab_size: int) -> List[TrafficRequest]:
    """Requests due in [0, warm_in_s + seconds), sorted by due time."""
    rate = float(mix["rate_rps"])
    warm = float(mix["warm_in_s"])
    # arrivals and lengths of each phase come from the mix alone; the
    # window's stream is drawn apart so its work does not depend on the
    # warm-in's length
    phases = []
    for name, t0, t1 in (("warm", 0.0, warm),
                         ("window", warm, warm + seconds)):
        prng = random.Random(f"{mix.get('sizes_seed', 0)}:{name}")
        due = _arrivals(prng, rate, t0, t1)
        sizes = [(_draw_len(prng, mix["prompt"]),
                  _draw_len(prng, mix["output"])) for _ in due]
        phases.append((name == "window", due, sizes))
    order = random.Random(seed)
    tok = np.random.default_rng(seed)
    out: List[TrafficRequest] = []
    for in_window, due, sizes in phases:
        sizes = list(sizes)
        order.shuffle(sizes)
        for t, (p, o) in zip(due, sizes, strict=True):
            prompt = tok.integers(0, vocab_size, size=p).tolist()
            out.append(TrafficRequest(rid=f"q{len(out)}", due=t,
                                      prompt=prompt, output_len=o,
                                      in_window=in_window))
    return out

