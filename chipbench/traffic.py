"""The one traffic generator: reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and draws an open-loop arrival schedule.

Derived from ``serving/loadgen.make_trace`` (seeded exponential inter-arrival
gaps, prompt and output lengths from ranges, prompts of uniform random
token ids), with one change: every seed gets the same work. Each phase of a
run (warm-up, measured window, tail) holds ``round(rate * seconds)``
requests whose inter-arrival gaps are the exponential distribution's
quantiles, scaled to fill the phase exactly, and whose prompt and output
lengths are the mix's quantiles. The seed only permutes those three lists
independently and draws the token ids. So two seeds differ in the order of
the same arrivals and sizes, never in how much work falls in the window.

Length distributions: ``{"dist": "uniform" | "log_uniform", "lo": a,
"hi": b}`` (inclusive integer bounds).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the schedule's origin
    prompt: np.ndarray         # int32 token ids
    max_new: int
    phase: str


def load(name: str, directory: str = DIR) -> Dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a length distribution, as integers."""
    lo, hi, q = float(spec["lo"]), float(spec["hi"]), _quantiles(n)
    if spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo)
    elif spec["dist"] == "log_uniform":
        x = np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def schedule(mix: Dict, *, rate: float, seed: int, vocab: int,
             phases: Sequence[Tuple[str, float]]) -> List[Request]:
    """Requests for consecutive phases ``[(name, seconds), ...]`` at
    ``rate`` requests per second, sorted by due time."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    rng = np.random.default_rng(abs(int(seed)))
    out: List[Request] = []
    start = 0.0
    for phase, seconds in phases:
        n = max(int(round(rate * seconds)), 1)
        gaps = -np.log1p(-_quantiles(n))
        gaps *= seconds / gaps.sum()
        gaps = rng.permutation(gaps)
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        plen = rng.permutation(lengths(mix["prompt_len"], n))
        olen = rng.permutation(lengths(mix["output_len"], n))
        for t, p, o in zip(due, plen, olen):
            out.append(Request(float(t), rng.integers(0, vocab, int(p))
                               .astype(np.int32), int(o), phase))
        start += seconds
    return out


def fingerprint(reqs: Sequence[Request]) -> str:
    """sha256 over every field of every request."""
    h = hashlib.sha256()
    for r in reqs:
        h.update(f"{r.due!r}|{r.max_new}|{r.phase}|".encode())
        h.update(np.ascontiguousarray(r.prompt, np.int32).tobytes())
    return h.hexdigest()
