"""Traffic generator: turns a mix file (``bench/traffic/<mix>.json``) and a
run seed into the requests a run serves.

The sizes are the mix's, not the seed's: the multiset of (prompt length,
output length) pairs and the multiset of inter-arrival gaps are drawn once
from the mix's own ``shape_seed``, and the run seed only orders them and
draws the token ids.  So every seed asks the same work of the system, and
runs with different seeds differ by order alone.

Mix keys:

``loop``            ``"closed"`` (a saturated backlog: the queue is kept
                    ``backlog`` requests deep) or ``"open"`` (Poisson
                    arrivals at ``rate_rps``, sent on schedule).
``slots`` / ``capacity`` / ``page_size`` / ``prefill_chunk``
                    the engine the mix is served by.
``prompt``          ``{"buckets": [...], "weights": [...]}``: prompt lengths
                    come from a few fixed buckets, so each compiles once.
``output``          ``{"median", "sigma", "min", "max"}``: a lognormal
                    output length, clipped.
``requests``        how many requests the fixed multiset holds, about as
                    many as one run serves (it cycles through it again in
                    a fresh order if it needs more).
``shape_seed``      fixes which prompt length goes with which output
                    length and gap.
``stagger``         the first ``stagger`` requests keep the shares
                    1/stagger ... 1 of their output length, so slots free
                    at spread times.
``warmup_s``        seconds served after set-up, before the window opens.
``trace_s``         seconds of the window a ``--trace 1`` run profiles.
``sample``          finished requests the correctness check replays.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List

import numpy as np

LOOPS = ("closed", "open")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    slots: int
    capacity: int
    page_size: int
    prefill_chunk: int
    prompt_buckets: tuple
    prompt_weights: tuple
    out_median: float
    out_sigma: float
    out_min: int
    out_max: int
    requests: int
    shape_seed: int
    stagger: int = 0
    backlog: int = 2
    rate_rps: float = 0.0
    warmup_s: float = 0.0
    trace_s: float = 4.0
    sample: int = 4

    def __post_init__(self):
        if self.loop not in LOOPS:
            raise ValueError(f"mix {self.name}: loop {self.loop!r} is not "
                             f"one of {LOOPS}")
        if self.loop == "open" and not self.rate_rps > 0:
            raise ValueError(f"mix {self.name}: an open loop needs "
                             f"rate_rps > 0")
        if len(self.prompt_buckets) != len(self.prompt_weights):
            raise ValueError(f"mix {self.name}: one weight per bucket")
        longest = max(self.prompt_buckets) + self.out_max
        if longest > self.capacity:
            raise ValueError(
                f"mix {self.name}: the longest request ({longest} tokens) "
                f"does not fit the capacity {self.capacity}")


def load_mix(path) -> Mix:
    path = Path(path)
    doc = json.loads(path.read_text())
    out = doc["output"]
    return Mix(
        name=path.stem, loop=doc["loop"], slots=int(doc["slots"]),
        capacity=int(doc["capacity"]), page_size=int(doc["page_size"]),
        prefill_chunk=int(doc["prefill_chunk"]),
        prompt_buckets=tuple(int(b) for b in doc["prompt"]["buckets"]),
        prompt_weights=tuple(float(w) for w in doc["prompt"]["weights"]),
        out_median=float(out["median"]), out_sigma=float(out["sigma"]),
        out_min=int(out["min"]), out_max=int(out["max"]),
        requests=int(doc["requests"]), shape_seed=int(doc["shape_seed"]),
        stagger=int(doc.get("stagger", 0)),
        backlog=int(doc.get("backlog", 2)),
        rate_rps=float(doc.get("rate_rps", 0.0)),
        warmup_s=float(doc.get("warmup_s", 0.0)),
        trace_s=float(doc.get("trace_s", 4.0)),
        sample=int(doc.get("sample", 4)))


@dataclasses.dataclass
class Planned:
    """One request of the plan: its prompt, its output budget and, in an
    open loop, when it is due (seconds after the schedule starts)."""
    index: int
    prompt: List[int]
    max_new: int
    due_s: float = 0.0


def shapes(mix: Mix):
    """The mix's fixed multiset, ``mix.requests`` entries each: prompt
    lengths in the buckets' exact proportions, output lengths at evenly
    spaced quantiles of the clipped lognormal, open-loop gaps at evenly
    spaced quantiles of the exponential, each list in an order fixed by
    ``shape_seed``.  No run seed enters."""
    n = mix.requests
    rng = np.random.default_rng(mix.shape_seed)
    w = np.asarray(mix.prompt_weights, np.float64)
    counts = np.floor(w / w.sum() * n + 0.5).astype(np.int64)
    counts[np.argmax(w)] += n - counts.sum()
    prompts = np.repeat(np.asarray(mix.prompt_buckets, np.int64), counts)
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([_normal_quantile(p) for p in q])
    outs = np.exp(math.log(mix.out_median) + mix.out_sigma * z)
    outs = np.clip(np.rint(outs), mix.out_min, mix.out_max).astype(np.int64)
    gaps = (-np.log1p(-q) / mix.rate_rps if mix.loop == "open"
            else np.zeros(n))
    return rng.permutation(prompts), rng.permutation(outs), \
        rng.permutation(gaps)


def _normal_quantile(p: float) -> float:
    """Inverse of the standard normal CDF (bisection on ``math.erf``)."""
    lo, hi = -10.0, 10.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        if 0.5 * (1.0 + math.erf(mid / math.sqrt(2.0))) < p:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def seed_rng(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed (negative and > 64-bit ones
    included)."""
    return np.random.default_rng(np.random.SeedSequence(abs(int(seed))))


def plan(mix: Mix, seed: int, vocab: int):
    """The run's requests, in order, without end: the multiset in an order
    drawn from ``seed``, then again in a fresh order, and so on.  The
    first ``stagger`` requests keep the shares 1/stagger, 2/stagger, ...
    of their output length, in an order drawn from the seed."""
    prompts, outs, gaps = shapes(mix)
    rng = seed_rng(seed)
    share = (rng.permutation(mix.stagger) + 1) / max(mix.stagger, 1)
    i, t = 0, 0.0
    while True:
        for j in rng.permutation(mix.requests):
            max_new = int(outs[j])
            if i < mix.stagger:
                max_new = max(1, int(math.ceil(share[i] * max_new)))
            t += float(gaps[j])
            toks = rng.integers(0, vocab, int(prompts[j])).tolist()
            yield Planned(i, toks, max_new, t)
            i += 1
