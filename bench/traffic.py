"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<mix>.json``) states the arrival process, the
length distributions and the share of sampled requests; the cell
(``bench/cells/<cell>.json``) states the rate where the arrivals are open.
Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps: they are quantiles of the mix's distributions, so the
work in a window does not change with the seed. The seed sets their order,
which requests sample, and every token of every prompt.

Arrival processes:

- ``backlog``: offline batch generation. ``n_slots + backlog_extra``
  requests are outstanding from the start, and each that finishes is
  replaced by the next, so the queue never runs dry. The first wave's
  output lengths are cut to (i + 1) / n_slots of their draw, so slots free
  at mixed times soon after the batch fills.
- ``poisson``: open-loop arrivals at ``rate_per_s``: ``warmup_s`` of them
  before the measured window, then ``seconds`` of them inside it, each
  phase's gaps the exponential quantiles at that rate, shuffled and
  scaled to fill the phase exactly.

A mix may fix its ``schedule_seed``: the order of the lengths, the arrival
gaps and which requests sample are then one trace, drawn from that seed
and replayed by every run, and the run's seed draws only the tokens and
the sampling seeds. An open-loop tail over a few tens of requests swings
with the order in which the seed shuffles the same gaps and lengths;
replaying one trace leaves it the step times to measure.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray            # int32 token ids
    max_new: int
    sampling: Optional[dict]      # None = greedy
    due: Optional[float] = None   # seconds after the schedule starts


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def _pool(mix: dict, n: int, seed: int, vocab: int,
          order_seed: int) -> List[Request]:
    order = _rng(order_seed, 1)
    prompts = order.permutation(lognormal_lengths(mix["prompt"], n))
    outs = order.permutation(lognormal_lengths(mix["output"], n))
    n_sampled = round(mix["sampled_share"] * n)
    sampled = order.permutation(np.arange(n) < n_sampled)
    tok = _rng(seed, 2)
    reqs = []
    for i in range(n):
        sp = None
        if sampled[i]:
            sp = dict(mix["sampling"], seed=int(tok.integers(1 << 31)))
        reqs.append(Request(i, tok.integers(0, vocab, int(prompts[i]),
                                            dtype=np.int32),
                            int(outs[i]), sp))
    return reqs


def generate(mix: dict, cell: dict, seed: int, seconds: float,
             vocab: int) -> List[Request]:
    """The requests of one run, in the order they are offered."""
    order_seed = mix.get("schedule_seed", seed)
    if mix["arrival"] == "backlog":
        reqs = _pool(mix, mix["pool"], seed, vocab, order_seed)
        n = cell["n_slots"]
        for i, r in enumerate(reqs[:n]):
            r.max_new = max(1, math.ceil(r.max_new * (i + 1) / n))
        return reqs
    if mix["arrival"] == "poisson":
        rate = cell["rate_per_s"]
        phases = [(0.0, mix["warmup_s"]), (mix["warmup_s"], seconds)]
        counts = [max(1, round(rate * length)) for _, length in phases]
        reqs = _pool(mix, sum(counts), seed, vocab, order_seed)
        order = _rng(order_seed, 3)
        i = 0
        for (start, length), n in zip(phases, counts):
            gaps = order.permutation(exponential_gaps(n, rate))
            t = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            t = start + (t - start) * (length / gaps.sum())
            for due in t:
                reqs[i].due = float(due)
                i += 1
        return reqs
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


def describe(reqs: List[Request]) -> dict:
    """Length distributions and the sampled share of a request list."""
    def summary(xs):
        xs = np.asarray(xs)
        return {"min": int(xs.min()), "p50": float(np.median(xs)),
                "p90": float(np.percentile(xs, 90)), "max": int(xs.max()),
                "mean": float(xs.mean())}
    return {"n": len(reqs),
            "prompt": summary([len(r.prompt) for r in reqs]),
            "output": summary([r.max_new for r in reqs]),
            "sampled": sum(r.sampling is not None for r in reqs)}
