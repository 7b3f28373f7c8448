"""End-to-end metrics from client-side timestamps, over the whole window.

Every timestamp is ``time.perf_counter()`` on the benchmark's host, taken
when the client receives the event. ``window`` is [t0, t1).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class ClientLog:
    """What one client saw of its request."""
    index: int
    due: Optional[float] = None      # scheduled send time (open loop)
    sent: Optional[float] = None     # when the client sent it
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None  # terminal event of a complete request
    failed: bool = False
    slot: Optional[int] = None       # the engine slot that served it


def _inside(t: float, window) -> bool:
    return window[0] <= t < window[1]


def output_tokens_per_s(logs: List[ClientLog], window) -> float:
    n = sum(_inside(t, window) for g in logs for t in g.token_times)
    return n / (window[1] - window[0])


def itl_samples(logs: List[ClientLog], window) -> np.ndarray:
    """Every gap between consecutive tokens of a request, both received
    inside the window."""
    gaps = [b - a for g in logs for a, b in zip(g.token_times,
                                                g.token_times[1:])
            if _inside(a, window) and _inside(b, window)]
    return np.asarray(gaps, np.float64)


def ttft_samples(logs: List[ClientLog], window) -> np.ndarray:
    """Scheduled send time to first token, for every request due inside the
    window. One still waiting at the window's end counts its wait so far."""
    out = []
    for g in logs:
        if g.due is None or not _inside(g.due, window):
            continue
        first = g.token_times[0] if g.token_times else None
        end = first if first is not None and first < window[1] else window[1]
        out.append(end - g.due)
    return np.asarray(out, np.float64)


def p95(samples: np.ndarray) -> Optional[float]:
    """The 95th percentile of all samples (numpy's linear interpolation);
    None without samples."""
    return float(np.percentile(samples, 95)) if len(samples) else None


def lateness(logs: List[ClientLog], window) -> dict:
    """How late the generator sent requests due inside the window."""
    late = np.asarray([g.sent - g.due for g in logs
                       if g.due is not None and g.sent is not None
                       and _inside(g.due, window)])
    if not len(late):
        return {}
    return {"n": int(len(late)), "p50_ms": float(np.median(late) * 1e3),
            "max_ms": float(late.max() * 1e3)}
