"""Arithmetic the metric readers share (``bench/metrics/<name>.py``).

Per-layer readers read the traced window: the steps that ran while it was
open (``ctx.steps``), the engine registry's change over it
(``ctx.series``) and the device trace (``ctx.trace``). A quantity that
cells of different traffic report under names of their own (``.batch``,
``.poisson``) is computed once here.
"""
from __future__ import annotations

import collections
import sys
from typing import Optional

from bench import flops
from bench.peaks import roofline_seconds


def roofline(ctx, kernel: str, calls_of_step) -> Optional[float]:
    """Percent of the kernel's device time that its work needs at the
    chip's peaks. ``calls_of_step(step)`` gives the (flops, bytes) of each
    of the kernel's calls in that decode step; each decode step makes the
    calls that the architecture's ``dims(mc)["calls"]`` gives for the
    kernel."""
    tr = ctx.trace
    calls = ctx.dims["calls"].get(kernel)
    if tr is None or kernel not in tr["kernels"] or not calls:
        return None
    n_calls, busy = tr["kernels"][kernel]
    steps = [s for s in ctx.steps if s.n_decode]
    if n_calls != calls * len(steps) or busy <= 0:
        print(f"bench: {kernel}: {n_calls} calls in the trace, "
              f"{calls * len(steps)} expected from {len(steps)} decode "
              f"steps; roofline not read", file=sys.stderr, flush=True)
        return None
    need, bounds = 0.0, set()
    for s in steps:
        for work, n in collections.Counter(calls_of_step(s)).items():
            t, bound = roofline_seconds(*work, ctx.peak)
            need += n * t
            bounds.add(bound)
    print(f"bench: {kernel}: {n_calls} calls, {busy:.6f} s on the device, "
          f"{need:.6f} s at the roofline, bound by {'/'.join(sorted(bounds))}",
          file=sys.stderr, flush=True)
    return 100.0 * need / busy


def window_flops(ctx) -> float:
    """Model FLOPs of every decode and prefill in the traced window."""
    mc = ctx.mc
    total = 0.0
    for s in ctx.steps:
        if s.n_decode:
            total += flops.decode_flops(mc, s.contexts)
        total += sum(flops.prefill_flops(mc, n) for n in s.prefill_lens)
    return total


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of every decode and prefill in the traced window, per
    second of it, as a percent of the chip's peak."""
    t0, t1 = ctx.span
    if t0 is None or t1 is None or not ctx.steps:
        return None
    rate = window_flops(ctx) / (t1 - t0)
    return 100.0 * rate / ctx.peak["flops_per_s"]


def idle_share(ctx) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device."""
    tr = ctx.trace
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def batch_occupancy(ctx) -> Optional[float]:
    """Mean share of the slots that decode, over the decode steps of the
    traced window."""
    steps = [s for s in ctx.steps if s.n_decode]
    if not steps:
        return None
    n = ctx.cell["n_slots"]
    return 100.0 * sum(s.n_decode for s in steps) / (n * len(steps))
