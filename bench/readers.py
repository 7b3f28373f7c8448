"""Arithmetic the metric readers share (``bench/metrics/<name>.py``).

Per-layer readers read the traced window: the steps that ran while it was
open (``ctx.steps``), the engine registry's change over it
(``ctx.series``) and the device trace (``ctx.trace``).
"""
from __future__ import annotations

import sys
from typing import Optional

from bench import flops
from bench.peaks import roofline_seconds


def roofline(ctx, kernel: str, work_of_step) -> Optional[float]:
    """Percent of the kernel's device time that its work needs at the
    chip's peaks. ``work_of_step(step)`` gives (flops, bytes) of one call
    in that step; each decode step makes the calls that the architecture's
    ``dims(mc)["calls"]`` gives for the kernel."""
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
        t, bound = roofline_seconds(*work_of_step(s), ctx.peak)
        need += calls * t
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
            total += flops.decode_flops(mc, s.n_decode, s.context_tokens)
        total += sum(flops.prefill_flops(mc, n) for n in s.prefill_lens)
    return total
