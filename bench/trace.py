"""Reduce a profiler trace of the window to the numbers the readers take.

The harness traces whole engine steps, each wrapped in a
``StepTraceAnnotation("engine_step", step_num=i)`` whose ``i`` indexes its
step records. From the trace this reads:

- the window: first step annotation's start to the last one's end;
- busy time: the union of the intervals in which an operation ran on the
  device (the device planes' "XLA Ops" line, without control-flow ops,
  which span their bodies' ops and the gaps between them), clipped to the
  window and averaged over the devices that ran anything;
- per kernel (one per ``bench/counts/<kernel>.py``): calls and device time
  of the instructions named after it (a Pallas call's ``name``);
- the ops that took most time, and the longest idle gaps, each labelled with what the
  host was doing: inside an engine step (and whether that step
  prefilled), or between steps.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

from bench.spec import BENCH

STEP = "engine_step"
TOP = 10


def kernel_names() -> List[str]:
    return sorted(os.path.basename(p)[:-3]
                  for p in glob.glob(str(BENCH / "counts" / "*.py"))
                  if not p.endswith("__init__.py"))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def op_name(name: str) -> str:
    """An "XLA Ops" event is named by its HLO instruction text,
    ``%copy.72 = bf16[24,257,32,64,64]{...} copy(...)``: the instruction's
    name and its result's first shape, without layouts or operands."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    shape = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(")
    return f"{head} {shape}" if shape else head


def _kernel_of(name: str, names) -> Optional[str]:
    """The kernel whose instruction this is: its name, before the ".N"
    XLA appends (operands that name a kernel do not count)."""
    inst = name.partition(" = ")[0].lstrip("%").rsplit(".", 1)[0]
    return inst if inst in names else None


def _is_container(name: str) -> bool:
    """Control-flow instructions span the ops of their bodies."""
    inst = name.partition(" = ")[0].lstrip("%")
    return inst.startswith(("while", "conditional", "call"))


def reduce(pd, records) -> Optional[dict]:
    """``pd``: a ``jax.profiler.ProfileData``; ``records``: the harness's
    step records, indexed by the annotations' step_num."""
    names = kernel_names()
    steps = []  # (start_s, end_s, step_num)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == STEP:
                    num = next((v for k, v in ev.stats if k == "step_num"),
                               None)
                    steps.append((ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  None if num is None else int(num)))
    steps.sort()
    # an instruction's text -> (kernel or None, its name in the breakdown),
    # or None for a control-flow op: parsed once per distinct text, since a
    # window repeats the same few thousand instructions every step
    kinds: Dict[str, Optional[tuple]] = {}

    def kind(name: str) -> Optional[tuple]:
        if name not in kinds:
            k = _kernel_of(name, names)
            kinds[name] = None if _is_container(name) else (
                k, k or op_name(name))
        return kinds[name]

    ops_by_dev = []  # per device: (start_ns, duration_ns, kernel, name)
    for plane in devices:
        evs = []
        for ln in plane.lines:
            if ln.name != "XLA Ops":
                continue
            for ev in ln.events:
                kd = kind(ev.name)
                if kd is not None:
                    evs.append((ev.start_ns, ev.duration_ns) + kd)
        if evs:
            ops_by_dev.append(evs)
    if not ops_by_dev:
        return None
    if steps:
        lo, hi = steps[0][0], max(s[1] for s in steps)
    else:
        lo = min(ev[0] for evs in ops_by_dev for ev in evs) * 1e-9
        hi = max(ev[0] + ev[1] for evs in ops_by_dev for ev in evs) * 1e-9
    busy_each, gaps = [], []
    kernels: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0, 0.0])
    op_time: Dict[str, float] = collections.Counter()
    for evs in ops_by_dev:
        iv = _union(_clip([(start * 1e-9, (start + dur) * 1e-9)
                           for start, dur, _, _ in evs], lo, hi))
        busy_each.append(sum(b - a for a, b in iv))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for start, dur, k, op in evs:
            if not lo <= start * 1e-9 < hi:
                continue
            if k is not None:
                kernels[k][0] += 1
                kernels[k][1] += dur * 1e-9
            op_time[op] += dur * 1e-9
    n_dev = len(ops_by_dev)

    # one engine runs its steps one after another: they do not overlap, so
    # the step that holds a time is the last one that starts at or before it
    starts = [s[0] for s in steps]

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < steps[i][1]:
            num = steps[i][2]
            rec = records[num] if num is not None and num < len(
                records) else None
            if rec is not None and rec.prefill_lens:
                return "engine.step (prefill + decode)"
            return "engine.step (decode)"
        return "between engine steps"

    # split each idle interval where the host changed state
    cuts = sorted({t for s0, s1, _ in steps for t in (s0, s1)})
    split = []
    for a, b in gaps:
        pts = ([a] + cuts[bisect.bisect_right(cuts, a):
                          bisect.bisect_left(cuts, b)] + [b])
        split += list(zip(pts, pts[1:]))
    gaps = sorted(split, key=lambda g: g[0] - g[1])
    idle_by = collections.Counter()
    for a, b in gaps:
        idle_by[label(a, b)] += (b - a) / n_dev
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_each) / n_dev,
        "devices": n_dev,
        "steps": len(steps),
        "kernels": {k: (int(n), t / n_dev) for k, (n, t) in kernels.items()},
        "top_ops": [[k, t / n_dev] for k, t in op_time.most_common(TOP)],
        "idle_gaps": [[label(a, b), b - a] for a, b in gaps[:TOP]],
        "idle_by_host_state": dict(idle_by),
    }
