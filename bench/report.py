"""Turn one run's readings into the result line.

Every metric, end-to-end or per-layer, has a reader of its own,
``bench/metrics/<name>.py`` with ``read(ctx) -> float | None``, found by
the name in ``BENCHMARK.json``. A reader that finds nothing to read returns
None and the metric is left out of the line.
"""
from __future__ import annotations

import importlib.util
import math
from typing import Optional

from bench import arch, peaks
from bench.spec import BENCH

_loaded: dict = {}


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    key = (kind, name)
    if key not in _loaded:
        path = BENCH / kind / f"{name}.py"
        s = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]


class Context:
    """What a metric reader may read: the run's raw readings, the steps of
    the traced window, registry deltas over it, the trace's reduction."""

    def __init__(self, raw: dict):
        self.raw = raw
        self.spec = raw["spec"]
        self.mc, self.cell = self.spec.mc, self.spec.cell
        self.logs, self.window = raw["logs"], raw["window"]
        self.setup_s = raw["setup_s"]
        self.steps = [r for r in raw["records"] if r.traced]
        self.span = raw["span"]
        self.trace = raw["trace"]

    @property
    def dims(self) -> dict:
        """The model's sizes and kernel calls, from its architecture."""
        return arch.dims(self.mc)

    @property
    def peak(self) -> dict:
        return peaks.peaks(self.raw["device"]["kind"])

    def counts(self, kernel: str):
        return module("counts", kernel)

    def series(self, metric: str, field: str, **labels) -> Optional[float]:
        """Change of a registry series' ``field`` (sum / count / value)
        over the traced window."""
        s0, s1 = self.raw["snaps"]
        if s0 is None or s1 is None:
            return None
        key = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))

        def get(snap):
            m = snap.get(metric)
            if m is None:
                return 0.0
            ser = m.get("series", {}).get(key)
            if ser is None:
                return 0.0
            return float(ser if not isinstance(ser, dict) else ser[field])
        return get(s1) - get(s0)


def metrics(raw: dict, trace: bool) -> dict:
    ctx = Context(raw)
    entries = raw["spec"].per_layer if trace else raw["spec"].end_to_end
    out = {}
    for m in entries:
        v = module("metrics", m["name"]).read(ctx)
        if v is None or not math.isfinite(v):
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def attempted_failed(raw: dict) -> tuple:
    t0, t1 = raw["window"]
    live = [g for g in raw["logs"] if g.sent is not None and g.sent < t1
            and (g.finished is None or g.finished >= t0)]
    return len(live), sum(g.failed for g in live)


def result(raw: dict, trace: bool) -> dict:
    limit = raw["spec"].cell["mean_logit_gap_limit"]
    chk = raw["check"]
    n, failed = attempted_failed(raw)
    gap = chk["mean_logit_gap"]
    correct = chk["tokens_checked"] > 0 and gap <= limit
    line = {"correct": bool(correct), "attempted": n, "failed": failed,
            "metrics": metrics(raw, trace), "device": dict(raw["device"])}
    if trace and raw["trace"] is not None:
        tr = raw["trace"]
        line["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["top_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["compared"] = {
        "mean_logit_gap": {"value": gap, "limit": limit},
        "tokens_checked": {"value": int(chk["tokens_checked"]),
                           "limit": 1}}
    return line
