#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip. It makes the cell's weights on the device from
the seed, builds the serving engine with the cell's settings, warms every
shape the cell's traffic can produce, opens the window once the load is
steady, measures for ``--seconds``, then checks what the window served
against the plain float32 reference (``bench/reference.py``). The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device`` and, last, ``compared``: each number the check
compared, beside its limit. The same numbers end standard error.

A run that finds no TPU, fewer chips than the cell asks for, or an engine
that did not choose the Pallas decode kernels exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness, report, spec as spec_mod
    spec = spec_mod.load(args.workload)
    try:
        cache = harness.start(spec.chips)
        raw = harness.run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except RuntimeError as exc:
        print(f"bench: {args.workload}: {exc}", file=sys.stderr)
        return 1
    line = report.result(raw, bool(args.trace))
    log = harness.log
    log(f"compile cache {cache}; {raw['compiles']} compiles, "
        f"{raw['cache_hits']} cache hits, {raw['n_prefill_shapes']} prefill "
        f"shapes; compiles inside the window: {raw['compiles_in_window']}")
    from bench import traffic, e2e
    log(f"traffic {json.dumps(traffic.describe(raw['reqs']))}; generator "
        f"lateness {json.dumps(e2e.lateness(raw['logs'], raw['window']))}")
    chk = raw["check"]
    log(f"weights {raw['weight_bytes']} bytes; window "
        f"{raw['window'][1] - raw['window'][0]:.3f} s; checked "
        f"{chk['requests_checked']} requests from {chk['slots_checked']} "
        f"slots: widest logit gap "
        f"{chk['logit_gap']}, share off the reference's argmax "
        f"{chk['off_argmax']}")
    t0, t1 = raw["window"]
    steps = [r for r in raw["records"] if t0 <= r.t_start < t1]
    if steps:
        ms = sorted((r.t_end - r.t_start) * 1e3 for r in steps)
        log(f"{len(steps)} engine steps in the window: median "
            f"{ms[len(ms) // 2]:.2f} ms, max {ms[-1]:.2f} ms, "
            f"{sum(1 for r in steps if r.prefill_lens)} with a prefill")
    print(json.dumps({"compiles_in_window": raw["compiles_in_window"]}),
          flush=True)
    for name, c in line["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
