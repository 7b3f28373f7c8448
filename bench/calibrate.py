#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in one process: the whole timed path of a run at the cell's
own size and load (weights, engine, warm-up, a window of ``--seconds``),
then, over the same sample of served greedy tokens, the program's logit
gaps against the float32 reference (mean, widest, share off the
reference's argmax) and the control's: the gaps of the tokens the float8
reference puts first. The control's readings then go through the result
line in the program's place, and ``control_correct`` says what that line
decided; ``correct`` is the program's own line. One JSON line per seed.
The limit goes above the largest program reading and below the smallest
control reading (``PERF.md`` gives both and the limit). The benchmark's
own runs never run the control.
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
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()

    from bench import harness, report, spec as spec_mod
    spec = spec_mod.load(args.workload)
    try:
        harness.start(spec.chips)
    except RuntimeError as exc:
        print(f"calibrate: {args.workload}: {exc}", file=sys.stderr)
        return 1
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        raw = harness.run_cell(spec, seed, args.seconds, False, t_start=t,
                               control=True)
        chk = raw["check"]
        as_control = dict(raw, check=dict(
            chk, mean_logit_gap=chk["control_mean_logit_gap"]))
        print(json.dumps({
            "workload": args.workload, "seed": seed, **chk,
            "correct": report.result(raw, False)["correct"],
            "control_correct": report.result(as_control, False)["correct"],
            "setup_s": raw["setup_s"],
            "memory_peak_bytes": raw["device"]["memory_peak_bytes"]}),
            flush=True)
        del raw, as_control
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
