"""The chip benchmark: one cell (configuration x traffic mix) per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``bench/cells/<cell>.json`` holds its
engine settings and correctness limit, ``bench/configs/<config>.json`` the
model, whose ``model_type`` names ``bench/arch/<model_type>.py``, its
architecture (sizes, weight layout, plain reference, model FLOPs),
``bench/traffic/<mix>.json`` the traffic, ``bench/metrics/<metric>.py``
one per-layer metric's reader and ``bench/counts/<kernel>.py`` one kernel's
operations and bytes.
"""
