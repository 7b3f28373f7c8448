"""One module per decoder architecture, ``bench/arch/<model_type>.py``,
found by the configuration file's ``"model_type"`` (the key of the
model's own ``config.json``). A module imports nothing of the program and
reads the file under the file's own key names. It gives:

- ``dims(mc)``: ``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads``,
  ``head_dim``, ``d_ff``, ``n_proj`` (projections through an FFN tile),
  ``vocab_size``, ``init_std`` and ``calls``: each kernel's calls in one
  decode step; and, where layers attend to a window of their last keys,
  ``windows``: one entry per layer, 0 for full attention;
- ``program_fields(mc)``: the program's ``ModelConfig`` fields that the
  file fixes (``bench/harness.py`` checks each against the registry);
- ``shapes(mc)``: weight leaf -> (shape, fill) or, for a leaf the program
  pads, (shape, fill, (axis, real entries)), for ``bench/weights.py``;
- ``logits_at(w, tokens, rows, *, mc, control)``: the plain float32
  forward pass, or its control, for ``bench/reference.py``.

``bench/flops.py`` counts a decoder's FLOPs from ``dims`` alone.
"""
from __future__ import annotations

import re

from bench.spec import BENCH

_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def load(mc: dict):
    """The architecture module that ``mc["model_type"]`` names. A file
    without the key, or one that names no module, is refused."""
    from bench import report
    if "model_type" not in mc:
        raise ValueError("the configuration file has no 'model_type': it "
                         "names its architecture module, bench/arch/"
                         "<model_type>.py")
    name = mc["model_type"]
    path = BENCH / "arch" / f"{name}.py"
    if not _NAME.match(str(name)) or not path.is_file():
        raise ValueError(f"'model_type' {name!r} names no architecture "
                         f"module: bench/arch/{name}.py is missing")
    return report.module("arch", name)


def dims(mc: dict) -> dict:
    return load(mc).dims(mc)
