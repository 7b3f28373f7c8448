"""The control, at a size a test run holds: the reference computed one
precision below the served bfloat16 (float8 matmul operands) in the
program's place, over the same served prompts and tokens, fails the limit
that the program's own runs pass. On the chip the same readings, at each
cell's own size, set the cells' limits (``bench/calibrate.py``)."""
import pytest

import rehearsal

LIMIT = rehearsal.CELL["mean_logit_gap_limit"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_where_the_program_passes(monkeypatch, seed):
    raw = rehearsal.run(monkeypatch, seed, seconds=1.5, control=True)
    c = raw["check"]
    assert c["tokens_checked"] >= 256
    assert c["mean_logit_gap"] <= LIMIT < c["control_mean_logit_gap"]
