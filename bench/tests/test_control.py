"""The control, at a size a test run can hold: the plain reference in
float8 (the step below the configurations' bf16) put in the program's
place by `harness.run(control=True)`, the path `bench/run.py --control 1`
takes on the chip. It has to come out not correct where the program comes
out correct, under one limit.

At these widths the logits are smaller than at a cell's size, and so are
both gaps: sound runs read 0.0008-0.030, the control 0.10-0.29 (CPU, the
seeds below and 2**31 + 9), so the limit here is 0.06 in place of a cell's
0.5 (`bench/limits/`)."""
import pytest

import tiny

LIMIT = 0.06


@pytest.mark.parametrize("cell", ["yi9b.zipf64-poisson",
                                  "phi3mini.longctx-backlog",
                                  "yi9b.resident-backlog"])
def test_control_is_not_correct(cell):
    sound = tiny.run(cell, seed=5, limit=LIMIT)
    assert sound["correct"], sound["checks"]
    ctrl = tiny.run(cell, seed=5, limit=LIMIT, control=True)
    assert not ctrl["correct"], ctrl["checks"]
    assert ctrl["checks"]["logit_gap"]["value"] > \
        3 * sound["checks"]["logit_gap"]["value"]
