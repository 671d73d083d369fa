"""The control: the program with its own lower-precision path switched on
(4 of 8 activation bit-planes) serves through the harness's own run, and
``check.judge`` calls it not correct where the program is correct, at a
size a test run holds (CPU, kernels interpreted)."""
import pytest

from bench import catalog


@pytest.mark.parametrize("cell", ["qwen3-8b.chat-backlog"])
def test_control_fails_where_the_program_passes(cell):
    from bench.control import one_run

    limit = catalog.limits(cell)["max_logit_gap"]["rehearsal_limit"]
    seed = 2**31 + 5
    prog = one_run(cell, seed, 2.0, rehearse=True)
    ctrl = one_run(cell, seed, 2.0, x_bits=4, rehearse=True)
    assert prog["checks"]["sampled_tokens"]["value"] > 0
    assert ctrl["checks"]["sampled_tokens"]["value"] > 0
    assert prog["correct"] is True
    assert prog["checks"]["max_logit_gap"]["value"] <= limit
    assert ctrl["correct"] is False
    assert ctrl["checks"]["max_logit_gap"]["value"] > limit
