"""The runnable examples stay runnable (fast ones, executed in-process)."""
import runpy

import pytest


@pytest.mark.parametrize("example", [
    "examples/quickstart.py",
    "examples/lenet_da_inference.py",
    pytest.param("examples/lenet_full_da.py", marks=pytest.mark.slow),
])
def test_example_runs(example, capsys):
    runpy.run_path(example, run_name="__main__")
    out = capsys.readouterr().out
    assert "✓" in out or "Table I" in out
