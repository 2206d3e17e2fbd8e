"""The control of every cell comes out not correct, at a size a CPU test
run holds: the cell's reference, one precision below the one its
configuration states, put in the program's place and judged by the run's
own comparison (``bench/control.py`` runs the same at the cell's own size
on the chip)."""
import pytest

import control
from small import CELLS, small_cell


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell, tmp_path):
    c = small_cell(cell)
    out = control.run(c, seed=2**33 + 5, seconds=2.0, scratch=str(tmp_path),
                      graph_cache=str(tmp_path / "graphs"))
    assert out["correct"] is False, out["checks"]
