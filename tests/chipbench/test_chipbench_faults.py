"""Whole runs of the harness on the CPU, the chip look skipped, with the
timed path broken underneath: ``correct`` must come out false.  One run
per fault that a cell of this benchmark can have (its cells hold one chip,
so no exchange between chips can be left out)."""
from __future__ import annotations

import pytest

from chipbench import faults, spec


@pytest.mark.parametrize("fault,cell", [
    ("state_unchanged", "tiny-moe-train"),
    ("half_batch", "tiny-dense-train"),
    ("altered_token", "tiny-moe-serve"),
])
def test_fault_is_not_correct(run_cell, bench_copy, fault, cell):
    root, bench_path = bench_copy
    c = spec.load_cell(spec.load_json(bench_path), cell, root)
    line = run_cell(cell, 7, hooks=faults.hooks(fault, c))
    assert line["correct"] is False
    assert list(line)[-1] == "checks"
    assert any(v["value"] > v["limit"] for v in line["checks"].values())
