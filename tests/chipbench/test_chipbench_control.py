"""The control at a size a test run holds: the reference computed in the
next precision below the configuration's (float8 e4m3 matmul operands, one
scale per tensor, for a bf16 program) put in the program's place must fail
a limit that the program itself meets, by a wide margin, in each kind of
tiny cell."""
from __future__ import annotations

import time

import pytest

from chipbench import common, spec


@pytest.mark.parametrize("cell", ["tiny-moe-train", "tiny-dense-train",
                                  "tiny-moe-serve"])
def test_control_fails_where_the_program_passes(bench_copy, no_compile_cache,
                                                 cell):
    root, bench_path = bench_copy
    c = spec.load_cell(spec.load_json(bench_path), cell, root)
    out = spec.job_module(c).run(c, 4242, 0.0, False,
                                 common.Clock(time.perf_counter()),
                                 control=True)
    assert out.correct, out.checks
    control = out.counters["control"]
    # some number that the control fails reads three times the program's
    assert any(control[k] > lim and control[k] > 3 * v
               for k, (v, lim) in out.checks.items())
