"""Shared set-up of the chip benchmark's CPU tests.

``bench_copy`` builds, in a temporary directory, a copy of the benchmark's
root with the tiny cells of ``data/`` added as files, and a
``BENCHMARK.json`` that names them: a whole run of the harness on the CPU,
at the program's smoke widths, drives it.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CELLS = [
    {"name": "tiny-moe-train", "config": "tiny-moe", "traffic": "tiny-train",
     "chips": 1, "why": "MoE duplex step at smoke widths"},
    {"name": "tiny-dense-train", "config": "tiny-dense",
     "traffic": "tiny-train", "chips": 1,
     "why": "dense duplex step at smoke widths"},
    {"name": "tiny-moe-serve", "config": "tiny-moe", "traffic": "tiny-serve",
     "chips": 1, "why": "prefill and decode rounds at smoke widths"},
]


def build_copy(tmp: Path) -> tuple[Path, Path]:
    """(root, BENCHMARK.json) of a copy with the tiny cells added."""
    root = tmp / "chipbench"
    shutil.copytree(REPO / "chipbench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "workloads"):
        for f in (DATA / sub).glob("*.json"):
            shutil.copy(f, root / sub / f.name)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [c["name"] for c in TINY_CELLS]
    bench["workloads"] += TINY_CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "serve" if any("serve" in w for w in m["workloads"]) \
                else "train"
            m["workloads"] += [n for n in names if kind in n]
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(bench, indent=1))
    return root, path


@pytest.fixture
def bench_copy(tmp_path):
    return build_copy(tmp_path)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep the harness's runs in a test out of the persistent cache."""
    import jax
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.fixture
def run_cell(bench_copy, no_compile_cache, capsys):
    """``run_cell(cell, seed, hooks=None)``: one whole run of the harness
    on a tiny cell, with the chip look skipped; returns the result line."""
    from chipbench import run
    root, bench_path = bench_copy

    def go(cell, seed, hooks=None, seconds=0.0, trace=0):
        capsys.readouterr()
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      look_for_chip=False, hooks=hooks, root=root,
                      bench_path=bench_path)
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go
