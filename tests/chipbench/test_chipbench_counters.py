"""The readers of the program's own counters: ``step_recompiles`` and
``prefetch_wait_ms.train``, on hand-built contexts and in a traced run of
a tiny cell."""
from __future__ import annotations

import sys
import types

import pytest

from chipbench import spec
from repro import obs
from repro.obs import runtime
from repro.obs.recorder import SpanRecorder


def _ctx(job: str, **counters) -> dict:
    return {"cell": types.SimpleNamespace(traffic={"job": job}),
            "counters": counters, "trace": None}


@pytest.fixture
def fresh(monkeypatch):
    """Empty process recorders, as a process that has compiled nothing."""
    compiles, data = SpanRecorder(), SpanRecorder(maxlen=runtime.MAXLEN)
    monkeypatch.setattr(runtime, "compiles", compiles)
    monkeypatch.setattr(runtime, "data", data)
    return compiles, data


@pytest.fixture
def no_recorder(monkeypatch):
    """A program without ``repro.obs.runtime``, as the parent commit."""
    monkeypatch.delattr(obs, "runtime")
    monkeypatch.setitem(sys.modules, "repro.obs.runtime", None)


def _compile(rec, name, times=1):
    for _ in range(times):
        rec.span("compile", "backend", 0, 1, fun_name=f"jit({name})")


def test_step_recompiles(fresh):
    compiles, _ = fresh
    read = spec.metric_reader(spec.ROOT, "step_recompiles.train")
    assert spec.metric_reader(spec.ROOT, "step_recompiles.serve") \
        .__module__ == read.__module__
    assert read(_ctx("train")) is None                 # nothing compiled
    _compile(compiles, "train_step")
    compiles.span("compile", "trace", 0, 1, fun_name="train_step")
    _compile(compiles, "make_state")
    assert read(_ctx("train")) == 0
    _compile(compiles, "train_step")
    assert read(_ctx("train")) == 1
    _compile(compiles, "prefill_step")
    assert read(_ctx("serve")) is None                 # decode never ran
    _compile(compiles, "decode_step", times=3)
    assert read(_ctx("serve")) == 2


def test_prefetch_wait(fresh):
    _, data = fresh
    read = spec.metric_reader(spec.ROOT, "prefetch_wait_ms.train")
    for i, wait in enumerate((0.5, 0.001, 0.003)):
        data.counter("prefetch_wait_s", i, wait)
        data.counter("prefetch_produce_s", i, 9.0)
    assert read(_ctx("train", steps=2)) == pytest.approx(2.0)
    assert read(_ctx("train", steps=3)) == pytest.approx(168.0)
    assert read(_ctx("train", steps=4)) is None        # fewer samples
    assert read(_ctx("train", steps=0)) is None
    assert read(_ctx("train")) is None


@pytest.mark.parametrize("metric", ["step_recompiles.train",
                                    "prefetch_wait_ms.train"])
def test_readers_without_the_recorder(no_recorder, metric):
    read = spec.metric_reader(spec.ROOT, metric)
    assert read(_ctx("train", steps=2)) is None


@pytest.mark.parametrize("cell,want", [
    ("tiny-moe-train", {"step_recompiles.train", "prefetch_wait_ms.train"}),
    ("tiny-moe-serve", {"step_recompiles.serve"})])
def test_traced_run_reports_the_counters(run_cell, fresh, cell, want):
    line = run_cell(cell, 2718281828, seconds=0.5, trace=1)
    got = line["metrics"]
    assert want <= set(got)
    for name in want & {"step_recompiles.train", "step_recompiles.serve"}:
        assert got[name] == {"value": 0, "unit": "count"}
    if "prefetch_wait_ms.train" in want:
        assert 0 <= got["prefetch_wait_ms.train"]["value"] \
            <= got["data_wait_ms.train"]["value"]
