"""BENCHMARK.json and the files it names, without a measurement."""
from __future__ import annotations

import hashlib
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
ROOT = REPO / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == TOP
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (REPO / p).is_dir()
    cmd = BENCH["command"]
    assert len(cmd) <= 32 and not any(w.startswith("/") or ".." in w
                                      for w in cmd)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_its_files(cell):
    from chipbench import spec
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    c = spec.load_cell(BENCH, cell, ROOT)
    assert (ROOT / "jobs" / f"{c.traffic['job']}.py").is_file()
    assert (ROOT / "reference" / f"{c.config['reference']}.py").is_file()
    assert c.limits and all(v > 0 for v in c.limits.values())
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported
        assert spec.metric_path(ROOT, m["name"]).is_file()


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCH["configs"]])
def test_config_file_is_used_and_stands_alone(cfg):
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg)
    assert entry["file"] == f"chipbench/configs/{cfg}.json"
    data = json.loads((REPO / entry["file"]).read_text())
    assert data["source"] == entry["source"]
    assert any(w["config"] == cfg for w in BENCH["workloads"])
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads")
    assert not set(entry["reduced"]) & set(widths)
    for k in entry["reduced"]:
        assert data["published"][k] != data[k]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_one_reported_metric(metric):
    m = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m["workloads"]:
        assert cell in CELLS
        scope = e2e[m["moves"]].get("workloads", CELLS)
        assert cell in scope


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_config_and_metric_are_new_files_only(tmp_path):
    from chipbench import spec
    root = tmp_path / "chipbench"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root)
    shutil.copy(root / "configs" / "granite-moe-1b-a400m.json",
                root / "configs" / "new-config.json")
    (root / "traffic" / "new-mix.json").write_text(json.dumps(
        {"job": "train", "batch": 4, "seq": 8192, "zipf_a": 1.1,
         "check_steps": 3}))
    (root / "workloads" / "new-cell.json").write_text(json.dumps(
        {"limits": {"loss": 1e-3, "grad": 0.1, "change": 0.1}}))
    (root / "metrics" / "new_metric.train.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="new-config",
                                 file="chipbench/configs/new-config.json"))
    bench["workloads"].append({"name": "new-cell", "config": "new-config",
                               "traffic": "new-mix", "chips": 1,
                               "why": "a cell added as files"})
    bench["per_layer"].append({"name": "new_metric.train", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device",
                               "moves": "train_tokens_per_s",
                               "workloads": ["new-cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("new-cell")
    cell = spec.load_cell(bench, "new-cell", root)
    assert cell.traffic["seq"] == 8192
    assert cell.config["hidden_size"] == 1024
    assert "new_metric.train" in [m["name"] for m in cell.per_layer]
    assert spec.metric_reader(root, "new_metric.train")({}) == 42.0
    assert spec.job_module(cell).run.__name__ == "run"
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())


def test_no_tpu_means_no_result(capsys):
    from chipbench import run
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable] + BENCH["command"][1:] +
        ["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
