"""BENCHMARK.json against the files it names, and the runner's neutrality."""

import json
import os
import re
import subprocess
import sys

import pytest
from bench_helpers import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_manifest_has_exactly_the_contracts_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in MANIFEST["end_to_end"])
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_names_units_and_one_line_texts():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for c in MANIFEST["configs"]:
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cells_files_exist_and_agree(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == entry["config"])
    assert cfg["file"].startswith("benchmark/")
    config = json.load(open(os.path.join(ROOT, cfg["file"])))
    wl = json.load(open(os.path.join(BENCH, "workloads", cell + ".json")))
    assert wl["config"] == entry["config"] and wl["chips"] == entry["chips"]
    assert sorted(set(config["changed"]) - {"note"}) == sorted(cfg["reduced"])
    assert config["source"] == cfg["source"]
    assert os.path.exists(os.path.join(BENCH, "references",
                                       config["reference"] + ".py"))
    reported = {m["name"] for m in MANIFEST["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert "setup_s" in reported and len(reported) >= 2
    assert config["throughput_metric"] in reported
    layer = [m for m in MANIFEST["per_layer"]
             if cell in m.get("workloads", [cell])]
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        # a cell that reports a layer metric reports the metric it moves
        assert m["moves"] in reported
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))


def test_runner_names_no_cell_configuration_model_or_metric():
    text = open(os.path.join(BENCH, "run.py")).read()
    words = [w["name"] for w in MANIFEST["workloads"]]
    words += [c["name"] for c in MANIFEST["configs"]]
    words += [m["name"] for m in MANIFEST["per_layer"]]
    words += [m["name"] for m in MANIFEST["end_to_end"]
              if m["name"] not in ("setup_s", "step_ms_p95")]
    words += ["gpt2", "resnet", "llama", "bert", "vit", "flash"]
    for w in words:
        assert w not in text, f"benchmark/run.py names {w!r}"


def test_without_the_program_the_runner_exits_nonzero_and_prints_no_result(
        tmp_path):
    # a directory that holds only BENCHMARK.json and the files under paths;
    # the child stops at the missing program, before it would load JAX
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cell = MANIFEST["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True,
        text=True, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not importable" in p.stderr
