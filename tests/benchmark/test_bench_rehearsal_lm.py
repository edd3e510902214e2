"""The runner's tiny CPU rehearsal of the decoder cell: the reference agrees
with the trainer's model, the last line has exactly the contract's keys, and
a made-up cell and metric added as FILES ONLY run without touching run.py."""

import json
import os

import pytest
from bench_helpers import (
    BENCH,
    RESULT_KEYS,
    copy_benchmark,
    load_run,
    rehearse,
    write_manifest,
)

CELL = "gpt2s-1chip-b16"


def test_rehearsal_last_line_and_reference_agreement(capfd, monkeypatch):
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert set(last) == RESULT_KEYS
    assert last["device"]["platform"] == "cpu"   # never a device number
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "step_ms_p95",
                                    "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in last["metrics"].values())
    compared = {ln["compared"]: ln for ln in lines if "compared" in ln}
    # the float32 rehearsal sits far inside every limit
    for name in ("loss_gap_step1", "first_grad_worst_matrix_leaf",
                 "param_change_worst_leaf"):
        assert compared[name]["value"] < 0.25 * compared[name]["limit"]
    info = next(ln for ln in lines if "compile_cache" in ln)
    assert info["compile_cache"]["compiles_in_window"] == 0


def test_no_tpu_and_no_explicit_cpu_is_refused(capfd, monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        load_run().main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                         "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capfd.readouterr().out.strip() == ""


def test_a_made_up_cell_and_metric_are_files_only(tmp_path, capfd,
                                                  monkeypatch):
    manifest = copy_benchmark(tmp_path)
    cell = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    cell["name"] = "madeup-cell"
    cell["rehearsal_overrides"] = ["data.batch_size=2", "data.seq_len=64"]
    cell["rehearsal"].update(items_per_step=128, rehearsal_batch=2)
    with open(tmp_path / "benchmark" / "workloads" / "madeup-cell.json",
              "w") as f:
        json.dump(cell, f)
    with open(tmp_path / "benchmark" / "layer_metrics" / "madeup_steps.py",
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx['counters']['steps'])\n")
    manifest["workloads"].append({"name": "madeup-cell", "config": "gpt2_small",
                                  "traffic": "b2-s64", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if "workloads" in m and CELL in m["workloads"]:
            m["workloads"].append("madeup-cell")
    manifest["per_layer"].append({
        "name": "madeup_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer loop",
        "moves": "tokens_per_s_per_chip", "workloads": ["madeup-cell"]})
    run = write_manifest(tmp_path, manifest)
    last, _ = rehearse(capfd, monkeypatch, "madeup-cell", trace=1, run=run)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["metrics"]["madeup_steps"] == {
        "value": float(last["attempted"]), "unit": "steps"}
    assert {"busy_s", "window_s"} <= set(last["device"])
    assert last["device"]["busy_s"] > 0
    assert len(last["breakdown"]["device_ops"]) <= 10
    assert not os.path.exists(tmp_path / "benchmark" / ".work" / "madeup-cell")
