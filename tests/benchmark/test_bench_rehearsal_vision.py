"""A second configuration added AS FILES ONLY, and its tiny CPU rehearsal.
ResNet-50 has no cell in BENCHMARK.json (PERF.md section 7 says why); its
configuration and plain reference stay under benchmark/, and this test adds
the manifest entries and the traffic file to a temporary copy:
the reference agrees with the trainer's model, the last line is the
contract's, and run.py needed no edit for an image model."""

import os
import shutil

from bench_helpers import RESULT_KEYS, copy_benchmark, rehearse, write_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "resnet50-files-only"


def test_a_second_configuration_is_files_only(tmp_path, capfd, monkeypatch):
    manifest = copy_benchmark(tmp_path)
    shutil.copy(os.path.join(HERE, "resnet50_cell.json"),
                tmp_path / "benchmark" / "workloads" / (CELL + ".json"))
    manifest["configs"].append({
        "name": "resnet50_imagenet", "source": "https://arxiv.org/abs/1512.03385",
        "file": "benchmark/configs/resnet50_imagenet.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": CELL, "config": "resnet50_imagenet", "traffic": "b128-224px",
        "chips": 1, "why": "test"})
    manifest["end_to_end"].append({
        "name": "images_per_s_per_chip", "unit": "images/s/chip",
        "better": "higher", "bound": 0.01, "source": "host_clock",
        "workloads": [CELL]})
    run = write_manifest(tmp_path, manifest)

    last, lines = rehearse(capfd, monkeypatch, CELL, run=run)
    assert set(last) == RESULT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    compared = {ln["compared"]: dict(ln) for ln in lines if "compared" in ln}
    judged = [c for c in compared.values() if c.get("limit") is not None]
    assert {"loss_gap_step3", "first_grad_worst_matrix_leaf",
            "param_change_worst_leaf"} <= {c["compared"] for c in judged}
    for c in judged:  # the float32 rehearsal sits far inside every limit
        if c["compared"] != "window_loss_last_tenth_minus_first":
            assert c["value"] < 0.25 * c["limit"], c
