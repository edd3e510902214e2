"""Shared by the benchmark's tests: load benchmark/run.py, run one cell's tiny
CPU rehearsal in this process, parse what it printed."""

import importlib.util
import json
import os
import shutil
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load_run(path=os.path.join(BENCH, "run.py"), name="bench_run"):
    if os.path.dirname(path) not in sys.path:
        sys.path.insert(0, os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rehearse(capfd, monkeypatch, workload, *, trace=0, seed=3, seconds=1.0,
             run=None):
    """(last line, all lines) of one in-process rehearsal run."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    run = run or load_run()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)])
    assert rc == 0
    lines = [ln for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    return json.loads(lines[-1]), [json.loads(ln) for ln in lines[:-1]]


def copy_benchmark(tmp_path):
    """A temporary copy of the benchmark beside a link to the program, for
    tests that add cells, configurations and metrics AS FILES: returns the
    manifest, to be changed and handed to ``write_manifest``."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(ROOT, "pytorch_distributed_train_tpu"),
               tmp_path / "pytorch_distributed_train_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def write_manifest(tmp_path, manifest):
    """Writes the changed manifest and loads the copy's own runner."""
    with open(tmp_path / "BENCHMARK.json", "w", encoding="utf-8") as f:
        json.dump(manifest, f)
    return load_run(str(tmp_path / "benchmark" / "run.py"), "bench_run_copy")
