"""Run-to-run determinism: same config + seed → identical metric stream.

The reproducibility contract the reference gets from torch.manual_seed +
DistributedSampler(seed=...) — here it falls out of functional RNG
(fold_in per step) + index-deterministic sampling. Also covers the
obs.log_memory and obs.compile_cache_dir knobs.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tiny import set_flags  # noqa: E402


def _run(tmp, tag, extra=()):
    import train

    rc = train.main([
        "--config", "resnet18_cifar10", "--steps", "4", "--resume", "none",
        *set_flags(f"checkpoint.dir={tmp}/{tag}",
                   "checkpoint.save_every_steps=0"),
        *extra,
    ])
    assert rc == 0
    path = f"{tmp}/{tag}/metrics.jsonl"
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _train_losses(rows):
    return [r["loss"] for r in rows if r.get("tag") == "train"]


def test_same_seed_same_losses(tmp_path):
    a = _run(tmp_path, "a")
    b = _run(tmp_path, "b")
    la, lb = _train_losses(a), _train_losses(b)
    assert la and la == lb

    c = _run(tmp_path, "c", extra=("--set", "seed=7"))
    assert _train_losses(c) != la  # different seed diverges


@pytest.mark.usefixtures("compile_cache_off")
def test_compile_cache_knob(tmp_path, monkeypatch):
    import jax

    cache = f"{tmp_path}/xla_cache"
    prev = jax.config.jax_compilation_cache_dir
    # (out of the run's own cache first: conftest's compile_cache_off);
    # this test is about the knob reaching JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        rows = _run(tmp_path, "m", extra=(
            "--set", "obs.log_memory=true",
            "--set", f"obs.compile_cache_dir={cache}",
        ))
        assert rows
        # the knob must actually reach jax (process-global; reset below)
        assert jax.config.jax_compilation_cache_dir == cache
        assert os.path.isdir(cache)
    finally:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()


def test_device_memory_metrics_helper(monkeypatch):
    import jax

    from pytorch_distributed_train_tpu import trainer as trainer_lib

    class FakeDev:
        def memory_stats(self):
            return {"bytes_in_use": 2**30, "peak_bytes_in_use": 3 * 2**30}

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    m = trainer_lib.device_memory_metrics()
    assert m == {"hbm_gb_in_use": 1.0, "hbm_gb_peak": 3.0}

    class EmptyDev:
        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [EmptyDev()])
    assert trainer_lib.device_memory_metrics() == {}
