"""Observability subsystems end-to-end (SURVEY §5.1/§5.5): the profiler
window flag produces a trace, the TensorBoard writer produces event
files, and the unified obs layer (spans + /metrics scrape + goodput)
delivers its artifacts — all from real (tiny, CPU) Trainer runs."""

import json
import os
import urllib.request

import pytest
from tiny import tiny_cfg

from pytorch_distributed_train_tpu.config import TrainConfig


def _tiny_cfg(tmp_path) -> TrainConfig:
    return tiny_cfg(
        "data.synthetic_size=128", "data.batch_size=32", "optim.name=sgd",
        "total_steps=4", f"checkpoint.dir={tmp_path}/ckpt",
        "checkpoint.save_every_steps=0")


@pytest.mark.slow
def test_profiler_window_writes_trace(tmp_path):
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = _tiny_cfg(tmp_path)
    cfg.obs.profile_start_step = 2
    cfg.obs.profile_num_steps = 1
    cfg.obs.profile_dir = str(tmp_path / "profile")
    t = Trainer(cfg)
    t.fit()
    t.close()
    found = []
    for root, _, files in os.walk(cfg.obs.profile_dir):
        found += [os.path.join(root, f) for f in files]
    assert any(f.endswith((".xplane.pb", ".trace.json.gz", ".json.gz"))
               or "xplane" in f for f in found), found


def test_obs_layer_end_to_end(tmp_path):
    """The ISSUE-1 acceptance run: a 4-step CPU fit with a metrics
    sidecar serves a parsable Prometheus scrape containing the
    train_step_seconds histogram, writes a loadable Chrome trace.json
    with >= 3 distinct span names, and logs goodput_pct with buckets
    summing to wall time within 5%."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = _tiny_cfg(tmp_path)
    cfg.obs.metrics_port = -1  # ephemeral: parallel tests must not collide
    t = Trainer(cfg)
    assert t.metrics_server is not None
    port = t.metrics_server.port
    t.fit()

    # --- live /metrics scrape, while the trainer process still serves
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=10) as r:
        assert r.status == 200
        body = r.read().decode()
    series = {}
    for line in body.strip().splitlines():
        if not line.startswith("#"):
            key, value = line.rsplit(" ", 1)
            series[key] = float(value)  # parses as exposition lines
    assert any(k.startswith("train_step_seconds_bucket") for k in series)
    assert series["train_step_seconds_count"] >= 3  # ticks (first primes)
    # MetricLogger mirror: the last logged train loss is scrapable
    assert any(k.startswith("train_loss") for k in series)
    # stall accounting mirror (data/pipeline.py StallStats)
    assert 'input_stall_seconds_total{split="train"}' in series
    t.close()

    # --- Chrome trace with the span vocabulary
    trace_path = os.path.join(cfg.checkpoint.dir, "trace.json")
    with open(trace_path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert len(names) >= 3, names
    assert {"train.compile", "train.step", "data.produce"} <= names
    assert all(e["ph"] == "X" for e in trace["traceEvents"])

    # --- goodput: per-window pct + summary buckets sum to wall
    recs = [json.loads(line)
            for line in open(os.path.join(cfg.checkpoint.dir,
                                          "metrics.jsonl"))]
    train_recs = [r for r in recs if r["tag"] == "train"]
    assert train_recs and all("goodput_pct" in r for r in train_recs)
    summary = [r for r in recs if r["tag"] == "summary"][-1]
    buckets = {k: v for k, v in summary.items()
               if k.startswith("goodput_s_")}
    assert set(buckets) == {f"goodput_s_{b}" for b in
                            ("init", "compile", "step", "input_stall",
                             "ckpt", "eval", "idle")}
    assert sum(buckets.values()) == pytest.approx(
        summary["goodput_wall_s"], rel=0.05)
    assert 0.0 <= summary["goodput_pct"] <= 100.0


@pytest.mark.slow
def test_tensorboard_writer_emits_events(tmp_path):
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = _tiny_cfg(tmp_path)
    cfg.obs.tensorboard = True
    t = Trainer(cfg)
    t.fit()
    t.close()
    tb_dir = os.path.join(cfg.checkpoint.dir, "tb")
    assert os.path.isdir(tb_dir)
    assert any("tfevents" in f for f in os.listdir(tb_dir)), os.listdir(tb_dir)
