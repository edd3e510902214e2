"""Unit tests for the unified observability layer (obs/): span nesting,
ring overflow, Chrome trace schema, Prometheus exposition format, goodput
bucket arithmetic, cross-host summarize, and the /metrics sidecar. All
CPU-only plain-python — no Trainer, no device work (the e2e wiring test
lives in test_observability.py)."""

import json
import threading
import urllib.request

import pytest

from pytorch_distributed_train_tpu.obs.cluster import summarize
from pytorch_distributed_train_tpu.obs.goodput import BUCKETS, GoodputTracker
from pytorch_distributed_train_tpu.obs.registry import (
    Histogram,
    MetricsRegistry,
    sanitize_name,
)
from pytorch_distributed_train_tpu.obs.spans import SpanRecorder


# ------------------------------------------------------------------ spans
def test_span_nesting_records_depth_and_thread():
    rec = SpanRecorder(capacity=16, feed_registry=False)
    with rec.span("outer"):
        assert rec.active() == ["outer"]
        with rec.span("inner", step=7):
            assert rec.active() == ["outer", "inner"]
    evs = rec.events()
    # completion order: inner closes before outer
    assert [s.name for s in evs] == ["inner", "outer"]
    inner, outer = evs
    assert inner.depth == 1 and outer.depth == 0
    assert inner.args == {"step": 7}
    assert inner.thread == threading.current_thread().name
    assert 0.0 <= inner.dur_s <= outer.dur_s


def test_span_ring_overflow_keeps_latest():
    rec = SpanRecorder(capacity=4, feed_registry=False)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    evs = rec.events()
    assert len(evs) == 4
    assert [s.name for s in evs] == ["s6", "s7", "s8", "s9"]
    assert rec.n == 10


def test_span_exception_flagged_and_rering():
    rec = SpanRecorder(capacity=8, feed_registry=False)
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("x")
    (sp,) = rec.events()
    assert sp.args.get("error") is True


def test_chrome_trace_schema(tmp_path):
    rec = SpanRecorder(capacity=8, feed_registry=False)
    with rec.span("a"):
        with rec.span("b", k="v"):
            pass
    path = rec.dump_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)  # must be loadable JSON
    evs = trace["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and e["tid"]
    assert {e["name"] for e in evs} == {"a", "b"}


def test_spans_threadsafe_nesting():
    rec = SpanRecorder(capacity=64, feed_registry=False)
    errs = []

    def worker(tag):
        try:
            for _ in range(5):
                with rec.span(f"{tag}.outer"):
                    with rec.span(f"{tag}.inner"):
                        pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(f"t{i}",)) for i in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errs
    assert rec.n == 40
    # per-thread stacks: every inner span has depth 1, outer 0
    for s in rec.events():
        assert s.depth == (1 if s.name.endswith(".inner") else 0)


# --------------------------------------------------------------- registry
def _parse_prom(text: str) -> dict[str, float]:
    """Minimal Prometheus text-format parser: {series_line: value}."""
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        name_labels, value = line.rsplit(" ", 1)
        assert " " not in name_labels.split("{")[0]
        out[name_labels] = float(value)
    return out


def test_registry_counter_gauge_render():
    reg = MetricsRegistry()
    reg.counter("requests_total", labels={"path": "/x"}).inc()
    reg.counter("requests_total", labels={"path": "/x"}).inc(2)
    reg.gauge("loss").set(1.5)
    series = _parse_prom(reg.render())
    assert series['requests_total{path="/x"}'] == 3.0
    assert series["loss"] == 1.5


def test_registry_histogram_exposition_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("train_step_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 500.0):
        h.observe(v)
    series = _parse_prom(reg.render())
    # cumulative le buckets, +Inf == count, sum matches
    assert series['train_step_seconds_bucket{le="0.1"}'] == 1
    assert series['train_step_seconds_bucket{le="1.0"}'] == 3
    assert series['train_step_seconds_bucket{le="10.0"}'] == 4
    assert series['train_step_seconds_bucket{le="+Inf"}'] == 5
    assert series["train_step_seconds_count"] == 5
    assert series["train_step_seconds_sum"] == pytest.approx(506.05)


def test_registry_kind_conflict_and_sanitize():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    assert sanitize_name("grad_norm/encoder.block-0") == \
        "grad_norm_encoder_block_0"
    reg.set_from_mapping({"a/b": 1.0, "text": "skip", "n": 2}, prefix="train")
    series = _parse_prom(reg.render())
    # "family/module" keys become ONE family with a module= label
    assert series['train_a{module="b"}'] == 1.0
    assert series["train_n"] == 2.0
    assert not any("text" in k for k in series)


# ---------------------------------------------------------------- goodput
def test_goodput_buckets_sum_to_wall():
    gp = GoodputTracker()
    gp.account("init", 0.5)
    gp.account("compile", 1.0)
    with gp.measure("step"):
        pass
    gp.account("step", 2.0)
    gp.account("ckpt", 0.25)
    snap = gp.snapshot(now=gp.t0 + 10.0)
    total = sum(v for k, v in snap.items() if k.startswith("goodput_s_"))
    assert total == pytest.approx(snap["goodput_wall_s"], rel=0.05)
    assert snap["goodput_pct"] == pytest.approx(100.0 * snap["goodput_s_step"]
                                                / 10.0, abs=0.1)
    assert set(f"goodput_s_{b}" for b in BUCKETS) <= set(snap)


def test_goodput_idle_never_negative_and_idle_unaccountable():
    gp = GoodputTracker()
    gp.account("step", 100.0)  # more than wall: clock skew must not crash
    snap = gp.snapshot(now=gp.t0 + 1.0)
    assert snap["goodput_s_idle"] == 0.0
    with pytest.raises(ValueError):
        gp.account("idle", 1.0)


# ---------------------------------------------------------------- cluster
def test_cluster_summarize_single_host_degenerate():
    out = summarize({"step_time_p50": 12.5, "input_stall_pct": 1.0},
                    process_index=0, process_count=1)
    assert out["step_time_p50_min"] == out["step_time_p50_max"] == 12.5
    assert out["step_time_p50_med"] == 12.5
    assert out["step_time_p50_max_host"] == 0
    assert out["input_stall_pct_max"] == 1.0
    # fixed schema: 4 keys per input key
    assert len(out) == 8


# --------------------------------------------------------------- watchdog
def test_flight_recorder_dump_includes_attached_spans():
    import io

    from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder

    fr = FlightRecorder(capacity=8)
    sp = SpanRecorder(capacity=8, feed_registry=False)
    fr.attach_spans(sp)
    with sp.span("checkpoint.save", step=3):
        pass
    fr.record("step", 3)
    out = io.StringIO()
    fr.dump(out)
    text = out.getvalue()
    assert "flight recorder" in text
    assert "trace spans" in text and "checkpoint.save" in text


# ------------------------------------------------------------- exposition
def test_metrics_server_scrape_parses():
    from pytorch_distributed_train_tpu.obs.exposition import MetricsServer
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    get_registry().gauge("scrape_probe").set(42.0)
    srv = MetricsServer(-1)  # ephemeral port
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        series = _parse_prom(body)
        assert series["scrape_probe"] == 42.0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            assert json.load(r)["status"] == "ok"
    finally:
        srv.close()
