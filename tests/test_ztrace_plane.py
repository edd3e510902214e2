"""Distributed tracing plane (obs/tracing.py + spans trace context +
serving/router propagation + timeline/obs_report surfaces + the
trace-hygiene analyze pass + the slo_soak trace bounds): unit tests per
layer and THE acceptance drill — a hedged slow request under a
serve.slow_decode storm yields ONE trace id whose tree spans router
attempt A (slow), hedge attempt B (winner), admission, queue, prefill
and decode quanta across two replica processes, while a fast healthy
request under default knobs is NOT retained."""

import json
import os
import queue as queue_mod
import re
import shutil
import signal
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import serve_http  # noqa: E402
import timeline_report  # noqa: E402

from pytorch_distributed_train_tpu.faults import (  # noqa: E402
    registry as fregistry,
)
from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs import spans as spans_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs import tracing  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.serving_plane import (  # noqa: E402
    DeadlineExceeded,
    ReliabilityPlane,
)
from pytorch_distributed_train_tpu.serving_plane.router import (  # noqa: E402
    HealthProber,
    ReplicaSet,
    Router,
)
from pytorch_distributed_train_tpu.serving_plane.testing import (  # noqa: E402
    FakeByteTok,
    FakeTokenBatcher,
)

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture(autouse=True)
def _clean_planes():
    fregistry._reset_for_tests()
    spans_lib.set_correlation_tags(gen=None, step=None,
                                   weight_version=None)
    yield
    fregistry._reset_for_tests()
    events_lib._reset_for_tests()
    tracing._reset_for_tests()
    spans_lib.set_correlation_tags(gen=None, step=None,
                                   weight_version=None)


# ------------------------------------------------------------ wire format
def test_traceparent_roundtrip_and_malformed():
    ctx = tracing.TraceContext(tracing.new_trace_id(),
                               tracing.new_span_id(), sampled=True)
    assert tracing.parse_traceparent(tracing.format_traceparent(ctx)) \
        == ctx
    plain = tracing.TraceContext(tracing.new_trace_id(),
                                 tracing.new_span_id())
    wire = tracing.format_traceparent(plain)
    assert wire.endswith("-00") and len(wire) == 55
    assert tracing.parse_traceparent(wire) == plain
    for bad in (None, "", "garbage", "00-xyz-abc-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",
                "00-" + "1" * 32 + "-" + "0" * 16 + "-01",
                "99-" + "1" * 32 + "-" + "2" * 16 + "-01"):
        assert tracing.parse_traceparent(bad) is None, bad
    # continue_or_start honors inbound, mints a root otherwise
    assert tracing.continue_or_start(wire) == plain
    minted = tracing.continue_or_start(None)
    assert minted.span_id is None and len(minted.trace_id) == 32


def test_span_scope_stamps_ids_and_nests(tmp_path):
    tracing.configure(str(tmp_path), who="h", sample_pct=100.0)
    rec = spans_lib.SpanRecorder(capacity=16, feed_registry=False)
    ctx = tracing.start_trace()
    with tracing.activate(ctx):
        with rec.span("outer"):
            with rec.span("inner"):
                pass
    with rec.span("untraced"):
        pass
    inner, outer, untraced = rec.events()
    assert outer.trace_id == ctx.trace_id and outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id
    assert untraced.trace_id is None and untraced.span_id is None
    # record() with an explicit trace tuple
    sid = rec.record("explicit", time.time(), 0.01,
                     trace=(ctx.trace_id, outer.span_id), tokens=2)
    sp = rec.events()[-1]
    assert sp.span_id == sid and sp.parent_id == outer.span_id
    assert sp.args == {"tokens": 2}


def test_correlation_tags_ride_spans_not_args():
    spans_lib.set_correlation_tags(gen="3", step=17)
    rec = spans_lib.SpanRecorder(capacity=4, feed_registry=False)
    with rec.span("train.step", step=17):
        pass
    (sp,) = rec.events()
    assert sp.corr == {"gen": "3", "step": 17}
    assert sp.args == {"step": 17}  # args stay pure (test_obs contract)
    chrome = sp.to_chrome(1)
    assert chrome["args"]["gen"] == "3"
    spans_lib.set_correlation_tags(step=None)
    assert spans_lib.correlation_tags() == {"gen": "3"}


def test_event_emit_stamps_active_trace(tmp_path):
    j = events_lib.configure(str(tmp_path), who="h0")
    ctx = tracing.start_trace()
    with tracing.activate(ctx):
        events_lib.emit("serve", "request_shed", queue_depth=1)
    events_lib.emit("serve", "drain_begin")
    j.close()
    recs = events_lib.load_events(str(tmp_path))
    assert recs[0]["trace"] == ctx.trace_id
    assert "trace" not in recs[1]


# ---------------------------------------------------------------- sampler
def _one_span_trace(tracer, name="root"):
    ctx = tracing.start_trace()
    with tracing.activate(ctx):
        with spans_lib.span(name):
            pass
    return ctx


def test_tail_sampler_decisions(tmp_path):
    class FixedRng:
        def __init__(self, v):
            self.v = v

        def random(self):
            return self.v

    t = tracing.configure(str(tmp_path), who="h0", sample_pct=5.0,
                          keep_slow_ms=100.0, rng=FixedRng(0.99))
    # fast, unflagged, rng above pct -> dropped
    ctx = _one_span_trace(t)
    assert t.finish(ctx.trace_id, dur_s=0.01) is None
    # slow -> kept
    ctx = _one_span_trace(t)
    assert t.finish(ctx.trace_id, dur_s=0.2) == "slow"
    # flagged reason outranks slow
    ctx = _one_span_trace(t)
    tracing.flag(ctx.trace_id, "deadline")
    assert t.finish(ctx.trace_id, dur_s=0.5) == "deadline"
    # forced (inbound sampled flag)
    ctx = tracing.TraceContext(tracing.new_trace_id(),
                               tracing.new_span_id(), sampled=True)
    with tracing.activate(ctx):
        with spans_lib.span("sub"):
            pass
    assert t.finish(ctx.trace_id, dur_s=0.001) == "flag"
    # error path
    ctx = _one_span_trace(t)
    assert t.finish(ctx.trace_id, dur_s=0.001, error=True) == "error"
    # random baseline
    t2 = tracing.configure(str(tmp_path), who="h1", sample_pct=5.0,
                           keep_slow_ms=100.0, rng=FixedRng(0.01))
    ctx = _one_span_trace(t2)
    assert t2.finish(ctx.trace_id, dur_s=0.001) == "baseline"
    trees = tracing.load_traces(str(tmp_path))
    assert {tr["reason"] for tr in trees} == {
        "slow", "deadline", "flag", "error", "baseline"}


def test_sampler_caps_drop_loudly(tmp_path):
    reg = get_registry()

    def drops(where):
        return reg.get_value("trace_dropped_total",
                             {"where": where}) or 0.0

    t = tracing.configure(str(tmp_path), who="h0", max_pending=4,
                          max_spans_per_trace=3, max_file_mb=0.001)
    d0 = drops("span_cap")
    ctx = tracing.start_trace()
    with tracing.activate(ctx):
        for _ in range(5):
            with spans_lib.span("s"):
                pass
    assert drops("span_cap") - d0 == 2  # 3 kept, 2 over the cap
    p0 = drops("pending_ring")
    for _ in range(6):
        _one_span_trace(t)
    assert drops("pending_ring") - p0 >= 2
    # file cap: tiny cap, every retained tree past it drops; the file
    # stays bounded
    f0 = drops("file_cap")
    cap = t.max_file_bytes
    for _ in range(20):
        c = _one_span_trace(t)
        tracing.flag(c.trace_id, "hedged")
        t.finish(c.trace_id, dur_s=0.001)
    assert os.path.getsize(t.path) <= cap
    assert drops("file_cap") - f0 >= 1


def test_trace_tree_spill_carries_tags(tmp_path):
    spans_lib.set_correlation_tags(weight_version="w7", gen="2")
    t = tracing.configure(str(tmp_path), who="h0", keep_slow_ms=1.0)
    ctx = _one_span_trace(t)
    assert t.finish(ctx.trace_id, dur_s=1.0) == "slow"
    (tree,) = tracing.load_traces(str(tmp_path))
    assert tree["tags"]["weight_version"] == "w7"
    assert tree["tags"]["gen"] == "2"
    assert tree["host"] == "h0" and tree["dur_ms"] == 1000.0
    (sp,) = tree["spans"]
    assert sp["corr"]["weight_version"] == "w7"


# ------------------------------------------------- service request tree
def _service(**plane_kw):
    plane = ReliabilityPlane(slots=2, **plane_kw)
    batcher = FakeTokenBatcher(slots=2, step_delay_s=0.01)
    svc = serve_http.BatcherService(batcher, FakeByteTok(), plane=plane,
                                    orphan_grace_s=0.3)
    return svc, batcher


def test_service_records_slo_phases_as_spans(tmp_path):
    t = tracing.configure(str(tmp_path), who="h0", keep_slow_ms=1.0)
    svc, _ = _service()
    try:
        ctx = tracing.start_trace()
        with tracing.activate(ctx):
            with spans_lib.span("http.v1.completions"):
                svc.complete("hello trace", 5, 0.0, timeout_s=30.0)
        assert t.finish(ctx.trace_id, dur_s=1.0) == "slow"
    finally:
        svc.shutdown()
    spans = tracing.merge_trace(tracing.load_traces(str(tmp_path)),
                                ctx.trace_id)
    names = [s["name"] for s in spans]
    assert "serve.admission" in names
    assert "serve.queue" in names and "serve.prefill" in names
    assert names.count("serve.decode") >= 2  # 5 tokens, 1/quantum
    assert "serve.stream" in names
    by_id = {s["span_id"]: s for s in spans}
    root = next(s for s in spans if s["name"] == "http.v1.completions")
    for s in spans:
        if s["name"].startswith("serve."):
            assert by_id[s["parent_id"]] is root
    stream = next(s for s in spans if s["name"] == "serve.stream")
    assert stream["args"]["outcome"] == "ok"


def test_deadline_504_flags_and_retains_trace(tmp_path):
    tracing.configure(str(tmp_path), who="h0", keep_slow_ms=10_000.0)
    svc, _ = _service(deadline_default_s=0.03)
    t = tracing.get_tracer()
    try:
        ctx = tracing.start_trace()
        t0 = time.monotonic()
        with tracing.activate(ctx):
            with spans_lib.span("http.v1.completions"):
                with pytest.raises(DeadlineExceeded):
                    svc.complete("x" * 30, 400, 0.0, timeout_s=30.0)
        reason = t.finish(ctx.trace_id,
                          dur_s=time.monotonic() - t0)
        assert reason == "deadline"
    finally:
        svc.shutdown()
    trees = [tr for tr in tracing.load_traces(str(tmp_path))
             if tr["trace_id"] == ctx.trace_id]
    assert trees and trees[0]["reason"] == "deadline"


# --------------------------------------------- in-process router + hedge
def _spawn_http(step_delay):
    from http.server import ThreadingHTTPServer

    plane = ReliabilityPlane(slots=4)
    svc = serve_http.BatcherService(
        FakeTokenBatcher(slots=4, step_delay_s=step_delay),
        FakeByteTok(), plane=plane)
    srv = ThreadingHTTPServer(("127.0.0.1", 0), None)
    srv.RequestHandlerClass = serve_http.make_handler(svc, None)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return svc, srv, f"127.0.0.1:{srv.server_address[1]}"


def test_router_hedge_yields_one_cross_component_tree(tmp_path):
    tracing.configure(str(tmp_path), who="proc", sample_pct=0.0,
                      keep_slow_ms=100_000.0)
    slow = _spawn_http(0.12)
    fast = _spawn_http(0.002)
    rs = ReplicaSet((slow[2], fast[2]))
    prober = HealthProber(rs, interval_s=0.3)
    prober.start()
    router = Router(rs, timeout_s=30.0, hedge_after_s=0.25)
    body = {"prompt": "hello world", "max_tokens": 5}
    raw = json.dumps(body).encode()
    tid = None

    def one():
        status, _rbody = router.request("/v1/completions", raw, body)
        assert status == 200

    try:
        for _ in range(15):
            # concurrent pair: least-outstanding balancing then puts one
            # request on the slow replica, which hedges onto the fast one
            ts = [threading.Thread(target=one) for _ in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            hedged = [t for t in tracing.load_traces(str(tmp_path))
                      if "hedged" in t.get("flags", [t.get("reason")])]
            if hedged:
                tid = hedged[0]["trace_id"]
                break
        assert tid, "no hedged trace retained"
        # the slow loser's attempt span flushes as a supplement on a
        # later finish
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            router.request("/v1/completions", raw, body)
            spans = tracing.merge_trace(
                tracing.load_traces(str(tmp_path)), tid)
            if [s for s in spans
                    if s["name"] == "router.attempt"].__len__() >= 2:
                break
            time.sleep(0.2)
    finally:
        prober.stop()
        for svc, srv, _a in (slow, fast):
            srv.shutdown()
            svc.shutdown()
    spans = tracing.merge_trace(tracing.load_traces(str(tmp_path)), tid)
    names = [s["name"] for s in spans]
    assert names.count("router.attempt") >= 2
    assert any(s["args"].get("hedge") for s in spans
               if s["name"] == "router.attempt")
    by_id = {s["span_id"]: s for s in spans}
    rr = next(s for s in spans if s["name"] == "router.request")
    for att in (s for s in spans if s["name"] == "router.attempt"):
        assert att["parent_id"] == rr["span_id"]
    for h in (s for s in spans if s["name"] == "http.v1.completions"):
        assert by_id[h["parent_id"]]["name"] == "router.attempt"


# ------------------------------------------------------- report surfaces
def _synthetic_two_process_trace(tmp_path):
    """router + one replica writing the same trace id from two 'hosts'."""
    tid = tracing.new_trace_id()
    tr_router = tracing.Tracer(str(tmp_path), who="router",
                               keep_slow_ms=1.0)
    tr_rep = tracing.Tracer(str(tmp_path), who="host1",
                            keep_slow_ms=1.0)
    t0 = 1000.0
    root = tracing.new_span_id()
    att = tracing.new_span_id()
    http = tracing.new_span_id()
    mk = spans_lib.Span
    tr_router._spill(tid, "hedged", 0.8, [
        mk("router.request", t0, 0.8, "t", 0, {}, tid, root, None),
        mk("router.attempt", t0 + 0.01, 0.7, "t", 0,
           {"addr": "a:1", "hedge": False}, tid, att, root)])
    tr_rep._spill(tid, "slow", 0.6, [
        mk("http.v1.completions", t0 + 0.02, 0.6, "t", 0, {},
           tid, http, att),
        mk("serve.queue", t0 + 0.03, 0.05, "t", 0, {}, tid,
           tracing.new_span_id(), http),
        mk("serve.decode", t0 + 0.1, 0.3, "t", 0, {"tokens": 2}, tid,
           tracing.new_span_id(), http),
        mk("serve.stream", t0 + 0.4, 0.2, "t", 0, {}, tid,
           tracing.new_span_id(), http)])
    tr_router.close()
    tr_rep.close()
    return tid


def test_timeline_report_trace_mode(tmp_path, capsys):
    tid = _synthetic_two_process_trace(tmp_path)
    out_json = tmp_path / "one.json"
    rc = timeline_report.main(["--traces", str(tmp_path),
                               "--trace", tid[:10],
                               "--out", str(out_json)])
    assert rc == 0
    out = capsys.readouterr().out
    assert f"trace {tid}" in out
    assert "router.request" in out and "serve.decode" in out
    assert "[router]" in out and "[host1]" in out
    assert "kept: hedged" in out and "kept: slow" in out
    trace = json.loads(out_json.read_text())
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(evs) == 6
    by_sid = {e["args"]["span_id"]: e for e in evs}
    http = next(e for e in evs if e["name"] == "http.v1.completions")
    att = by_sid[http["args"]["parent_id"]]
    assert att["name"] == "router.attempt"
    # two process rows, children in deeper lanes than their parents
    assert {e["pid"] for e in evs} == {1, 2}
    rr = next(e for e in evs if e["name"] == "router.request")
    assert att["pid"] == rr["pid"] and att["tid"] > rr["tid"]
    # prefix must be unique
    assert timeline_report.main(["--traces", str(tmp_path),
                                 "--trace", "zz"]) == 0  # not found text
    out = capsys.readouterr().out
    assert "not retained" in out


def test_obs_report_slowest_traces_section(tmp_path):
    import obs_report

    _synthetic_two_process_trace(tmp_path)
    lines = obs_report.traces_section(str(tmp_path), top=3)
    text = "\n".join(lines)
    assert "slowest traces" in text
    assert "hedged" in text and "decode=" in text and "queue=" in text
    assert "timeline_report.py --trace" in text
    # absent dir -> section omitted entirely
    assert obs_report.traces_section(str(tmp_path / "nope")) == []


# ----------------------------------------------------- analyze pass
def test_trace_hygiene_catches_seeded_violations(tmp_path):
    from tools.analyze import core
    from tools.analyze.passes import trace_hygiene

    os.makedirs(tmp_path / "pytorch_distributed_train_tpu"
                / "serving_plane")
    rel = "pytorch_distributed_train_tpu/serving_plane/fix_bad.py"
    shutil.copy(
        os.path.join(REPO, "tools/analyze/fixtures/trace_hygiene_bad.py"),
        tmp_path / rel)
    p = trace_hygiene.TraceHygienePass()
    findings = p.run(core.build_context(str(tmp_path), [rel]))
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 6
    assert msgs.count("manual `__enter__()`") == 2
    assert "manual `__exit__()`" in msgs
    assert "created and discarded" in msgs
    assert "tracing.start_trace" in msgs and "new_trace_id" in msgs
    assert "continue_or_start" in msgs


def test_trace_hygiene_passes_clean_patterns(tmp_path):
    from tools.analyze import core
    from tools.analyze.passes import trace_hygiene

    os.makedirs(tmp_path / "tools")
    rel = "tools/serve_clean.py"
    shutil.copy(os.path.join(
        REPO, "tools/analyze/fixtures/trace_hygiene_clean.py"),
        tmp_path / rel)
    assert trace_hygiene.TraceHygienePass().run(
        core.build_context(str(tmp_path), [rel])) == []


# ----------------------------------------------------------- soak smoke
def test_slo_soak_smoke_trace_bounds():
    import slo_soak
    rc = slo_soak.main(["--requests", "36", "--clients", "3",
                        "--slots", "2", "--max-queue-depth", "8",
                        "--step-delay", "0.002",
                        "--hedge-requests", "12"])
    assert rc == 0


# ----------------------------------------------------- acceptance drill
def _spawn_replica(tmp_path, name, pid, *, faults=""):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PDTT_EVENTS_DIR": str(tmp_path / "events"),
           "PDTT_TRACE_DIR": str(tmp_path / "traces"),
           "PROCESS_ID": str(pid)}
    if faults:
        env["PDTT_FAULTS"] = faults
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve_http.py"),
         "--fake-backend", "--fake-step-delay", "0.01", "--port", "0",
         "--slots", "4", "--drain-grace", "5"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    q: queue_mod.Queue = queue_mod.Queue()

    def pump():
        for line in proc.stdout:
            q.put(line)

    threading.Thread(target=pump, daemon=True).start()
    deadline = time.monotonic() + 120.0
    port = None
    while time.monotonic() < deadline:
        try:
            line = q.get(timeout=max(0.1, deadline - time.monotonic()))
        except queue_mod.Empty:
            break
        m = re.search(r"serving on http://127\.0\.0\.1:(\d+)", line)
        if m:
            port = int(m.group(1))
            break
    assert port is not None, f"replica {name} never came up"
    return proc, f"127.0.0.1:{port}"


def test_e2e_drill_hedged_request_one_cross_process_trace(tmp_path):
    """THE acceptance drill (ISSUE 11): router + 2 subprocess replicas
    under a serve.slow_decode storm on A — a hedged slow request yields
    ONE trace id whose merged tree spans router attempt A (slow), hedge
    attempt B (winner), admission, queue, prefill and >=2 decode-
    quantum spans across two processes; timeline_report --trace renders
    the merged Perfetto tree with correct parentage; the trace carries
    the replicas' weight-version/gen correlation tags; and a fast
    healthy request under default knobs is NOT retained."""
    traces_dir = tmp_path / "traces"
    proc_a, addr_a = _spawn_replica(
        tmp_path, "a", 1,
        faults="serve.slow_decode@call=30:count=25:delay=0.4")
    proc_b, addr_b = _spawn_replica(tmp_path, "b", 2)
    # the router side of the trace plane lives in THIS process
    tracing.configure(str(traces_dir), who="router", sample_pct=0.0,
                      keep_slow_ms=100_000.0)
    rs = ReplicaSet((addr_a, addr_b))
    prober = HealthProber(rs, interval_s=0.5)
    prober.start()
    router = Router(rs, timeout_s=60.0, hedge_after_s=0.8)
    stop = threading.Event()
    failures = []
    lock = threading.Lock()

    def traffic(ci):
        i = 0
        while not stop.is_set():
            body = {"prompt": f"drill {ci}-{i}", "max_tokens": 6}
            status, rbody = router.request(
                "/v1/completions", json.dumps(body).encode(), body)
            if status != 200:
                with lock:
                    failures.append((status, rbody[:200]))
            i += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=traffic, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    tid = None
    try:
        # wait for a hedged request whose trace is retained ROUTER-side
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline and tid is None:
            hedged = [t for t in tracing.load_traces(str(traces_dir))
                      if "hedged" in t.get("flags", [t.get("reason")])
                      and t.get("host") == "router"]
            if hedged:
                tid = hedged[0]["trace_id"]
                break
            time.sleep(0.25)
        assert tid, "no hedged trace retained at the router"
        # both replicas must flush their subtrees of the SAME trace id:
        # A (the slow loser) retains via keep_slow_ms, B (the hedge
        # winner, fast and healthy) via the wire-propagated sampled
        # flag — and the slow loser's router.attempt span must have
        # late-flushed as a supplement (traffic is still flowing, so
        # later finishes sweep it out)
        deadline = time.monotonic() + 60.0
        hosts: set = set()
        n_attempts = 0
        while time.monotonic() < deadline:
            trees = tracing.load_traces(str(traces_dir))
            hosts = {t["host"] for t in trees if t["trace_id"] == tid}
            n_attempts = sum(
                1 for s in tracing.merge_trace(trees, tid)
                if s["name"] == "router.attempt")
            if {"router", "host1", "host2"} <= hosts and n_attempts >= 2:
                break
            time.sleep(0.25)
        assert {"router", "host1", "host2"} <= hosts, hosts
        assert n_attempts >= 2, n_attempts
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        prober.stop()
    try:
        # ---- the merged tree: one trace id across three processes
        trees = tracing.load_traces(str(traces_dir))
        spans = tracing.merge_trace(trees, tid)
        names = [s["name"] for s in spans]
        assert "router.request" in names
        attempts = [s for s in spans if s["name"] == "router.attempt"]
        assert len(attempts) >= 2
        assert any(s["args"].get("hedge") for s in attempts)
        https = [s for s in spans if s["name"] == "http.v1.completions"]
        assert {s["host"] for s in https} == {"host1", "host2"}
        for phase in ("serve.admission", "serve.queue", "serve.prefill"):
            assert phase in names, phase
        decodes = [s for s in spans if s["name"] == "serve.decode"]
        assert len(decodes) >= 2
        assert {s["host"] for s in decodes} == {"host1", "host2"}
        # parentage across the process boundary
        by_id = {s["span_id"]: s for s in spans}
        rr = next(s for s in spans if s["name"] == "router.request")
        for att in attempts:
            assert att["parent_id"] == rr["span_id"]
        for h in https:
            assert by_id[h["parent_id"]]["name"] == "router.attempt"
        for ph in (s for s in spans if s["name"].startswith("serve.")):
            assert by_id[ph["parent_id"]]["name"] == "http.v1.completions"
        # correlation tags: the replicas' weight version + generation
        rep_trees = [t for t in trees if t["trace_id"] == tid
                     and t["host"] in ("host1", "host2")]
        for t in rep_trees:
            assert t["tags"].get("weight_version") == "fake"
            assert t["tags"].get("gen") == "0"
        # ---- timeline_report --trace renders the merged Perfetto tree
        out_json = tmp_path / "one_trace.json"
        rc = timeline_report.main(["--traces", str(traces_dir),
                                   "--trace", tid,
                                   "--out", str(out_json)])
        assert rc == 0
        trace = json.loads(out_json.read_text())
        evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert {e["args"]["span_id"] for e in evs} == \
            {s["span_id"] for s in spans}
        pids = {e["args"]["host"]: e["pid"] for e in evs}
        assert len(set(pids.values())) == 3  # one process row per host
        # ---- tail sampling proven the other way: a fast healthy
        # request under default knobs is NOT retained anywhere
        fast_ctx = tracing.TraceContext(tracing.new_trace_id(),
                                        tracing.new_span_id())
        body = {"prompt": "quick", "max_tokens": 3}
        status, _ = router.request(
            "/v1/completions", json.dumps(body).encode(), body,
            traceparent=tracing.format_traceparent(fast_ctx))
        assert status == 200
        time.sleep(1.0)
        assert not any(t["trace_id"] == fast_ctx.trace_id
                       for t in tracing.load_traces(str(traces_dir)))
    finally:
        for p in (proc_a, proc_b):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in (proc_a, proc_b):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)
