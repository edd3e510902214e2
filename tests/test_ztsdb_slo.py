"""Fleet history plane (obs/tsdb.py + obs/slo_budget.py +
tools/postmortem.py): chunk seal/CRC durability, torn-chunk handling,
online downsample math vs raw, retention-GC invariants (newest +
pinned chunks survive), restart re-attach with no gap and no duplicate
aggregate buckets, the HistogramWindow mixed-generation counter-reset
regression, multi-window burn-rate ordering (fast pages before slow
warns), the console --since retrospective, postmortem smokes, and the
ISSUE-16 acceptance drill: a subprocess collector writing through the
store is SIGKILLed mid-drill and a fresh one re-attaches while a
serve.slow_decode storm burns the TTFT SLO budget — fast burn alert
before slow, both resolved, postmortem --alert renders the chain."""

import json
import os
import queue as queue_mod
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import fleet_console  # noqa: E402
import postmortem  # noqa: E402

from pytorch_distributed_train_tpu.obs import events as events_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs.collector import (  # noqa: E402
    HistogramWindow,
    parse_exposition,
)
from pytorch_distributed_train_tpu.obs.events import load_events  # noqa: E402
from pytorch_distributed_train_tpu.obs.registry import get_registry  # noqa: E402
from pytorch_distributed_train_tpu.obs.slo_budget import (  # noqa: E402
    SLO_CATALOG,
    SLOBudgetTracker,
)
from pytorch_distributed_train_tpu.obs.tsdb import (  # noqa: E402
    TimeSeriesStore,
    read_chunk,
    write_chunk,
)

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture(autouse=True)
def _clean_events():
    yield
    events_lib._reset_for_tests()


T0 = 1_700_000_000.0  # any 10s-aligned epoch


# ------------------------------------------------------- chunk durability

def test_chunk_crc_roundtrip_and_bitflip(tmp_path):
    path = str(tmp_path / "chunk-000.tsc")
    rows = [(T0 + i, float(i) * 0.5) for i in range(16)]
    write_chunk(path, "s", "raw", rows)
    header, got = read_chunk(path)
    assert got == rows
    assert header["n"] == 16 and header["start"] == T0
    before = get_registry().get_value("tsdb_chunk_corrupt_total") or 0.0
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0xFF  # flip a payload bit: CRC must catch it
    open(path, "wb").write(bytes(blob))
    assert read_chunk(path) is None
    assert get_registry().get_value(
        "tsdb_chunk_corrupt_total") == before + 1


def test_torn_final_chunk_ignored_and_counted(tmp_path):
    store = TimeSeriesStore(str(tmp_path), chunk_samples=4, tiers=())
    for i in range(8):  # seals two 4-row chunks
        store.append("serving@h0", "ttft_p95_s", T0 + i, float(i))
    d = tmp_path / "serving@h0" / "ttft_p95_s" / "raw"
    chunks = sorted(p for p in os.listdir(d) if p.endswith(".tsc"))
    assert len(chunks) == 2
    # truncate the final chunk mid-payload: the kill-during-seal shape
    final = d / chunks[-1]
    final.write_bytes(final.read_bytes()[:-20])
    before = get_registry().get_value("tsdb_chunk_corrupt_total") or 0.0
    got = store.query("serving@h0", "ttft_p95_s", T0, T0 + 100)
    # the torn chunk is a HOLE (rows 4..7 gone), not a crash and not
    # garbage — the intact chunk still serves
    assert got == [(T0 + i, float(i)) for i in range(4)]
    assert (get_registry().get_value("tsdb_chunk_corrupt_total")
            or 0.0) >= before + 1


# ------------------------------------------------------- downsample math

def test_downsample_tier_matches_raw_aggregation(tmp_path):
    """Property: a step-aggregated query answered from the 10s tier
    equals the same query answered from raw — the online aggregates
    lose no math (count-weighted mean, true min/max/count/sum)."""
    store = TimeSeriesStore(str(tmp_path))
    for i in range(100):  # 0..49.5s, values with structure
        store.append("trainer@h1", "steps_per_s", T0 + 0.5 * i,
                     (i % 13) * 0.7)
    end = T0 + 39.9  # buckets 0..3 complete AND emitted (sample at 40s)
    for agg in ("mean", "min", "max", "count", "sum", "last"):
        raw = store.query("trainer@h1", "steps_per_s", T0, end,
                          step=10.0, agg=agg, tier="raw")
        tiered = store.query("trainer@h1", "steps_per_s", T0, end,
                             step=10.0, agg=agg, tier="10s")
        assert len(raw) == len(tiered) == 4, agg
        for (rt, rv), (tt, tv) in zip(raw, tiered):
            assert rt == tt
            assert abs(rv - tv) < 1e-9, (agg, rt, rv, tv)
    # and the auto tier picker actually uses the coarse tier for a
    # coarse step (same answer, fewer rows read)
    auto = store.query("trainer@h1", "steps_per_s", T0, end,
                       step=20.0, agg="mean")
    raw20 = store.query("trainer@h1", "steps_per_s", T0, end,
                        step=20.0, agg="mean", tier="raw")
    assert len(auto) == len(raw20)
    for (at, av), (rt, rv) in zip(auto, raw20):
        assert at == rt and abs(av - rv) < 1e-9


# ------------------------------------------------------------- retention

def test_gc_never_evicts_newest_sealed_chunk(tmp_path):
    store = TimeSeriesStore(str(tmp_path), chunk_samples=4, tiers=())
    for i in range(20):  # five sealed chunks
        store.append("serving@h0", "shed_per_s", T0 + i, float(i))
    d = tmp_path / "serving@h0" / "shed_per_s" / "raw"
    assert len([p for p in os.listdir(d) if p.endswith(".tsc")]) == 5
    before = get_registry().get_value("tsdb_gc_evicted_total") or 0.0
    store.disk_budget_bytes = 0  # squeeze to nothing
    assert store.gc() == 4
    left = [p for p in os.listdir(d) if p.endswith(".tsc")]
    # the NEWEST sealed chunk survives any squeeze: a restarting
    # reader must always find some history
    assert len(left) == 1
    assert read_chunk(str(d / left[0]))[1][-1] == (T0 + 19, 19.0)
    assert get_registry().get_value(
        "tsdb_gc_evicted_total") == before + 4


def test_gc_never_evicts_pinned_chunk(tmp_path):
    store = TimeSeriesStore(str(tmp_path), chunk_samples=4, tiers=())
    for i in range(20):
        store.append("serving@h0", "shed_per_s", T0 + i, float(i))
    it = store.query_iter("serving@h0", "shed_per_s", T0, T0 + 100)
    first = next(it)  # oldest chunk now PINNED by the open iterator
    assert first == (T0, 0.0)
    store.disk_budget_bytes = 0
    store.gc()
    d = tmp_path / "serving@h0" / "shed_per_s" / "raw"
    left = sorted(p for p in os.listdir(d) if p.endswith(".tsc"))
    assert len(left) == 2  # pinned oldest + protected newest
    # the in-flight read completes with its data intact
    rest = list(it)
    assert (T0 + 3, 3.0) in [first] + rest
    store.gc()  # pin released: a later squeeze may now evict it
    left = [p for p in os.listdir(d) if p.endswith(".tsc")]
    assert len(left) == 1


# ------------------------------------------------------------- re-attach

def test_reattach_no_gap_no_duplicate_buckets(tmp_path):
    """A killed writer's successor resumes the same store: every
    pre-kill raw sample stays queryable, appends continue seamlessly,
    and the re-attach guard keeps already-emitted downsample buckets
    from appearing twice."""
    s1 = TimeSeriesStore(str(tmp_path), tiers=(10.0,))
    for i in range(12):
        s1.append("serving@h0", "ttft_p95_s", T0 + i, 0.01 * i)
    s1.close()  # SIGKILL shape: no flush, no seal
    s2 = TimeSeriesStore(str(tmp_path), tiers=(10.0,))
    for i in range(12, 24):
        s2.append("serving@h0", "ttft_p95_s", T0 + i, 0.01 * i)
    rows = s2.query("serving@h0", "ttft_p95_s", T0 - 1, T0 + 100)
    assert [r[0] for r in rows] == [T0 + i for i in range(24)]  # no gap
    tier = s2.query("serving@h0", "ttft_p95_s", T0 - 1, T0 + 100,
                    tier="10s", agg="count")
    starts = [r[0] for r in tier]
    assert starts == sorted(set(starts)), "duplicate aggregate bucket"
    # bucket [0,10) was emitted by the FIRST writer and must appear
    # exactly once with its full count
    assert (T0, 10.0) in tier


# ------------------------------------- HistogramWindow counter regression

def _expo(b01: float, binf: float, count: float) -> dict:
    return parse_exposition(
        f'x_ttft_seconds_bucket{{le="0.1"}} {b01}\n'
        f'x_ttft_seconds_bucket{{le="+Inf"}} {binf}\n'
        f"x_ttft_seconds_count {count}\n"
        f"x_ttft_seconds_sum 1.0\n")


def test_histogram_window_mixed_generation_reprimes():
    """Counter-reset regression: a target restart where the NEW process
    out-accumulates the old total between scrapes passes the delta_n>0
    guard, but individual buckets go BACKWARDS — diffing across
    generations would fabricate a quantile from a mixed window. Any
    negative per-bucket delta must re-prime and report None."""
    win = HistogramWindow()
    assert win.observe(_expo(10, 10, 10), "x_ttft_seconds") is None
    assert win.observe(_expo(12, 12, 12), "x_ttft_seconds") == 0.1
    # restart: total grew 12 -> 14 (delta_n = +2) yet the 0.1 bucket
    # fell 12 -> 8 — a mixed-generation window, not a quantile
    assert win.observe(_expo(8, 14, 14), "x_ttft_seconds") is None
    # ...and the re-prime is clean: the next honest delta reads fine
    assert win.observe(_expo(9, 15, 15), "x_ttft_seconds") == 0.1


# ------------------------------------------------------- burn-rate order

def test_burn_fast_window_pair_crosses_before_slow(tmp_path):
    """The Google-SRE shape on real store data: a storm crosses the
    fast (short/long) window pair first — the page — and only later
    the slow pair — the warn; calm traffic drains the fast pair first
    on the way back down."""
    store = TimeSeriesStore(str(tmp_path))
    key = "serving@h0"
    for i in range(100):  # 100s of good TTFT, 1 sample/s
        store.append(key, "ttft_p95_s", T0 + i, 0.01)
    for i in range(30):  # then a 30s storm
        store.append(key, "ttft_p95_s", T0 + 100 + i, 2.0)
    tracker = SLOBudgetTracker(store)
    fast, slow, factor = (5.0, 15.0), (15.0, 60.0), 10.0

    def actionable(pair, now):
        s = tracker.burn_rate("serve_ttft_p95", key, pair[0], now=now)
        lg = tracker.burn_rate("serve_ttft_p95", key, pair[1], now=now)
        return min(s, lg)

    # 9s into the storm: the fast pair is over factor, the slow is not
    assert actionable(fast, T0 + 109) >= factor
    assert actionable(slow, T0 + 109) < factor
    # by storm end (+2s of slack past the exact-boundary bucket) the
    # slow pair has crossed too
    assert actionable(slow, T0 + 132) >= factor
    # the budget itself is overspent by then
    assert tracker.budget_remaining("serve_ttft_p95", key,
                                    now=T0 + 130) < 0
    # calm traffic: the fast pair drains quickly, exporting gauges works
    for i in range(70):
        store.append(key, "ttft_p95_s", T0 + 130 + i, 0.01)
    assert actionable(fast, T0 + 200) < factor
    tracker.export_gauges(now=T0 + 200)
    assert get_registry().get_value(
        "slo_error_budget_remaining",
        {"slo": "serve_ttft_p95"}) is not None


# ----------------------------------------------------------- tool smokes

def test_fleet_console_since_retrospective(tmp_path, capsys):
    hist = tmp_path / "tsdb"
    store = TimeSeriesStore(str(hist))
    now = time.time()
    for i in range(60):
        store.append("serving@h0", "ttft_p95_s", now - 300 + 5 * i,
                     0.02 * (1 + i % 3))
    store.flush()
    rc = fleet_console.main(
        ["--run-dir", str(tmp_path), "--since=-10m"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "retrospective" in out
    assert "serving@h0" in out and "ttft_p95_s" in out
    assert "n=60" in out
    assert "SLO budgets" in out and "serve_ttft_p95" in out
    # an existing-but-empty store renders the empty-store line, not a
    # traceback; a MISSING store is a usage error (exit 2)
    (tmp_path / "empty").mkdir()
    rc = fleet_console.main(
        ["--run-dir", str(tmp_path), "--since=-10m",
         "--history-dir", str(tmp_path / "empty")])
    out = capsys.readouterr().out
    assert rc == 0 and "store is empty" in out
    assert fleet_console.main(
        ["--run-dir", str(tmp_path), "--since=-10m",
         "--history-dir", str(tmp_path / "nothing")]) == 2


def test_postmortem_alert_and_time_range(tmp_path, capsys):
    events_dir = tmp_path / "events"
    events_lib.configure(str(events_dir), who="pm")
    now = time.time()
    store = TimeSeriesStore(str(tmp_path / "tsdb"))
    for i in range(50):  # good before, bad at the end
        store.append("serving@h0", "ttft_p95_s", now - 50 + i,
                     0.01 if i < 45 else 2.0)
    store.flush()
    aid = f"slo_serve_ttft_p95_burn_fast@h0@{int(now * 1000)}"
    events_lib.emit("alert", "fired",
                    rule="slo_serve_ttft_p95_burn_fast", host="h0",
                    role="serving", gen="0", id=aid, value=20.0)
    events_lib.emit("alert", "profile_requested",
                    rule="slo_serve_ttft_p95_burn_fast", host="h0",
                    gen="0", id=aid, status="ok")
    events_lib.emit("alert", "resolved",
                    rule="slo_serve_ttft_p95_burn_fast", host="h0",
                    role="serving", gen="0", id=aid, after_s=3.0)
    events_lib._reset_for_tests()
    rc = postmortem.main(["--run-dir", str(tmp_path), "--alert",
                          "slo_serve_ttft_p95_burn_fast@h0"])
    out = capsys.readouterr().out
    assert rc == 0
    assert f"incident {aid}" in out
    assert "alert lifecycle:" in out
    assert "fired" in out and "profile_requested" in out \
        and "resolved" in out
    assert "ttft_p95_s:" in out
    assert "before" in out and "during" in out and "after" in out
    assert "journal slice" in out
    assert "SLO budget impact" in out and "serve_ttft_p95" in out
    # pure time-range mode needs no alert id (and no store sections die)
    rc = postmortem.main(["--run-dir", str(tmp_path),
                          "--from", f"{now - 120:.0f}",
                          "--to", f"{now:.0f}"])
    out = capsys.readouterr().out
    assert rc == 0 and "window" in out and "journal slice" in out
    # a bogus id is exit 2 with a message, not a traceback
    assert postmortem.main(["--run-dir", str(tmp_path),
                            "--alert", "nope@never"]) == 2
    capsys.readouterr()


def test_slo_soak_budget_phase_report_shape():
    """The soak's budget phase (tools/slo_soak.py) in miniature: burn
    crosses the factor during the storm, recovers after, and the
    journal's alert lifecycle matches the engine's transitions — the
    FAIL lines in main() assert exactly these fields."""
    import argparse

    import slo_soak

    args = argparse.Namespace(
        seed=3, budget_storm_s=0.9, budget_calm_s=2.5,
        budget_ttft=0.05, budget_store_dir="")
    bp = slo_soak.run_budget_phase(args)
    assert bp["burn_peak"] >= bp["burn_factor"]
    assert bp["burn_final"] is not None \
        and bp["burn_final"] < bp["burn_factor"]
    assert bp["budget_after_storm"] is not None \
        and bp["budget_after_storm"] < 1.0
    assert bp["alerts_fired"] >= 1
    assert bp["alerts_resolved"] == bp["alerts_fired"]
    assert bp["journal_fired"] == bp["alerts_fired"]
    assert bp["journal_resolved"] == bp["alerts_resolved"]


# ----------------------------------------------------- acceptance drill

COLLECTOR_WORKER = """
import sys, time
sys.path.insert(0, {repo!r})
sys.path.insert(0, {repo!r} + "/tools")
import fleet_console
from pytorch_distributed_train_tpu.obs import events as events_lib
from pytorch_distributed_train_tpu.obs.alerts import AlertEngine
from pytorch_distributed_train_tpu.obs.collector import FleetCollector
from pytorch_distributed_train_tpu.obs.slo_budget import SLOBudgetTracker
from pytorch_distributed_train_tpu.obs.tsdb import TimeSeriesStore

events_lib.configure({events!r}, who={who!r})
store = TimeSeriesStore({hist!r})
tracker = SLOBudgetTracker(store)
col = FleetCollector(
    store_factory=fleet_console._store_factory({store_addr!r}),
    poll_s=0.15, stale_after_s=30.0, history=store)
engine = AlertEngine(
    slo_tracker=tracker, profile_on_alert=True, profile_cooldown_s=1.0,
    overrides={{
        "slo_serve_ttft_p95_burn_fast.short_s": "1",
        "slo_serve_ttft_p95_burn_fast.long_s": "3",
        "slo_serve_ttft_p95_burn_fast.factor": "2",
        "slo_serve_ttft_p95_burn_fast.cooldown_s": "1",
        "slo_serve_ttft_p95_burn_slow.short_s": "3",
        "slo_serve_ttft_p95_burn_slow.long_s": "9",
        "slo_serve_ttft_p95_burn_slow.factor": "2",
        "slo_serve_ttft_p95_burn_slow.cooldown_s": "1",
        "ttft_regression.cooldown_s": "5",
    }})
print("collector up", flush=True)
while True:
    try:
        col.poll()
        engine.evaluate(col)
    except Exception:
        pass
    time.sleep(0.15)
"""


def _spawn_replica(tmp_path, store_addr, *, faults=""):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "TPUSTORE_ADDR": store_addr,
           "PROCESS_ID": "1",
           "NUM_PROCESSES": "2",
           "PDTT_EVENTS_DIR": str(tmp_path / "events"),
           "PDTT_PROFILE_BACKEND": "fake",
           "PDTT_PROFILE_DIR": str(tmp_path / "profiles")}
    if faults:
        env["PDTT_FAULTS"] = faults
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve_http.py"),
         "--fake-backend", "--fake-step-delay", "0.01", "--port", "0",
         "--slots", "4", "--advertise", "--drain-grace", "5"],
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
    assert port is not None, "replica never came up"
    return proc, f"127.0.0.1:{port}"


def _spawn_collector(tmp_path, store_addr, who):
    script = tmp_path / f"{who}.py"
    script.write_text(COLLECTOR_WORKER.format(
        repo=REPO, events=str(tmp_path / "events"),
        hist=str(tmp_path / "tsdb"), store_addr=store_addr, who=who))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, str(script)], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=env, cwd=REPO)
    line = proc.stdout.readline()
    assert "collector up" in line, line
    threading.Thread(
        target=lambda: [None for _ in proc.stdout],  # drain
        daemon=True).start()
    return proc


def _store_query(tmp_path, series="ttft_p95_s"):
    """Read the drill store with a FRESH instance (the writer is a
    different process; a fresh reader sees its latest appends)."""
    store = TimeSeriesStore(str(tmp_path / "tsdb"))
    return store.query("serving@host1", series, 0, time.time() + 10)


def test_e2e_drill_slo_burn_and_collector_reattach(tmp_path):
    """THE ISSUE-16 acceptance drill: one subprocess fake-backend
    replica + a subprocess collector writing every scrape through the
    durable store. The collector is SIGKILLed mid-drill and a fresh
    one re-attaches — every pre-kill sample stays queryable, no gap.
    Then a serve.slow_decode storm burns the TTFT SLO budget: the
    fast-window burn rule fires BEFORE the slow one, both journal
    their lifecycle and resolve after the storm, and
    tools/postmortem.py --alert <id> renders the
    alert→capture→resolve chain with before/during/after TTFT series."""
    from pytorch_distributed_train_tpu.native.store import StoreServer

    (tmp_path / "events").mkdir()
    with StoreServer() as srv:
        store_addr = f"127.0.0.1:{srv.port}"
        # the storm arms after ~500 decode quanta of good traffic
        proc_r, addr = _spawn_replica(
            tmp_path, store_addr,
            faults="serve.slow_decode@call=500:count=30:delay=0.7")
        col1 = _spawn_collector(tmp_path, store_addr, "collector1")
        traffic_stop = threading.Event()

        def traffic(ci):
            i = 0
            while not traffic_stop.is_set():
                body = json.dumps({"prompt": f"drill {ci}-{i}",
                                   "max_tokens": 4}).encode()
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://{addr}/v1/completions", data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=30).read()
                except Exception:
                    pass
                i += 1
                time.sleep(0.04)

        tthreads = [threading.Thread(target=traffic, args=(i,),
                                     daemon=True) for i in range(3)]
        for t in tthreads:
            t.start()
        col2 = None
        try:
            # -- phase 1: the first collector persists good samples
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if len(_store_query(tmp_path)) >= 8:
                    break
                time.sleep(0.05)
            pre_kill = _store_query(tmp_path)
            assert len(pre_kill) >= 8, "collector1 never wrote history"

            # -- phase 2: SIGKILL the collector mid-drill; a fresh one
            #    re-attaches to the same store
            col1.kill()
            col1.wait(timeout=30)
            t_kill = time.time()
            col2 = _spawn_collector(tmp_path, store_addr, "collector2")
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                rows = _store_query(tmp_path)
                if rows and rows[-1][0] > t_kill + 0.5:
                    break
                time.sleep(0.05)
            rows = _store_query(tmp_path)
            assert rows[-1][0] > t_kill, "collector2 never re-attached"
            # every pre-kill sample is still queryable — no amnesia gap
            ts = [r[0] for r in rows]
            assert ts[:len(pre_kill)] == [r[0] for r in pre_kill]
            assert ts == sorted(ts) and len(ts) == len(set(ts))

            # -- phase 3: the storm burns the budget — fast fires
            #    before slow, per the journal
            def fired_ts(rule):
                evs = load_events(str(tmp_path / "events"))
                for e in evs:
                    if (e.get("category") == "alert"
                            and e.get("name") == "fired"
                            and (e.get("detail") or {}).get("rule")
                            == rule):
                        return e["ts"], (e.get("detail") or {}).get("id")
                return None, None

            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if fired_ts("slo_serve_ttft_p95_burn_slow")[0]:
                    break
                time.sleep(0.1)
            ts_fast, fast_id = fired_ts("slo_serve_ttft_p95_burn_fast")
            ts_slow, _ = fired_ts("slo_serve_ttft_p95_burn_slow")
            assert ts_fast is not None, "fast burn rule never fired"
            assert ts_slow is not None, "slow burn rule never fired"
            assert ts_fast < ts_slow, (ts_fast, ts_slow)
            assert fast_id and fast_id.startswith(
                "slo_serve_ttft_p95_burn_fast@host1@")

            # -- phase 4: the storm exhausts; both rules resolve
            def resolved_rules():
                evs = load_events(str(tmp_path / "events"))
                return {(e.get("detail") or {}).get("rule")
                        for e in evs if e.get("category") == "alert"
                        and e.get("name") == "resolved"}

            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if {"slo_serve_ttft_p95_burn_fast",
                        "slo_serve_ttft_p95_burn_slow"} \
                        <= resolved_rules():
                    break
                time.sleep(0.1)
            assert {"slo_serve_ttft_p95_burn_fast",
                    "slo_serve_ttft_p95_burn_slow"} <= resolved_rules()

            # the budget visibly burned over the drill
            store = TimeSeriesStore(str(tmp_path / "tsdb"))
            rem = SLOBudgetTracker(store).budget_remaining(
                "serve_ttft_p95", "serving@host1")
            assert rem is not None and rem < 1.0
        finally:
            traffic_stop.set()
            for t in tthreads:
                t.join(timeout=30)
            for p in (col2, col1, proc_r):
                if p is not None and p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)

    # -- phase 5: the postmortem reconstructs the incident offline
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
         "--run-dir", str(tmp_path), "--alert", fast_id],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    text = out.stdout
    assert f"incident {fast_id}" in text
    assert "alert lifecycle:" in text
    assert "fired" in text and "resolved" in text
    assert "profile_requested" in text  # the capture in the chain
    assert "ttft_p95_s:" in text
    assert "before" in text and "during" in text and "after" in text
    assert "SLO budget impact" in text
