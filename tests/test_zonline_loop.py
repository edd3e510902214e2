"""tools/online_loop.py end-to-end drills (docs/online_training.md).

Tier-1 ``--smoke``: 2 fake-backend replicas under continuous client
traffic; rollouts tagged with the generating ``weight_version`` feed 2
train steps per cycle, each cycle publishes the next version and swaps
it onto EVERY replica with zero failed requests, and the fleet's
/healthz weight state converges on the final version.

The slow acceptance drill additionally renders one cycle's trace with
``tools/timeline_report.py --traces <dir> --trace <id>`` and asserts
the cross-process causal chain — rollout → train → publish → per-
replica swap — with the old/new ``weight_version`` correlation tags
visible on both the trainer and replica writers."""

import json
import os
import shutil
import subprocess
import sys
import time
import urllib.request

import jax.numpy as jnp
import pytest

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_loop(extra=(), timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPUSTORE_ADDR", None)
    env.pop("PDTT_EVENTS_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "online_loop.py"),
         *extra],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)
    # the report is the last JSON object line on stdout (replica
    # subprocess chatter is pumped above it)
    report = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                report = json.loads(line)
            except ValueError:
                continue
    assert report is not None, \
        f"no JSON report\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc, report


def _cleanup(report):
    for key in ("events_dir", "trace_dir"):
        d = report.get(key)
        if d and os.path.isdir(d):
            shutil.rmtree(d, ignore_errors=True)


def test_online_loop_smoke():
    proc, report = _run_loop(["--smoke"])
    try:
        assert proc.returncode == 0, \
            f"report={report}\nstderr:\n{proc.stderr[-2000:]}"
        assert report["ok"] is True
        assert report["replicas"] == 2 and report["cycles"] == 2

        # zero failed requests across both swaps; traffic actually ran
        # (counters only materialize on first increment — absent == 0)
        traffic = report["traffic"]
        assert traffic.get("failed", 0) == 0
        assert traffic.get("ok", 0) > 0

        log = report["cycle_log"]
        assert len(log) == 2
        for entry in log:
            # rollouts are version-tagged with the GENERATING version:
            # cycle 0 harvests at the boot version, cycle 1 at v1
            assert sum(entry["rollout_versions"].values()) > 0
            assert len(entry["losses"]) == 2
            assert entry["swapped"] == 2  # every replica took the swap
        assert log[0]["published_version"] == 1
        assert log[1]["published_version"] == 2
        assert "1" in log[1]["rollout_versions"], \
            "cycle 1 rollouts must come from the swapped v1 weights"

        # the fleet converged: every replica's mutable /healthz weight
        # state reads the final published version
        assert report["converged"] is True
        assert set(report["final_versions"].values()) == {"2"}

        # model-health plane (ISSUE 20): the rollout/KL analytics are
        # LIVE on the loop's scrape registry — reward level/spread and
        # the mixed-version census from every converted batch, token
        # entropy and KL-to-behavior from the GRPO aux (the loop
        # recomputes behavior logprobs against the harvest-version
        # weights, so kl_behavior flows from the first update on)
        hg = report["health_gauges"]
        assert hg["rollout_reward_mean"] is not None
        assert hg["rollout_reward_std"] is not None
        assert hg["rollout_advantage_mean"] is not None
        assert hg["rollout_advantage_std"] is not None
        assert hg["rollout_mixed_versions"] >= 1.0
        assert hg["train_token_entropy"] > 0.0
        assert hg["train_kl_behavior"] is not None
        for entry in log:
            assert entry["kl_behavior"] is not None
    finally:
        _cleanup(report)


def _http(addr, path, body=None, timeout=10.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(f"http://{addr}{path}", data=data)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_replica_swap_rejects_keep_old_version():
    """The satellite-1 contract at the REPLICA level: an injected
    ``weights.swap`` fault 503s before any fetch, and a corrupt
    published shard fails CRC verification and 409s — both leave the
    replica serving its current version (visible on /healthz)."""
    from pytorch_distributed_train_tpu.native.store import (StoreClient,
                                                            StoreServer)
    from pytorch_distributed_train_tpu.online import publisher as pub_lib

    server = StoreServer()
    store = StoreClient("127.0.0.1", server.port)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPUSTORE_ADDR=f"127.0.0.1:{server.port}",
               PROCESS_ID="7",
               PDTT_FAULTS="weights.swap@call=1")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve_http.py"),
         "--fake-backend", "--port", "0", "--slots", "4",
         "--drain-grace", "2"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    addr = None
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            line = proc.stdout.readline() if proc.stdout else ""
            if not line and proc.poll() is not None:
                break
            if line.startswith("serving on http://"):
                addr = line.split("http://", 1)[1].split()[0].strip("/")
                break
        assert addr, "replica failed to start"

        savable = {"params": {"w": jnp.arange(12, dtype=jnp.float32)}}
        pub_lib.publish_version(store, savable, version=1, step=10)

        # first POST trips the armed weights.swap fault: 503, version
        # untouched
        code, body = _http(addr, "/admin/weights", {})
        assert code == 503 and "injected" in body["error"]
        _code, health = _http(addr, "/healthz")
        assert health["weights"]["version"] == "fake"
        assert health["weights"]["rejects"] == 1

        # fault consumed: the same swap now lands
        code, body = _http(addr, "/admin/weights", {})
        assert code == 200 and body["status"] == "swapped"
        assert body["version"] == "1" and body["old_version"] == "fake"

        # corrupt one chunk of v2: CRC rejects, replica stays on v1
        pub_lib.publish_version(store, savable, version=2, step=20)
        blob = bytearray(store.get("wts/2/0/c0", timeout_ms=2000))
        blob[0] ^= 0xFF
        store.set("wts/2/0/c0", bytes(blob))
        code, body = _http(addr, "/admin/weights", {"version": 2})
        assert code == 409 and body["serving"] == "1"
        _code, health = _http(addr, "/healthz")
        assert health["weights"]["version"] == "1"

        # a clean republish (v3) swaps fine — the reject was the shard,
        # not the replica
        pub_lib.publish_version(store, savable, version=3, step=30)
        code, body = _http(addr, "/admin/weights", {})
        assert code == 200 and body["version"] == "3"
        _code, health = _http(addr, "/healthz")
        assert (health["weights"]["version"] == "3"
                and health["weights"]["lag_steps"] == 0)
    finally:
        try:
            proc.terminate()
            proc.wait(timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            proc.kill()
        store.close()
        server.stop()


@pytest.mark.slow
def test_online_loop_acceptance_timeline():
    proc, report = _run_loop(
        ["--replicas", "2", "--cycles", "3", "--steps-per-cycle", "2",
         "--max-tokens", "4", "--prompts", "2"], timeout=600)
    try:
        assert proc.returncode == 0, \
            f"report={report}\nstderr:\n{proc.stderr[-2000:]}"
        assert report["ok"] is True
        assert [e["published_version"]
                for e in report["cycle_log"]] == [1, 2, 3]
        assert report["traffic"].get("failed", 0) == 0

        # render the LAST cycle's trace: old-version rollouts on one
        # side of the swap, the new version tagged on the other
        entry = report["cycle_log"][-1]
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "timeline_report.py"),
             "--traces", report["trace_dir"], "--trace", entry["trace"]],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr
        text = out.stdout
        for span in ("online.cycle", "online.rollout", "online.train",
                     "online.publish", "http.admin.weights"):
            assert span in text, f"span {span!r} missing:\n{text}"
        # cross-process: the trainer writer AND at least one replica
        # writer contribute spans to the same trace
        assert "trainer" in text and "host1" in text
        assert "weight_version" in text
    finally:
        _cleanup(report)
