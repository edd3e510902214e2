"""Fault-injection chaos layer (faults/ — ISSUE 2): schedule grammar,
registry matching semantics, retry/backoff policies with a flaky
injected fault, decode substitute-and-count, graceful-preemption
handler composition with the watchdog dump handler (both install
orders), and checkpoint integrity manifests."""

import json
import os
import signal
import time

import pytest

from pytorch_distributed_train_tpu import faults
from pytorch_distributed_train_tpu.faults import integrity
from pytorch_distributed_train_tpu.faults import registry as fregistry
from pytorch_distributed_train_tpu.faults.preemption import PreemptionHandler
from pytorch_distributed_train_tpu.obs.registry import get_registry

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")


@pytest.fixture(autouse=True)
def _clean_schedule(monkeypatch):
    """Each test gets a fresh process-global schedule and no ambient
    generation/env schedule."""
    monkeypatch.delenv("RESTART_GENERATION", raising=False)
    monkeypatch.delenv(fregistry.ENV_VAR, raising=False)
    fregistry._reset_for_tests()
    yield
    fregistry._reset_for_tests()


FAST = faults.RetryPolicy(max_attempts=3, base_delay_s=0.001,
                          max_delay_s=0.004)


# ------------------------------------------------------------------ grammar
def test_parse_spec_full_grammar():
    s = faults.parse_spec("ckpt.save_io@step=3:count=2:gen=-1")
    assert (s.point, s.step, s.count, s.gen) == ("ckpt.save_io", 3, 2, -1)
    s = faults.parse_spec("step.straggle@step=1:delay=0.25")
    assert s.delay_s == 0.25
    s = faults.parse_spec("data.decode@p=0.5:call=2")
    assert s.p == 0.5 and s.at_call == 2


@pytest.mark.parametrize("bad", [
    "ckpt.save_io",                   # no trigger
    "nonexistent.point@step=1",       # unknown point
    "ckpt.save_io@step=x",            # bad value
    "ckpt.save_io@frobnicate=1",      # unknown key
])
def test_parse_spec_rejects_typos(bad):
    with pytest.raises(ValueError):
        faults.parse_spec(bad)


# ----------------------------------------------------------------- matching
def test_step_trigger_and_count():
    sched = fregistry.FaultSchedule(("ckpt.save_io@step=3:count=2",))
    sched.set_step(2)
    assert sched.check("ckpt.save_io") is None
    sched.set_step(3)
    assert sched.check("ckpt.save_io") is not None
    sched.set_step(7)  # step>= semantics: still armed until count runs out
    assert sched.check("ckpt.save_io") is not None
    assert sched.check("ckpt.save_io") is None  # count exhausted


def test_call_trigger():
    sched = fregistry.FaultSchedule(("serve.handler@call=3",))
    assert sched.check("serve.handler") is None
    assert sched.check("serve.handler") is None
    assert sched.check("serve.handler") is not None


def test_generation_gating(monkeypatch):
    sched = fregistry.FaultSchedule(("step.crash@step=1",))
    sched.set_step(5)
    monkeypatch.setenv("RESTART_GENERATION", "1")
    assert sched.check("step.crash") is None  # gen 0 spec, gen 1 process
    monkeypatch.setenv("RESTART_GENERATION", "0")
    assert sched.check("step.crash") is not None
    # gen=-1 fires in any generation
    sched2 = fregistry.FaultSchedule(("step.crash@step=1:gen=-1",))
    sched2.set_step(5)
    monkeypatch.setenv("RESTART_GENERATION", "3")
    assert sched2.check("step.crash") is not None


def test_probabilistic_trigger_seeded():
    fired = [fregistry.FaultSchedule(("data.decode@p=0.5:count=1000",),
                                     seed=7)
             for _ in range(2)]
    seq = [tuple(s.check("data.decode") is not None for _ in range(64))
           for s in fired]
    assert seq[0] == seq[1]  # same seed, same chaos
    assert any(seq[0]) and not all(seq[0])


def test_maybe_fire_raises_and_counts():
    sched = fregistry.FaultSchedule(("serve.handler@call=1",))
    before = get_registry().get_value(
        "faults_injected_total", {"point": "serve.handler"}) or 0.0
    with pytest.raises(faults.InjectedFault):
        sched.maybe_fire("serve.handler")
    after = get_registry().get_value(
        "faults_injected_total", {"point": "serve.handler"})
    assert after == before + 1
    assert sched.maybe_fire("serve.handler") is False  # exhausted


def test_undeclared_point_is_an_error():
    sched = fregistry.FaultSchedule(())
    with pytest.raises(KeyError):
        sched.check("not.a.point")


def test_legacy_crash_shim_routes_through_registry():
    sched = fregistry.configure((), legacy_crash_step=5)
    specs = [s for s in sched.specs if s.point == "step.crash"]
    assert len(specs) == 1 and specs[0].step == 5 and specs[0].gen == 0


def test_env_var_schedule(monkeypatch):
    monkeypatch.setenv(fregistry.ENV_VAR, "serve.handler@call=1")
    sched = fregistry.get_schedule()
    assert any(s.point == "serve.handler" for s in sched.specs)


# -------------------------------------------------------------------- retry
def test_retry_flaky_fault_recovers_and_counts():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    before = get_registry().get_value("retries_total",
                                      {"point": "flaky"}) or 0.0
    assert faults.retry_call(flaky, policy=FAST, point="flaky") == "ok"
    assert len(calls) == 3
    assert get_registry().get_value("retries_total",
                                    {"point": "flaky"}) == before + 2


def test_retry_exhaustion_raises_last_error():
    def always():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        faults.retry_call(always, policy=FAST, point="t")


def test_retry_backoff_is_bounded():
    policy = faults.RetryPolicy(max_attempts=4, base_delay_s=0.01,
                                max_delay_s=0.02, jitter=0.0)
    t0 = time.perf_counter()
    with pytest.raises(OSError):
        faults.retry_call(lambda: (_ for _ in ()).throw(OSError("x")),
                          policy=policy, point="t")
    # 0.01 + 0.02 + 0.02 (capped) = 0.05s of backoff, with headroom
    assert 0.04 < time.perf_counter() - t0 < 2.0


def test_injected_fault_is_retryable_oserror():
    sched = fregistry.FaultSchedule(("data.decode@call=1:count=2",))

    calls = []

    def decode():
        calls.append(1)
        sched.maybe_fire("data.decode")
        return "decoded"

    assert faults.retry_call(decode, policy=FAST,
                             point="data.decode") == "decoded"
    assert len(calls) == 3  # two injected failures absorbed


def test_decode_substitute_and_count():
    before = get_registry().family_total("records_skipped_total")

    def load(j):
        if j == 5:
            raise OSError("bad jpeg")
        return {"x": j}

    out = faults.decode_with_retry(load, 5, 10, policy=FAST)
    assert out == {"x": 6}  # neighbor substituted, shape preserved
    assert get_registry().family_total("records_skipped_total") == before + 1


def test_decode_all_substitutes_fail_raises():
    def load(j):
        raise OSError("disk gone")

    with pytest.raises(OSError, match="disk gone"):
        faults.decode_with_retry(load, 0, 10, policy=FAST)


# --------------------------------------------------------------- preemption
def _send_sigterm_to_self():
    os.kill(os.getpid(), signal.SIGTERM)


@pytest.mark.parametrize("watchdog_first", [True, False])
def test_preemption_composes_with_watchdog_dump(watchdog_first, capfd):
    """SIGTERM with BOTH handlers installed (either order) must dump the
    flight recorder AND set the preempt flag AND leave the process alive
    — the train loop owns the exit (utils/watchdog.py chaining +
    faults/preemption.py armed())."""
    from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder

    prev = signal.getsignal(signal.SIGTERM)
    fr = FlightRecorder(capacity=4)
    fr.record("step", 3)
    ph = PreemptionHandler()
    try:
        if watchdog_first:
            fr.install_signal_dump()
            ph.install()
        else:
            ph.install()
            fr.install_signal_dump()
        _send_sigterm_to_self()
        time.sleep(0.01)  # handler runs synchronously; settle stderr
        assert ph.requested  # flag set, no SystemExit raised
        err = capfd.readouterr().err
        assert "flight recorder" in err.lower()  # dump still happened
    finally:
        ph.uninstall()
        signal.signal(signal.SIGTERM, prev)
        fr._installed = False


def test_watchdog_alone_still_exits_143():
    """Without a preemption handler the dump handler keeps the legacy
    terminal behavior (SystemExit 143) — the existing preemption drill
    in test_fault_tolerance.py depends on it."""
    from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder

    prev = signal.getsignal(signal.SIGTERM)
    fr = FlightRecorder(capacity=4)
    try:
        fr.install_signal_dump()
        with pytest.raises(SystemExit) as exc:
            _send_sigterm_to_self()
            time.sleep(0.01)
        assert exc.value.code == 143
    finally:
        signal.signal(signal.SIGTERM, prev)
        fr._installed = False


# ---------------------------------------------------------------- integrity
def _write_fake_step(root, step, payload=b"x" * 64):
    sdir = os.path.join(root, str(step))
    os.makedirs(os.path.join(sdir, "state"))
    with open(os.path.join(sdir, "state", "data.bin"), "wb") as f:
        f.write(payload)
    with open(os.path.join(sdir, "_CHECKPOINT_METADATA"), "w") as f:
        f.write("{}")


def test_manifest_roundtrip(tmp_path):
    root = str(tmp_path)
    _write_fake_step(root, 2)
    integrity.write_manifest(root, 2, config_json='{"a": 1}')
    ok, reason = integrity.verify_step(root, 2)
    assert ok is True, reason
    body = json.load(open(integrity.manifest_path(root, 2)))
    assert body["step"] == 2
    assert "state/data.bin" in {os.path.normpath(k).replace(os.sep, "/")
                                for k in body["files"]}


def test_manifest_detects_truncation_and_tamper(tmp_path):
    root = str(tmp_path)
    _write_fake_step(root, 2)
    integrity.write_manifest(root, 2)
    target = os.path.join(root, "2", "state", "data.bin")
    with open(target, "r+b") as f:
        f.truncate(5)
    ok, reason = integrity.verify_step(root, 2)
    assert ok is False and "size mismatch" in reason
    # same-size tamper: content hash catches it
    _write_fake_step(root, 3, payload=b"a" * 64)
    integrity.write_manifest(root, 3)
    with open(os.path.join(root, "3", "state", "data.bin"), "wb") as f:
        f.write(b"b" * 64)
    ok, reason = integrity.verify_step(root, 3)
    assert ok is False and "hash mismatch" in reason


def test_manifest_missing_is_unknown_not_corrupt(tmp_path):
    _write_fake_step(str(tmp_path), 4)
    ok, reason = integrity.verify_step(str(tmp_path), 4)
    assert ok is None and reason == "no manifest"


def test_manifest_self_seal(tmp_path):
    root = str(tmp_path)
    _write_fake_step(root, 2)
    path = integrity.write_manifest(root, 2)
    body = json.load(open(path))
    body["files"] = {}  # an attacker/bitrot edits the manifest itself
    json.dump(body, open(path, "w"))
    ok, reason = integrity.verify_step(root, 2)
    assert ok is False and "seal" in reason


def test_prune_manifests(tmp_path):
    root = str(tmp_path)
    for s in (2, 4):
        _write_fake_step(root, s)
        integrity.write_manifest(root, s)
    integrity.prune_manifests(root, [4])
    assert not integrity.has_manifest(root, 2)
    assert integrity.has_manifest(root, 4)


# ------------------------------------------------- restore fallback (e2e)
def test_corrupt_latest_falls_back_to_previous_step(tmp_path, capsys):
    """Truncate a file inside the NEWEST checkpoint step — restore must
    skip it with a logged reason + counter and land on the previous
    manifest-verified step (latest_good_step fallback). Uses a bare TrainState (no
    mesh/model build): the contract is the manager's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
    from pytorch_distributed_train_tpu.config import CheckpointConfig
    from pytorch_distributed_train_tpu.train_state import TrainState

    tx = optax.sgd(0.1)
    params1 = {"w": jnp.arange(64.0), "b": jnp.ones((8,))}
    state1 = TrainState.create(params=params1, tx=tx)
    state1 = state1.replace(step=jnp.int32(1))
    ck = CheckpointManager(CheckpointConfig(dir=str(tmp_path / "ckpt"),
                                            async_save=False))
    assert ck.save(state1, step=1)
    state2 = state1.replace(
        step=jnp.int32(2),
        params=jax.tree.map(lambda x: x * 2.0, params1))
    assert ck.save(state2, step=2)
    ck.wait()
    assert integrity.has_manifest(ck.dir, 1)
    assert integrity.has_manifest(ck.dir, 2)
    assert ck.latest_good_step() == 2

    # Corrupt the NEWEST step: truncate its largest file (the manifest
    # lives outside the step dir, so the evidence survives).
    sdir = os.path.join(ck.dir, "2")
    biggest = max(
        (os.path.join(r, f) for r, _, fs in os.walk(sdir) for f in fs),
        key=os.path.getsize)
    with open(biggest, "r+b") as f:
        f.truncate(3)

    before = get_registry().family_total("ckpt_integrity_failures_total")
    assert ck.latest_good_step() == 1
    out = capsys.readouterr().out
    assert "failed integrity check" in out and "falling back" in out
    assert get_registry().family_total(
        "ckpt_integrity_failures_total") == before + 1

    # restore (no explicit step) lands on the previous good step with
    # the step-1 params intact.
    restored, _ = ck.restore(state1)
    assert int(restored.step) == 1
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(params1), jax.device_get(restored.params))
    ck.close()


def test_explicit_step_matches_without_trainer_loop():
    """check/maybe_fire accept an explicit step= so step-gated specs
    work in processes that never run the Trainer's set_step loop (a
    tool driving CheckpointManager.save directly)."""
    sched = fregistry.FaultSchedule(("ckpt.save_io@step=3",))
    assert sched.check("ckpt.save_io", step=2) is None
    assert sched.check("ckpt.save_io", step=3) is not None


def test_watchdog_chains_foreign_handler_but_still_exits():
    """A SIGTERM handler installed by some OTHER library chains, but
    without a graceful-preemption handler armed the dump handler keeps
    the terminal exit(143) guarantee — otherwise the job would train
    through its preemption grace window and be SIGKILLed with nothing
    saved."""
    from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder

    prev = signal.getsignal(signal.SIGTERM)
    seen = []
    signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    fr = FlightRecorder(capacity=4)
    try:
        fr.install_signal_dump()
        with pytest.raises(SystemExit) as exc:
            _send_sigterm_to_self()
            time.sleep(0.01)
        assert exc.value.code == 143
        assert seen == [signal.SIGTERM]  # the foreign handler DID run
    finally:
        signal.signal(signal.SIGTERM, prev)
        fr._installed = False
