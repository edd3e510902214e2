"""Tiered async checkpointing plane (ckpt/; docs/checkpointing.md):
async-vs-sync restore equivalence, snapshot-only blocking, back-pressure
drain, kill-during-persist fallback to the newest sealed step, peer
fetch over a fake store, retention pins, sentinel rewind tier hits, and
the per-worker compile-cache satellite."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from tiny import tiny_cfg

from pytorch_distributed_train_tpu import faults as faults_lib
from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
from pytorch_distributed_train_tpu.ckpt import (
    TieredCheckpointManager,
    build_checkpoint_manager,
)
from pytorch_distributed_train_tpu.ckpt import retention
from pytorch_distributed_train_tpu.ckpt import snapshot as snapshot_lib
from pytorch_distributed_train_tpu.config import CheckpointConfig, TrainConfig
from pytorch_distributed_train_tpu.faults.retry import (
    RetryPolicy,
    default_policy,
    set_default_policy,
)
from pytorch_distributed_train_tpu.obs.registry import get_registry
from pytorch_distributed_train_tpu.train_state import TrainState


@pytest.fixture(autouse=True)
def _clean_fault_schedule():
    """Each test owns the process-global fault schedule + retry policy."""
    prev_policy = default_policy()
    yield
    faults_lib.configure(())
    set_default_policy(prev_policy)


def _tiny_state(step: int = 0, seed: int = 0) -> TrainState:
    rng = np.random.default_rng(seed)
    params = {
        "dense": {"kernel": jnp.asarray(rng.standard_normal((8, 4)),
                                        jnp.float32),
                  "bias": jnp.asarray(rng.standard_normal(4), jnp.float32)},
    }
    state = TrainState.create(params=params, tx=optax.sgd(0.1, momentum=0.9),
                              batch_stats={})
    return state.replace(step=jnp.int32(step))


def _assert_trees_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def _tier_hits(tier: str) -> float:
    return get_registry().get_value("ckpt_restore_tier_total",
                                    {"tier": tier}) or 0.0


class FakeStore:
    """Dict-backed stand-in for native/store.py StoreClient (the peer
    plane only needs set/get/delete)."""

    def __init__(self):
        self.kv: dict[str, bytes] = {}

    def set(self, key, value):
        self.kv[key] = bytes(value)

    def get(self, key, timeout_ms=0, max_len=0):
        if key not in self.kv:
            raise TimeoutError(key)
        return self.kv[key]

    def delete(self, key):
        self.kv.pop(key, None)

    def close(self):
        pass


# ------------------------------------------------------------- snapshot unit
def test_snapshot_seal_verify_and_wire_roundtrip():
    state = _tiny_state(step=5)
    from pytorch_distributed_train_tpu.checkpoint import _savable

    snap = snapshot_lib.take_snapshot(_savable(state), step=5, epoch=1)
    assert not snapshot_lib.verify(snap)  # unsealed never verifies
    snapshot_lib.seal(snap)
    assert snapshot_lib.verify(snap)
    # wire roundtrip: leaves + header CRC-verify, order preserved
    payload = snapshot_lib.serialize_leaves(snap)
    header = snapshot_lib.snapshot_meta(snap)
    assert snapshot_lib.verify_payload(payload, header)
    leaves = snapshot_lib.deserialize_leaves(payload)
    t_leaves = jax.tree_util.tree_leaves(snap.tree)
    assert snapshot_lib.leaves_match_template(leaves, t_leaves)
    for got, want in zip(leaves, t_leaves):
        np.testing.assert_array_equal(got, want)
    # corruption detected at both layers
    snap.tree["params"]["dense"]["bias"] = (
        snap.tree["params"]["dense"]["bias"] + 1.0)
    assert not snapshot_lib.verify(snap)
    assert not snapshot_lib.verify_payload(payload[:-8], header)


# ------------------------------------------------------------ retention unit
def test_retention_plan_keep_rules_and_pins():
    assert retention.plan_evictions([1, 2, 3, 4], keep_last=2) == [1, 2]
    assert retention.plan_evictions([10, 20, 30, 40], keep_last=1,
                                    keep_every=20) == [10, 30]
    assert retention.plan_evictions([], keep_last=2) == []
    # pins always survive, regardless of age
    assert retention.plan_evictions([1, 2, 3], keep_last=1,
                                    pinned=[1]) == [2]


def test_gc_never_deletes_newest_verified_step(tmp_path):
    """The acceptance property: however aggressive the keep policy, the
    newest verified step is pinned in both hot tiers."""
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           hot_keep=1, peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}")
    for s in (1, 2, 3):
        assert tm.save(_tiny_state(step=s), epoch=0, step=s)
        tm.wait()
    # keep_last=1 would keep only step 3; the newest verified persistent
    # step IS 3 here, so older hot steps age out but 3 stays everywhere.
    tiers = tm.steps_by_tier()
    assert tiers["persistent"] == [1, 2, 3]  # Orbax max_to_keep=3 default
    assert tm.latest_good_step() == 3
    assert 3 in tiers["ram"] and 3 in tiers["disk"]
    assert tiers["ram"] == [3]  # keep_last=1 evicted 1, 2
    # and the planner itself refuses to evict a pinned newest-verified
    assert 3 not in retention.plan_evictions([1, 2, 3], keep_last=1,
                                             pinned=[3])
    tm.close()


# ------------------------------------------------- async save / equivalence
def test_async_restore_byte_identical_to_sync_and_blocking_small(tmp_path):
    state = _tiny_state(step=4, seed=7)
    # sync plane: the pre-existing Orbax path
    sync = CheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "sync"), async_save=False), "{}")
    assert sync.save(state, epoch=2, step=4)
    sync.wait()
    # tiered plane, with an artificially slow persistent write so the
    # blocking/persist split is unambiguous even on a noisy CPU box
    cfg = CheckpointConfig(dir=str(tmp_path / "tiered"), tiered=True,
                           peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}")
    orig_save = tm.persistent.save

    def slow_save(*a, **k):
        time.sleep(0.8)
        return orig_save(*a, **k)

    tm.persistent.save = slow_save
    assert tm.save(state, epoch=2, step=4)
    tm.wait()
    reg = get_registry()
    blocking_ms = reg.get_value("ckpt_last_blocking_ms")
    persist_ms = reg.get_value("ckpt_last_persist_ms")
    assert blocking_ms is not None and persist_ms is not None
    assert persist_ms >= 800.0
    # step-boundary blocking is snapshot-only: a small fraction of the
    # total persist pipeline
    assert blocking_ms < persist_ms * 0.5

    sync_restored, sync_meta = sync.restore(_tiny_state())
    # RAM-tier restore == sync restore, byte-identical params/opt_state
    ram_restored, ram_meta = tm.restore(_tiny_state())
    assert int(ram_restored.step) == 4 and ram_meta["epoch"] == 2
    _assert_trees_equal(jax.device_get(ram_restored.params),
                        jax.device_get(sync_restored.params))
    _assert_trees_equal(jax.device_get(ram_restored.opt_state),
                        jax.device_get(sync_restored.opt_state))
    assert sync_meta["epoch"] == ram_meta["epoch"]
    tm.close()
    # Orbax-tier restore of the async-written checkpoint (fresh manager,
    # hot tiers disabled) is byte-identical too
    cold = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "tiered"), tiered=True,
                         hot_disk=False, peer_fetch=False), "{}")
    before = _tier_hits("orbax")
    orbax_restored, _ = cold.restore(_tiny_state())
    assert _tier_hits("orbax") == before + 1
    _assert_trees_equal(jax.device_get(orbax_restored.params),
                        jax.device_get(sync_restored.params))
    _assert_trees_equal(jax.device_get(orbax_restored.opt_state),
                        jax.device_get(sync_restored.opt_state))
    cold.close()
    sync.close()


def test_backpressure_drain_accounted(tmp_path):
    """Second save boundary arriving mid-persist waits (single persist
    in flight) and the wait lands in the ckpt.drain goodput bucket."""
    from pytorch_distributed_train_tpu.obs.goodput import GoodputTracker

    gp = GoodputTracker()
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}", goodput=gp)
    orig_save = tm.persistent.save

    def slow_save(*a, **k):
        time.sleep(0.5)
        return orig_save(*a, **k)

    tm.persistent.save = slow_save
    with gp.measure("ckpt"):
        assert tm.save(_tiny_state(step=1), epoch=0, step=1)
    with gp.measure("ckpt"):
        assert tm.save(_tiny_state(step=2), epoch=0, step=2)  # drains 1
    tm.wait()
    assert gp.buckets.get("ckpt.drain", 0.0) > 0.1
    # reattribution preserves the bucket sum (ckpt gave what drain got)
    assert gp.buckets["ckpt"] >= 0.0
    tm.close()


# -------------------------------------------------- kill-during-persist path
def test_failed_persist_falls_back_to_newest_sealed_step(tmp_path):
    """Persist of step 2 dies after the hot seal+spill (the pipeline
    order guarantee): restores still land on step 2 from the disk tier;
    corrupting that spill falls back to step 1 (Orbax-verified)."""
    set_default_policy(RetryPolicy(max_attempts=2, base_delay_s=0.01,
                                   max_delay_s=0.02))
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}")
    assert tm.save(_tiny_state(step=1, seed=1), epoch=0, step=1)
    tm.wait()
    # every Orbax write for step >= 2 fails — the persister gives up
    faults_lib.configure(("ckpt.persist_io@step=2:count=99",))
    state2 = _tiny_state(step=2, seed=2)
    assert tm.save(state2, epoch=0, step=2)
    with pytest.raises(OSError):
        tm.wait()  # the terminal persist error escalates to the waiter
    tiers = tm.steps_by_tier()
    assert tiers["persistent"] == [1] and 2 in tiers["disk"]
    assert (get_registry().get_value("ckpt_persist_failures_total")
            or 0) >= 1
    tm.close()
    faults_lib.configure(())

    # fresh process: RAM gone, disk survives → newest SEALED step wins
    tm2 = TieredCheckpointManager(cfg, "{}")
    assert tm2.latest_good_step() == 2
    before = _tier_hits("disk")
    restored, _ = tm2.restore(_tiny_state())
    assert int(restored.step) == 2
    assert _tier_hits("disk") == before + 1
    _assert_trees_equal(jax.device_get(restored.params),
                        jax.device_get(state2.params))
    tm2.close()

    # truncate the spill of step 2 → verification fails → fall back to
    # the newest Orbax-verified step (1), counting the corruption
    npz = tmp_path / "c" / "hot" / "host_0" / "step_2" / "data.npz"
    npz.write_bytes(npz.read_bytes()[:64])
    tm3 = TieredCheckpointManager(cfg, "{}")
    before_corrupt = get_registry().get_value("ckpt_hot_corrupt_total") or 0
    restored3, _ = tm3.restore(_tiny_state())
    assert int(restored3.step) == 1
    assert (get_registry().get_value("ckpt_hot_corrupt_total")
            or 0) > before_corrupt
    tm3.close()


def test_foreign_hot_dir_snapshot_never_restored(tmp_path):
    """A node-local hot_dir outliving its run (config guidance: point it
    at scratch) must not hand a NEW experiment the old run's state just
    because shapes/dtypes match — run identity (the persistent dir) is
    stamped into every spill and checked on restore."""
    hot = str(tmp_path / "scratch")
    old_state = _tiny_state(step=9, seed=11)
    old = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "old_run"), tiered=True,
                         hot_dir=hot, peer_fetch=False), "{}")
    assert old.save(old_state, epoch=0, step=9)
    old.wait()
    old.close()
    # fresh experiment, same architecture, same scratch dir
    new = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "new_run"), tiered=True,
                         hot_dir=hot, peer_fetch=False), "{}")
    assert new.latest_good_step() is None  # foreign spills are not ours
    assert new.restore(_tiny_state()) is None
    assert new.restore(_tiny_state(), step=9) is None  # even explicitly
    new.close()
    # the old run itself still restores its own spill after a restart
    again = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "old_run"), tiered=True,
                         hot_dir=hot, peer_fetch=False), "{}")
    restored, _ = again.restore(_tiny_state())
    assert int(restored.step) == 9
    again.close()


def test_stale_persist_error_does_not_poison_later_wait(tmp_path):
    """A terminal persist failure surfaces at the NEXT drain/wait only;
    once a later persist has been submitted (and succeeded), wait() must
    not re-raise the hours-old error — a finished job whose final
    checkpoint landed must not fail on history."""
    set_default_policy(RetryPolicy(max_attempts=2, base_delay_s=0.01,
                                   max_delay_s=0.02))
    faults_lib.configure(("ckpt.persist_io@step=1:count=2",))  # step 1 only
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}")
    assert tm.save(_tiny_state(step=1), epoch=0, step=1)
    deadline = time.time() + 30
    while tm.persister.busy and time.time() < deadline:
        time.sleep(0.01)  # let the failing persist finish WITHOUT drain
    assert tm.save(_tiny_state(step=2), epoch=0, step=2)
    tm.wait()  # step 2 persisted fine — no stale step-1 error
    assert tm.steps_by_tier()["persistent"] == [2]
    assert tm.latest_good_step() == 2
    tm.close()


# ----------------------------------------------------------------- peer tier
def test_peer_fetch_restore_with_fake_store(tmp_path):
    store = FakeStore()
    state = _tiny_state(step=7, seed=3)
    # host 0 trains, seals, publishes
    h0 = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "h0"), tiered=True), "{}",
        store=store, host_id=0, peer_hosts=[0, 1])
    assert h0.save(state, epoch=2, step=7)
    h0.wait()
    assert any(k.startswith("ckptp/0/") for k in store.kv)
    h0.close()
    # host 1 restarts cold (own dir: no RAM, no disk, no Orbax) — with a
    # transient injected fetch fault absorbed by the retry policy
    set_default_policy(RetryPolicy(max_attempts=3, base_delay_s=0.01,
                                   max_delay_s=0.02))
    faults_lib.configure(("ckpt.peer_fetch@call=1:count=1",))
    h1 = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "h1"), tiered=True), "{}",
        store=store, host_id=1, peer_hosts=[0, 1])
    assert h1.latest_good_step() == 7  # advertised by the peer
    before = _tier_hits("peer")
    restored, meta = h1.restore(_tiny_state())
    assert int(restored.step) == 7 and meta["epoch"] == 2
    assert _tier_hits("peer") == before + 1
    _assert_trees_equal(jax.device_get(restored.params),
                        jax.device_get(state.params))
    retried = get_registry().get_value("retries_total",
                                       {"point": "ckpt.peer_fetch"})
    assert (retried or 0) >= 1
    h1.close()


# ----------------------------------------------------- sentinel rewind tiers
def _e2e_cfg(d: str) -> TrainConfig:
    return tiny_cfg(
        "data.batch_size=32", "total_steps=6", f"checkpoint.dir={d}",
        "checkpoint.save_every_steps=2", "checkpoint.async_save=true",
        "checkpoint.tiered=true", "checkpoint.peer_fetch=false",
        "obs.log_every_steps=100", "sentinel.enabled=true",
        "sentinel.max_consecutive_bad=1", "sentinel.spike_min_samples=2")


def test_sentinel_rewind_restores_from_ram_tier(tmp_path):
    """Auto-rewind under the tiered plane: the restore is served from
    host RAM (tier-hit metric), and the summary still records the
    rewind. The drain in _sentinel_rewind's ckpt.wait() guarantees the
    rewind target's persist committed first."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = _e2e_cfg(str(tmp_path / "run"))
    cfg.faults.inject = ("step.loss_spike@step=5",)
    before = _tier_hits("ram")
    t = Trainer(cfg)
    t.fit()
    assert t._rewinds == 1
    assert _tier_hits("ram") >= before + 1
    t.close()
    recs = [json.loads(line)
            for line in open(os.path.join(cfg.checkpoint.dir,
                                          "metrics.jsonl"))]
    summary = [r for r in recs if r["tag"] == "summary"][-1]
    assert summary["rewinds"] == 1
    # blocking vs persist metric pair exists for the cadence saves
    assert get_registry().get_value("ckpt_last_blocking_ms") is not None
    assert get_registry().get_value("ckpt_last_persist_ms") is not None


def test_rewind_falls_back_to_orbax_when_hot_corrupt(tmp_path):
    """Hot tier cold/corrupt → the rewind path still lands on
    latest_good_step() via the persistent tier."""
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           hot_disk=False, peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}")
    state = _tiny_state(step=3, seed=5)
    assert tm.save(state, epoch=1, step=3)
    tm.wait()
    # corrupt the RAM copy in place: CRC verification must catch it
    snap = tm.ram.get(3)
    snap.tree["params"]["dense"]["kernel"][...] += 1.0
    good = tm.latest_good_step()
    assert good == 3  # the persistent step verified via its manifest
    before_orbax = _tier_hits("orbax")
    before_corrupt = get_registry().get_value("ckpt_hot_corrupt_total") or 0
    restored, _ = tm.restore(_tiny_state(), step=good)
    assert int(restored.step) == 3
    assert _tier_hits("orbax") == before_orbax + 1
    assert (get_registry().get_value("ckpt_hot_corrupt_total")
            or 0) > before_corrupt
    # the Orbax copy predates the corruption: bytes match the original
    _assert_trees_equal(jax.device_get(restored.params),
                        jax.device_get(state.params))
    tm.close()


# --------------------------------------------- satellite: compile-cache dirs
def test_per_worker_compile_cache_dirs(tmp_path, monkeypatch):
    from pytorch_distributed_train_tpu import elastic

    base = str(tmp_path / "cc")
    assert elastic.worker_cache_dir(base, 0) != elastic.worker_cache_dir(
        base, 1)
    # _spawn hands each worker its own PDTT_COMPILE_CACHE_DIR
    envs = []

    class _FakeProc:
        pid = 0

        def poll(self):
            return 0

    def fake_popen(cmd, env=None):
        envs.append(env)
        return _FakeProc()

    monkeypatch.setattr(elastic.subprocess, "Popen", fake_popen)
    agent = elastic.ElasticAgent(
        elastic.LaunchConfig(nprocs=2, compile_cache_base=base), ["true"])
    agent.coord_port = 1
    agent.store_port = 2
    agent._spawn(0)
    dirs = [e["PDTT_COMPILE_CACHE_DIR"] for e in envs]
    assert len(dirs) == 2 and len(set(dirs)) == 2
    assert all(d.startswith(base) for d in dirs)
    # without a base, the env var is not set at all
    envs.clear()
    agent2 = elastic.ElasticAgent(elastic.LaunchConfig(nprocs=1), ["true"])
    agent2.coord_port = 1
    agent2.store_port = 2
    agent2._spawn(0)
    assert "PDTT_COMPILE_CACHE_DIR" not in envs[0]


# ------------------------------------------------- satellite: inspector tool
def test_ckpt_inspect_smoke(tmp_path, capsys):
    import tools.ckpt_inspect as inspect_tool

    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}")
    for s in (1, 2):
        tm.save(_tiny_state(step=s), epoch=0, step=s)
        tm.wait()
    tm.close()
    assert inspect_tool.main(["--dir", cfg.dir]) == 0
    out = capsys.readouterr().out
    assert "persistent tier" in out and "hot disk tier" in out
    report = inspect_tool.inspect_dir(cfg.dir)
    assert report["restore_would_land_on"] == 2
    assert report["newest_verified_persistent"] == 2
    assert [r["step"] for r in report["persistent"]] == [1, 2]
    assert all(r["verdict"] == "verified" for r in report["persistent"])
    # a missing dir is a clean nonzero exit, not a traceback
    assert inspect_tool.main(["--dir", str(tmp_path / "nope")]) == 1


# --------------------------------------------- satellite: catalog stays sync
def test_new_fault_points_cataloged():
    from pytorch_distributed_train_tpu.faults.registry import POINTS
    from tools.check_fault_points import documented_points, main

    assert {"ckpt.persist_io", "ckpt.peer_fetch"} <= set(POINTS)
    assert {"ckpt.persist_io", "ckpt.peer_fetch"} <= documented_points()
    assert main() == 0
