"""Elastic resharding (docs/elastic.md): restore any checkpoint tier
onto a DIFFERENT mesh shape, byte-exactly, and reshard the input stream
mid-epoch when the world changes.

- Orbax reshard-on-restore: save on an N-device mesh, restore on M
  (shrink AND grow), params/opt_state — including the sentinel
  LR-cooldown leaf — plus step/SWA counters byte-identical.
- Hot (disk) tier: host-side global leaves device_put into the new
  mesh's shardings.
- Peer tier: per-host SHARD payloads reassembled into global leaves
  (a dead host's pieces outlive it on the store), then resharded.
- Union-of-shards: for BOTH loaders, the union of all hosts' batch b
  is the same global index set at any world size, including a
  mid-epoch start_batch resume with a changed shard_count.
- The 4→3 e2e drill: kill one host permanently; survivors re-rendezvous
  degraded, restore resharded, resume mid-epoch, and the loss
  trajectory matches a fixed-3-host control run bit-exactly."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from tiny import WORKER_HEAD, tiny_cfg

from pytorch_distributed_train_tpu import steps as steps_lib
from pytorch_distributed_train_tpu.checkpoint import CheckpointManager
from pytorch_distributed_train_tpu.ckpt import TieredCheckpointManager
from pytorch_distributed_train_tpu.ckpt import snapshot as snapshot_lib
from pytorch_distributed_train_tpu.config import (
    CheckpointConfig,
    DataConfig,
    ModelConfig,
    OptimConfig,
)
from pytorch_distributed_train_tpu.optim import make_optimizer
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
from pytorch_distributed_train_tpu.parallel.partition import (
    PartitionRules,
    dense_rules,
)
from pytorch_distributed_train_tpu.sentinel import numeric as sentinel_numeric
from pytorch_distributed_train_tpu.train_state import TrainState

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeStore:
    """Dict-backed stand-in for native store (peer-plane set/get/delete)."""

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


# ------------------------------------------------------- state helpers
def _make_state(mesh, *, step: int, seed: int = 0,
                cooldown: float | None = 0.25) -> TrainState:
    """A TrainState with real structure: rules-sharded params, momentum
    opt_state, the sentinel LR-cooldown leaf, and the SWA counter —
    every kind of leaf the reshard restore must carry exactly."""
    rng = np.random.default_rng(seed)
    params = {
        "dense": {"kernel": jnp.asarray(rng.standard_normal((8, 8)),
                                        jnp.float32),
                  "bias": jnp.asarray(rng.standard_normal(8), jnp.float32)},
        "tok_embed": {"embedding": jnp.asarray(
            rng.standard_normal((16, 8)), jnp.float32)},
    }
    tx, _ = make_optimizer(
        OptimConfig(name="momentum", learning_rate=0.1, schedule="constant",
                    warmup_steps=0), 100, 10, sentinel_cooldown=True)
    state = TrainState.create(params=params, tx=tx, batch_stats={}, swa=True)
    state = state.replace(step=jnp.int32(step), swa_count=jnp.int32(3))
    if cooldown is not None:
        state = state.replace(opt_state=sentinel_numeric.scale_cooldown(
            state.opt_state, cooldown))
    rules = PartitionRules(dense_rules())
    sh = steps_lib.state_shardings(mesh, rules,
                                   jax.eval_shape(lambda: state))
    return jax.device_put(state, sh), sh


def _abstract(state, sh):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, sh)


def _assert_state_equal(got, want):
    for name in ("params", "opt_state"):
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(jax.device_get(a)),
                np.asarray(jax.device_get(b))),
            getattr(got, name), getattr(want, name))
    assert int(got.step) == int(want.step)
    assert int(got.swa_count) == int(want.swa_count)
    got_cd = sentinel_numeric.cooldown_scale(got.opt_state)
    want_cd = sentinel_numeric.cooldown_scale(want.opt_state)
    assert got_cd == want_cd  # the sentinel LR-cooldown leaf


# ------------------------------------------- Orbax reshard-on-restore
@pytest.mark.parametrize("n_save,n_restore", [(4, 3), (4, 8), (4, 2)])
def test_orbax_restore_reshards_byte_identical(tmp_path, devices8,
                                               n_save, n_restore):
    """Save on an N-device fsdp mesh, restore on M devices: every leaf
    byte-identical, landed in the NEW mesh's shardings (dims M cannot
    divide fall back to replication — parallel/partition.validate_spec
    — still byte-identical)."""
    from pytorch_distributed_train_tpu.config import MeshConfig

    mesh_a = build_mesh(MeshConfig(data=1, fsdp=-1),
                        devices=devices8[:n_save])
    state, _sh = _make_state(mesh_a, step=7, seed=3)
    mgr = CheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "c"), async_save=False), "{}",
        run_meta={"world": n_save, "global_batch": 16})
    assert mgr.save(state, epoch=1, step=7)
    mgr.wait()
    mgr.close()

    mesh_b = build_mesh(MeshConfig(data=1, fsdp=-1),
                        devices=devices8[:n_restore])
    fresh, sh_b = _make_state(mesh_b, step=0, seed=99, cooldown=None)
    mgr2 = CheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "c"), async_save=False), "{}")
    restored, meta = mgr2.restore(_abstract(fresh, sh_b))
    mgr2.close()
    _assert_state_equal(restored, state)
    assert meta["epoch"] == 1 and meta["world"] == n_save
    assert meta["global_batch"] == 16
    # the restored arrays live on the NEW mesh's devices
    kernel = restored.params["dense"]["kernel"]
    assert kernel.sharding.device_set <= set(devices8[:n_restore])


def test_hot_disk_tier_restores_onto_different_mesh(tmp_path, devices8):
    """Tiered plane: a per-host disk spill taken on a 4-device mesh
    restores onto a 2-device mesh (host leaves are GLOBAL; device_put
    reshards at placement) — disk tier hit, bytes equal."""
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    mesh_a = build_mesh(MeshConfig(data=1, fsdp=-1), devices=devices8[:4])
    state, _sh = _make_state(mesh_a, step=5, seed=11)
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           peer_fetch=False)
    tm = TieredCheckpointManager(cfg, "{}", run_meta={"world": 4})
    assert tm.save(state, epoch=0, step=5)
    tm.wait()
    tm.close()

    mesh_b = build_mesh(MeshConfig(data=1, fsdp=-1), devices=devices8[:2])
    fresh, sh_b = _make_state(mesh_b, step=0, seed=1, cooldown=None)
    tm2 = TieredCheckpointManager(cfg, "{}")
    before = get_registry().get_value("ckpt_restore_tier_total",
                                      {"tier": "disk"}) or 0
    restored, meta = tm2.restore(_abstract(fresh, sh_b))
    tm2.close()
    assert (get_registry().get_value("ckpt_restore_tier_total",
                                     {"tier": "disk"}) or 0) == before + 1
    _assert_state_equal(restored, state)
    assert meta["world"] == 4  # run_meta rode the snapshot header too


# --------------------------------------------- peer shard reconstruction
def test_peer_shard_payloads_reassemble_and_reshard(tmp_path, devices8):
    """Two 'hosts' publish only the SHARDS they own; a restoring
    survivor reassembles the global leaves from BOTH payloads (the dead
    host's outlives it on the store) and reshards onto a smaller mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_distributed_train_tpu.ckpt import peer
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    mesh_a = build_mesh(MeshConfig(data=-1), devices=devices8[:4])
    rng = np.random.default_rng(5)
    w = jax.device_put(
        jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
        NamedSharding(mesh_a, PartitionSpec("data")))
    b = jax.device_put(jnp.asarray(rng.standard_normal(4), jnp.float32),
                       NamedSharding(mesh_a, PartitionSpec()))
    savable = {"step": jnp.int32(9), "params": {"w": w, "b": b}}

    host_devs = {0: set(devices8[:2]), 1: set(devices8[2:4])}
    store = FakeStore()
    for host, devs in host_devs.items():
        payload, header = snapshot_lib.take_shard_snapshot(
            savable, step=9, epoch=2,
            owned=lambda s, _d=devs: s.device in _d and s.replica_id == 0)
        assert snapshot_lib.verify_shard_payload(payload, header)
        peer.publish(store, host, header, payload)

    # neither host's payload alone covers the sharded leaf
    one = snapshot_lib.take_shard_snapshot(
        savable, step=9,
        owned=lambda s: s.device in host_devs[0] and s.replica_id == 0)
    assert snapshot_lib.assemble_shards([one]) is None

    fetched = peer.fetch_state(store, 9, [0, 1])
    assert fetched is not None and fetched[0] == "leaves"
    _kind, leaves, header = fetched
    want = [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(savable)]
    assert len(leaves) == len(want)
    for got_leaf, want_leaf in zip(leaves, want):
        np.testing.assert_array_equal(got_leaf, want_leaf)
    assert header["epoch"] == 2

    # end to end through the manager: a new-world host restores step 9
    # from the store onto a 2-device mesh
    mesh_b = build_mesh(MeshConfig(data=-1), devices=devices8[4:6])
    # shape the template exactly like the published savable
    fresh = TrainState.create(
        params={"w": jnp.zeros((8, 4), jnp.float32),
                "b": jnp.zeros(4, jnp.float32)},
        tx=optax.identity(), batch_stats={})
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh_b, PartitionSpec())), fresh)
    tm = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "survivor"), tiered=True),
        "{}", store=store, host_id=2, peer_hosts=[0, 1, 2])
    assert tm.latest_good_step() == 9
    before = get_registry().get_value("ckpt_restore_tier_total",
                                      {"tier": "peer"}) or 0
    restored, meta = tm.restore(template)
    tm.close()
    assert (get_registry().get_value("ckpt_restore_tier_total",
                                     {"tier": "peer"}) or 0) == before + 1
    assert int(restored.step) == 9 and meta["epoch"] == 2
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.params["w"])),
        np.asarray(jax.device_get(w)))
    assert restored.params["w"].sharding.device_set <= set(devices8[4:6])


def test_assemble_rejects_corrupt_and_incomplete():
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    payload, header = snapshot_lib.take_shard_snapshot({"x": x}, step=1)
    leaves, _ = snapshot_lib.assemble_shards([(payload, header)])
    np.testing.assert_array_equal(leaves[0], np.asarray(x))
    # corrupt payload → CRC mismatch → None
    assert snapshot_lib.assemble_shards([(payload[:-8], header)]) is None
    # header step mismatch across hosts → None
    other = dict(header, step=2)
    assert snapshot_lib.assemble_shards(
        [(payload, header), (payload, other)]) is None


# -------------------------------------------------- union of shards
def _loader_cfg(**kw) -> DataConfig:
    return DataConfig(dataset="synthetic_images", batch_size=12,
                      num_workers=0, seed=7, synthetic_size=48, **kw)


def _union_stream(loader_cls, ds, cfg, world, start_batch=0):
    """Per GLOBAL batch: sorted multiset of row bytes over all hosts."""
    loaders = [loader_cls(ds, cfg, train=True, num_hosts=world, host_id=h)
               for h in range(world)]
    iters = [iter(loader.epoch(0, start_batch)) for loader in loaders]
    out = []
    while True:
        batches = []
        try:
            for it in iters:
                batches.append(next(it))
        except StopIteration:
            break
        rows = []
        for batch in batches:
            n = len(next(iter(batch.values())))
            for i in range(n):
                rows.append(b"|".join(
                    np.ascontiguousarray(batch[k][i]).tobytes()
                    for k in sorted(batch)))
        out.append(sorted(rows))
    return out


@pytest.mark.parametrize("loader_name", ["threads", "grain"])
def test_union_of_shards_invariant_to_world_and_resume(loader_name):
    """The elastic-reshard data contract: the union of all hosts' batch
    b is the same global index set at world 1, 3 and 4 — and a
    mid-epoch resume (start_batch) on a DIFFERENT world continues the
    exact same global stream, for both loaders."""
    from pytorch_distributed_train_tpu.data.datasets import build_dataset

    cfg = _loader_cfg(loader=loader_name)
    ds = build_dataset(cfg, ModelConfig(image_size=8, num_classes=10),
                       train=True)
    if loader_name == "grain":
        from pytorch_distributed_train_tpu.data.grain_pipeline import (
            GrainHostDataLoader as cls,
        )
    else:
        from pytorch_distributed_train_tpu.data.pipeline import (
            HostDataLoader as cls,
        )
    s4 = _union_stream(cls, ds, cfg, 4)
    s3 = _union_stream(cls, ds, cfg, 3)
    s1 = _union_stream(cls, ds, cfg, 1)
    assert len(s4) == len(s3) == len(s1) == 4  # 48 / 12
    for b, (a4, a3, a1) in enumerate(zip(s4, s3, s1)):
        assert a4 == a3 == a1, f"global batch {b} diverged across worlds"
    # mid-epoch resume with CHANGED shard_count: 4-host run died after
    # batch 1; 3 survivors resume at start_batch=2
    resumed = _union_stream(cls, ds, cfg, 3, start_batch=2)
    assert resumed == s4[2:]


# ---------------------------------------------- launcher world plane
def test_elastic_world_env_contract(monkeypatch):
    from pytorch_distributed_train_tpu.elastic import elastic_world

    monkeypatch.delenv("NUM_PROCESSES", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    assert elastic_world() == (1, 0)
    monkeypatch.setenv("NUM_PROCESSES", "3")
    monkeypatch.setenv("PROCESS_ID", "2")
    assert elastic_world() == (3, 2)
    # a PRESENT but inconsistent contract is loud, never a silent
    # 1-host world (which would un-shard this host's data stream)
    monkeypatch.setenv("PROCESS_ID", "7")  # stale rank beyond the world
    with pytest.raises(RuntimeError, match="corrupt launcher env"):
        elastic_world()
    monkeypatch.setenv("PROCESS_ID", "nope")
    with pytest.raises(RuntimeError, match="corrupt launcher env"):
        elastic_world()


def test_agent_publishes_world_and_store_helpers():
    from pytorch_distributed_train_tpu.elastic import (
        WORLD_MAX_KEY,
        ElasticAgent,
        LaunchConfig,
        store_world,
        store_world_max,
    )

    store = FakeStore()
    agent = ElasticAgent(LaunchConfig(nprocs=2, nnodes=3, min_nnodes=2),
                         ["true"])
    agent.agent_client = store
    agent._publish_world(1, [0, 2], 2)
    rec = store_world(store, 1)
    assert rec == {"gen": 1, "members": [0, 2], "nodes": 2, "nprocs": 2,
                   "world": 4}
    store.set(WORLD_MAX_KEY, b"6")
    assert store_world_max(store, 1) == 6
    assert store_world_max(FakeStore(), 4) == 4  # absent → default
    assert store_world(store, 99) is None


def test_manager_peer_hosts_use_world_max(tmp_path):
    """After a shrink the manager must enumerate the ORIGINAL world's
    ranks (elastic/world_max), not the current one — a dead host's
    published snapshot lives under its old rank."""
    from pytorch_distributed_train_tpu.elastic import WORLD_MAX_KEY

    store = FakeStore()
    store.set(WORLD_MAX_KEY, b"4")
    tm = TieredCheckpointManager(
        CheckpointConfig(dir=str(tmp_path / "c"), tiered=True), "{}",
        store=store, host_id=0)
    assert tm._hosts() == [0, 1, 2, 3]
    tm.close()


# ------------------------------------ trainer reshard detection (1-proc)
def test_trainer_reshard_event_and_batch_guard(tmp_path, monkeypatch):
    from pytorch_distributed_train_tpu.obs.events import load_events
    from pytorch_distributed_train_tpu.trainer import Trainer

    monkeypatch.delenv("NUM_PROCESSES", raising=False)
    monkeypatch.delenv("PROCESS_ID", raising=False)
    cfg = tiny_cfg(
        "data.synthetic_size=64", "data.elastic_shards=true",
        "optim.learning_rate=0.1", "total_steps=3",
        f"checkpoint.dir={tmp_path}/ckpt", "checkpoint.save_every_steps=2",
        "obs.log_every_steps=10")
    t = Trainer(cfg)
    t.fit()
    t.close()
    # the checkpoint meta carries the world + global batch it trained on
    mgr = CheckpointManager(CheckpointConfig(dir=cfg.checkpoint.dir,
                                             async_save=False, resume="none"))
    meta = mgr.read_meta()
    mgr.close()
    assert meta["world"] == 1 and meta["global_batch"] == 16

    # a resumed generation on a different world journals the reshard
    monkeypatch.setenv("NUM_PROCESSES", "2")
    monkeypatch.setenv("PROCESS_ID", "0")
    t2 = Trainer(cfg)
    assert t2.world == 2 and t2.resumed
    assert t2.train_loader.host_batch == 8  # global 16 / world 2
    t2.close()
    events = load_events(os.path.join(cfg.checkpoint.dir, "events"))
    reshards = [e for e in events if e["category"] == "elastic"
                and e["name"] == "reshard"]
    assert reshards and reshards[-1]["detail"]["from_world"] == 1
    assert reshards[-1]["detail"]["to_world"] == 2

    # a changed GLOBAL batch is refused loudly (the documented policy)
    cfg.data.batch_size = 32
    with pytest.raises(ValueError, match="GLOBAL batch"):
        Trainer(cfg)


# ----------------------------------------- inspector --mesh satellite
def test_ckpt_inspect_mesh_feasibility(tmp_path, devices8):
    import tools.ckpt_inspect as inspect_tool
    from pytorch_distributed_train_tpu.config import MeshConfig, TrainConfig

    mesh = build_mesh(MeshConfig(data=1, fsdp=-1), devices=devices8[:4])
    state, _sh = _make_state(mesh, step=6, seed=2)
    cfg = CheckpointConfig(dir=str(tmp_path / "c"), tiered=True,
                           peer_fetch=False)
    # model.name must map to the SAME rule set _make_state sharded the
    # saved state with (dense_rules) — feasibility re-derives rules from
    # the checkpoint's own saved config, exactly like a resharded restore
    tcfg = TrainConfig()
    tcfg.model.name = "dense"
    tm = TieredCheckpointManager(cfg, tcfg.to_json(),
                                 run_meta={"world": 4, "global_batch": 12})
    assert tm.save(state, epoch=0, step=6)
    tm.wait()
    tm.close()
    assert inspect_tool.parse_mesh("data=2,fsdp=3") == {"data": 2,
                                                        "fsdp": 3}
    with pytest.raises(ValueError):
        inspect_tool.parse_mesh("bogus=2")
    # fsdp=3: the (8,8) kernel / (16,8) embedding shard dim 8 % 3 != 0
    # → replication fallbacks reported; restore still feasible
    rep = inspect_tool.mesh_feasibility(cfg.dir, {"data": 1, "fsdp": 3})
    assert rep["feasible"] is True and rep["step"] == 6
    assert rep["fallback_leaves"], "expected replication fallbacks"
    assert rep["batch_divisible"] is True  # 12 % (1*3) == 0
    assert rep["reshard_would_land_on"] == 6
    # fsdp=2 divides everything: no fallbacks
    rep2 = inspect_tool.mesh_feasibility(cfg.dir, {"fsdp": 2})
    assert rep2["fallback_leaves"] == []
    # CLI end to end
    assert inspect_tool.main(["--dir", cfg.dir, "--mesh", "fsdp=2"]) == 0
    assert inspect_tool.main(["--dir", cfg.dir, "--mesh", "nope"]) == 2


# --------------------------------------------------- e2e: 4 → 3 drill
DRILL_WORKER = WORKER_HEAD + """
control = os.environ.get("DRILL_CONTROL") == "1"
out = {out!r}
cfg.data.synthetic_size = 48
cfg.data.batch_size = 12
cfg.data.elastic_shards = True
cfg.total_steps = 6
cfg.checkpoint.save_every_steps = 2
cfg.checkpoint.async_save = True
cfg.checkpoint.tiered = True
if control:
    cfg.checkpoint.dir = os.path.join(out, f"control-ckpt-{{rank}}")
    cfg.obs.jsonl_path = os.path.join(out, f"metrics-control-{{rank}}.jsonl")
else:
    cfg.checkpoint.dir = os.path.join(out, f"ckpt-{{rank}}")
    cfg.obs.jsonl_path = os.path.join(
        out, f"metrics-{{rank}}-gen{{gen}}.jsonl")
    # generation 0 only: every survivor waits at its step 3 for node 3 to
    # die at ITS step 3 (the agents' SIGTERM ends the wait; the delay is
    # only its upper bound), and node 3 gives them 5 s at its step 2 to
    # get there. The survivors then all force-save step 3: the restore
    # takes the gang's newest step from the peer tier, which a control
    # resuming from one rank's own copy can only follow when that rank
    # has that step too.
    cfg.faults.inject = (
        ("step.straggle@step=2:count=1:delay=5", "elastic.shrink@step=3")
        if rank == 3 else ("step.straggle@step=3:count=1:delay=60",))
t = Trainer(cfg)
if not control:  # the hosts enter fit together, whatever their start-up
    from pytorch_distributed_train_tpu.elastic import worker_store
    worker_store().barrier(f"built/{{gen}}", world, rank, timeout_ms=120000)
t.fit()
t.close()
"""


def test_shrink_4_to_3_resumes_bitexact_vs_control(tmp_path):
    """The acceptance drill (ISSUE 6): train on a 4-process world, kill
    one host PERMANENTLY at step 3, survivors re-rendezvous degraded at
    3, restore the step-2 checkpoint resharded, resume mid-epoch with
    recomputed data shards — and the per-rank loss trajectory matches a
    fixed-3-host control run started from the same checkpoint
    BIT-EXACTLY. Reshard lifecycle shows in the journal and in
    tools/timeline_report.py."""
    import socket
    import threading

    from pytorch_distributed_train_tpu.elastic import (
        ElasticAgent,
        LaunchConfig,
    )

    script = tmp_path / "worker.py"
    script.write_text(DRILL_WORKER.format(out=str(tmp_path)))
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    events_dir = str(tmp_path / "events")
    rcs: dict[int, int] = {}

    def agent(node_rank: int, max_restarts: int) -> None:
        cfg = LaunchConfig(
            nprocs=1, max_restarts=max_restarts, monitor_interval_s=0.1,
            nnodes=4, node_rank=node_rank, master_addr="127.0.0.1",
            store_port=port, min_nnodes=3, rendezvous_window_s=3.0,
            # room for a survivor's force-save on a loaded box: the
            # default 10 s was overrun one run in six, and a worker
            # killed in its save leaves a torn step behind
            shutdown_grace_s=60.0,
            backoff_base_s=0.05, backoff_max_s=0.1, env=env,
            events_dir=events_dir)
        rcs[node_rank] = ElasticAgent(
            cfg, [sys.executable, str(script)]).run()

    # node 3's agent has no restart budget: its elastic.shrink exit is
    # a permanent machine loss. Daemon: a wedged agent past the join
    # timeout fails the rcs assertion instead of hanging pytest.
    threads = [threading.Thread(target=agent, args=(r, 0 if r == 3 else 2),
                                daemon=True)
               for r in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=560)
    assert rcs == {0: 0, 1: 0, 2: 0, 3: 45}, rcs

    def losses(path):
        out = {}
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("tag") == "train":
                    out[int(rec["step"])] = rec["loss"]
        return out

    # Generation 1 ran DEGRADED at world 3 to the horizon. The resume
    # step is per-rank: each survivor's teardown force-save landed at
    # whatever step that rank had reached when the gang came down
    # (fit()'s finally — real host-loss semantics), so rank r resumed
    # from s_r = min(gen-1 steps) - 1. No floor: with 4 concurrent
    # compiles on a 2-core box a slow rank can still be at step 0-1
    # when node 3 dies — the comparison below is per-rank exact either
    # way (s_r = 0 means both runs restore the step-0 force-save, or
    # both fresh-init from the same seed).
    resume_step = {}
    for rank in range(3):
        gen1 = losses(tmp_path / f"metrics-{rank}-gen1.jsonl")
        assert gen1 and max(gen1) == 6, (rank, sorted(gen1))
        s_r = min(gen1) - 1
        assert 0 <= s_r <= 5, (rank, s_r)
        resume_step[rank] = s_r

    # control: 3 fresh single-process workers, world=3, resuming from a
    # COPY of each rank's checkpoint pruned back to that rank's actual
    # resume step — no launcher, no peer store, Orbax tier only (the
    # tiered plane persists the same snapshot bytes to every tier, so
    # Orbax-restoring the control IS restoring what gen 1 got from its
    # hot/peer tier).
    for rank in range(3):
        src = tmp_path / f"ckpt-{rank}"
        dst = tmp_path / f"control-ckpt-{rank}"
        shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
            "hot", "events", "metrics.jsonl", "trace.json", "flight_*"))
        for name in os.listdir(dst):
            if name.isdigit() and int(name) > resume_step[rank]:
                shutil.rmtree(dst / name)
        mandir = dst / "manifests"
        if mandir.is_dir():
            for name in os.listdir(mandir):
                step = "".join(ch for ch in name if ch.isdigit())
                if step and int(step) > resume_step[rank]:
                    os.remove(mandir / name)
    procs = []
    for rank in range(3):
        wenv = {**os.environ, **env, "NUM_PROCESSES": "3",
                "PROCESS_ID": str(rank), "DRILL_CONTROL": "1"}
        wenv.pop("TPUSTORE_ADDR", None)
        wenv.pop("RESTART_GENERATION", None)
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      env=wenv))
    for p in procs:
        assert p.wait(timeout=560) == 0

    # bit-exact: same restored state + same recomputed shards ⇒ the
    # degraded generation IS the control run, loss for loss
    for rank in range(3):
        gen1 = losses(tmp_path / f"metrics-{rank}-gen1.jsonl")
        ctrl = losses(tmp_path / f"metrics-control-{rank}.jsonl")
        assert sorted(gen1) == sorted(
            s for s in ctrl if s > resume_step[rank])
        for step in sorted(gen1):
            assert gen1[step] == ctrl[step], (
                rank, step, gen1[step], ctrl[step])

    # reshard lifecycle: journaled by agent AND workers, and visible in
    # the timeline report
    from pytorch_distributed_train_tpu.obs.events import load_events

    events = load_events(events_dir)
    agent_reshard = [e for e in events if e["category"] == "elastic"
                     and e["name"] == "reshard" and "agent" in e["host"]]
    worker_reshard = [e for e in events if e["category"] == "elastic"
                      and e["name"] == "reshard"
                      and e["host"].startswith("host")]
    assert agent_reshard and worker_reshard
    assert worker_reshard[-1]["detail"]["from_world"] == 4
    assert worker_reshard[-1]["detail"]["to_world"] == 3
    degraded = [e for e in events
                if e["name"] == "rendezvous_degraded"]
    assert degraded and degraded[-1]["detail"]["nodes"] == 3

    import contextlib
    import io

    import tools.timeline_report as tr

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert tr.main(["--events", events_dir]) == 0
    text = buf.getvalue()
    assert "reshard" in text
