"""Online weight-publication wire format + swap state machine + GRPO
plumbing (docs/online_training.md, ISSUE 19).

- Byte-identity across meshes: a 2-host trainer mesh publishes via
  per-host ownership predicates; ``fetch_version`` reassembles the
  GLOBAL flatten-order leaves bit-exactly and ``place_leaves`` lands
  them on a 1-device serving mesh (shrink) and back onto a wider mesh
  (grow), still bit-equal.
- A corrupt published chunk fails the payload CRC and reads as
  "version unavailable" — never a half-applied swap.
- GC keeps exactly ``KEEP_VERSIONS`` versions on the store.
- ``WeightState``: stage/apply/busy/reject protocol, lag gauge.
- ``group_advantages`` / ``to_grpo_batch`` layout, ``make_grpo_loss``
  REINFORCE and clipped-ratio branches against a numpy oracle."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_train_tpu import losses as losses_lib
from pytorch_distributed_train_tpu.ckpt import snapshot as snapshot_lib
from pytorch_distributed_train_tpu.config import MeshConfig
from pytorch_distributed_train_tpu.faults import registry as fregistry
from pytorch_distributed_train_tpu.online import publisher as pub_lib
from pytorch_distributed_train_tpu.online import rollouts as roll_lib
from pytorch_distributed_train_tpu.online.swap import (PendingSwap,
                                                       WeightState)
from pytorch_distributed_train_tpu.parallel.mesh import build_mesh


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


def _savable(mesh, *, seed: int = 0) -> dict:
    """A small params tree with one sharded + one replicated leaf."""
    rng = np.random.default_rng(seed)
    w = jax.device_put(
        jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
        NamedSharding(mesh, PartitionSpec("data")))
    b = jax.device_put(jnp.asarray(rng.standard_normal(4), jnp.float32),
                       NamedSharding(mesh, PartitionSpec()))
    return {"params": {"b": b, "w": w}}


def _host_leaves(tree) -> list[np.ndarray]:
    return [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(tree)]


def _two_host_preds(devices8):
    host_devs = {0: set(devices8[:2]), 1: set(devices8[2:4])}
    return {
        h: (lambda s, _d=devs: s.device in _d and s.replica_id == 0)
        for h, devs in host_devs.items()
    }


# --------------------------------------------------- wire byte-identity
def test_publish_fetch_shrink_grow_bit_exact(devices8):
    mesh_a = build_mesh(MeshConfig(data=-1), devices=devices8[:4])
    savable = _savable(mesh_a, seed=7)
    want = _host_leaves(savable)

    store = FakeStore()
    # tiny chunk size so every payload spans multiple chunks on the wire
    info = pub_lib.publish_version(
        store, savable, version=1, step=5,
        owned_preds=_two_host_preds(devices8), chunk_bytes=64)
    assert info["version"] == 1 and sorted(info["hosts"]) == [0, 1]
    assert any(k.endswith("/c1") for k in store.kv), \
        "chunk_bytes=64 should force multi-chunk payloads"

    got = pub_lib.fetch_version(store)
    assert got is not None
    info2, leaves, header = got
    assert info2["version"] == 1 and info2["step"] == 5
    assert header["meta"]["weight_version"] == 1
    assert len(leaves) == len(want)
    for got_leaf, want_leaf in zip(leaves, want):
        np.testing.assert_array_equal(got_leaf, want_leaf)

    # shrink: place onto a 1-device serving mesh
    mesh_b = build_mesh(MeshConfig(data=-1), devices=devices8[4:5])
    template = jax.tree.map(
        lambda x: jax.device_put(
            jnp.zeros(x.shape, x.dtype),
            NamedSharding(mesh_b, PartitionSpec())), savable)
    placed = pub_lib.place_leaves(template, leaves)
    assert placed is not None
    for got_leaf, want_leaf in zip(_host_leaves(placed), want):
        np.testing.assert_array_equal(got_leaf, want_leaf)
    assert all(x.sharding.mesh == mesh_b
               for x in jax.tree_util.tree_leaves(placed))

    # grow: republish from the 1-device tree (single default host),
    # place back onto a WIDER sharded mesh — still bit-equal
    pub_lib.publish_version(store, placed, version=2, step=6)
    got2 = pub_lib.fetch_version(store)
    assert got2 is not None and got2[0]["version"] == 2
    mesh_c = build_mesh(MeshConfig(data=-1), devices=devices8[:8])
    wide = _savable(mesh_c, seed=99)  # same shapes, different values
    placed_wide = pub_lib.place_leaves(wide, got2[1])
    assert placed_wide is not None
    for got_leaf, want_leaf in zip(_host_leaves(placed_wide), want):
        np.testing.assert_array_equal(got_leaf, want_leaf)


def test_single_host_shard_does_not_assemble(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices=devices8[:4])
    savable = _savable(mesh)
    host0 = set(devices8[:2])
    one = snapshot_lib.take_shard_snapshot(
        savable, step=1, origin="online",
        owned=lambda s: s.device in host0 and s.replica_id == 0)
    assert snapshot_lib.assemble_shards([one]) is None


def test_fetch_absent_and_corrupt(devices8):
    store = FakeStore()
    assert pub_lib.latest_meta(store) is None
    assert pub_lib.fetch_version(store) is None
    assert pub_lib.fetch_version(store, 3) is None

    mesh = build_mesh(MeshConfig(data=-1), devices=devices8[:2])
    savable = _savable(mesh, seed=3)
    pub_lib.publish_version(store, savable, version=1, step=2,
                            chunk_bytes=64)
    assert pub_lib.fetch_version(store) is not None

    # flip one byte in the first chunk: payload CRC must reject the
    # whole version — None, never partial leaves
    key = "wts/1/0/c0"
    blob = bytearray(store.kv[key])
    blob[0] ^= 0xFF
    store.kv[key] = bytes(blob)
    assert pub_lib.fetch_version(store) is None

    # a missing chunk (torn transfer) reads the same way
    store.kv[key] = blob  # restore, then tear a later chunk
    blob2 = bytearray(store.kv[key])
    blob2[0] ^= 0xFF  # undo the flip
    store.kv[key] = bytes(blob2)
    assert pub_lib.fetch_version(store) is not None
    del store.kv["wts/1/0/c1"]
    assert pub_lib.fetch_version(store) is None


def test_placement_rejects_shape_mismatch(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices=devices8[:2])
    savable = _savable(mesh)
    store = FakeStore()
    pub_lib.publish_version(store, savable, version=1, step=1)
    _info, leaves, _hdr = pub_lib.fetch_version(store)
    bad_template = {"params": {"b": jnp.zeros(4, jnp.float32),
                               "w": jnp.zeros((8, 5), jnp.float32)}}
    assert pub_lib.place_leaves(bad_template, leaves) is None
    assert pub_lib.place_leaves({"params": {"b": jnp.zeros(4)}},
                                leaves) is None


def test_gc_keeps_last_two_versions(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices=devices8[:2])
    savable = _savable(mesh)
    store = FakeStore()
    for v in (1, 2, 3):
        pub_lib.publish_version(store, savable, version=v, step=v * 10)
    assert pub_lib.latest_meta(store)["version"] == 3
    # KEEP_VERSIONS=2: v2 and v3 fetchable, v1 fully collected
    assert pub_lib.fetch_version(store, 3) is not None
    assert pub_lib.fetch_version(store, 2) is not None
    assert pub_lib.fetch_version(store, 1) is None
    assert not any(k.startswith("wts/1/") for k in store.kv)


def test_weight_publisher_cadence(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices=devices8[:2])
    savable = _savable(mesh)

    # no store (no TPUSTORE_ADDR): publication is a no-op
    off = pub_lib.WeightPublisher(None)
    assert not off.due(10 ** 6)
    assert off.maybe_publish(savable, step=10 ** 6) is None

    store = FakeStore()
    p = pub_lib.WeightPublisher(store, cadence_steps=3)
    assert p.maybe_publish(savable, step=0) is None  # -1 + 3 > 0
    assert p.maybe_publish(savable, step=2) == 1
    assert p.maybe_publish(savable, step=3) is None  # 2 + 3 > 3
    assert p.maybe_publish(savable, step=5) == 2
    assert pub_lib.latest_meta(store)["version"] == 2
    with pytest.raises(ValueError):
        pub_lib.WeightPublisher(store, cadence_steps=0)


def test_publish_fault_never_seals(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices=devices8[:2])
    savable = _savable(mesh)
    store = FakeStore()
    try:
        fregistry.configure(specs=("weights.publish@call=1",))
        with pytest.raises(OSError):
            pub_lib.publish_version(store, savable, version=1, step=1)
    finally:
        fregistry._reset_for_tests()
    # the fault fired before any shard write: nothing on the store
    assert store.kv == {}
    assert pub_lib.latest_meta(store) is None
    # next attempt (the retry) succeeds cleanly
    pub_lib.publish_version(store, savable, version=1, step=1)
    assert pub_lib.fetch_version(store) is not None


# ------------------------------------------------- swap state machine
def test_weight_state_stage_apply():
    ws = WeightState(version="0", step=0)
    assert ws.version == "0"
    applied = []
    p = PendingSwap(version="1", step=7,
                    apply_fn=lambda: applied.append(1),
                    t0=time.monotonic())
    assert ws.stage(p)
    # second stage while one is pending: busy
    p2 = PendingSwap(version="2", step=8, apply_fn=None,
                     t0=time.monotonic())
    assert not ws.stage(p2)
    assert ws.apply_pending()
    assert applied == [1]
    assert p.done.is_set() and p.error is None
    snap = ws.snapshot()
    assert snap["version"] == "1" and snap["step"] == 7
    assert snap["swaps"] == 1 and snap["rejects"] == 0
    assert not snap["pending"]
    # nothing staged: apply is a cheap no-op
    assert not ws.apply_pending()


def test_weight_state_apply_failure_rejects():
    ws = WeightState(version="3", step=30)

    def boom():
        raise RuntimeError("quantized tree mismatch")

    p = PendingSwap(version="4", step=40, apply_fn=boom,
                    t0=time.monotonic())
    assert ws.stage(p)
    assert not ws.apply_pending()
    assert p.done.is_set()
    assert "quantized tree mismatch" in (p.error or "")
    snap = ws.snapshot()
    # the replica keeps serving its current version
    assert snap["version"] == "3" and snap["step"] == 30
    assert snap["swaps"] == 0 and snap["rejects"] == 1
    # the slot is free again: a corrected swap can stage + land
    ok = PendingSwap(version="4", step=40, apply_fn=None,
                     t0=time.monotonic())
    assert ws.stage(ok) and ws.apply_pending()
    assert ws.version == "4"


def test_weight_state_lag_and_reject_counts():
    ws = WeightState(version="1", step=10)
    assert ws.snapshot()["lag_steps"] is None  # nothing published yet
    ws.note_published(2, 25)
    assert ws.snapshot()["lag_steps"] == 15
    ws.note_published(1, 5)  # stale news never regresses the gauge
    snap = ws.snapshot()
    assert snap["published_version"] == 2 and snap["lag_steps"] == 15
    ws.reject("2", "crc")
    assert ws.snapshot()["rejects"] == 1


def test_weight_state_handler_scheduler_threads():
    """The real two-thread shape: a handler stages and waits on the
    event; the scheduler thread applies between quanta."""
    ws = WeightState()
    p = PendingSwap(version="9", step=90, apply_fn=None,
                    t0=time.monotonic())

    def scheduler():
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if ws.apply_pending():
                return
            time.sleep(0.005)

    t = threading.Thread(target=scheduler, daemon=True)
    t.start()
    assert ws.stage(p)
    assert p.done.wait(timeout=5.0)
    t.join(timeout=5.0)
    assert ws.version == "9" and p.duration_s >= 0.0


# --------------------------------------------------- rollouts → batch
def test_group_advantages_normalizes_within_group():
    advs = roll_lib.group_advantages({0: [1.0, 2.0, 3.0],
                                      1: [5.0, 5.0, 5.0]})
    a = np.asarray(advs[0], np.float32)
    assert abs(float(a.mean())) < 1e-5
    assert abs(float(a.std()) - 1.0) < 1e-4
    assert a[0] < a[1] < a[2]
    # a tied group gets zero advantage, not 0/0
    assert advs[1] == [0.0, 0.0, 0.0]


def test_rollout_batch_version_census():
    def rec(v):
        return roll_lib.RolloutRecord(prompt="p", completion="c",
                                      finish_reason="length",
                                      weight_version=v, group=0)

    batch = roll_lib.RolloutBatch(records=[rec("1"), rec("2"), rec("2")])
    assert batch.versions() == {"1": 1, "2": 2}
    assert batch.weight_version == "2"
    assert len(batch) == 3
    assert roll_lib.RolloutBatch(records=[]).weight_version == ""


def test_to_grpo_batch_layout():
    def encode(s):
        return [1 + (b % 255) for b in s.encode()]

    recs = [
        roll_lib.RolloutRecord(prompt="ab", completion="cde",
                               finish_reason="length",
                               weight_version="1", group=0),
        roll_lib.RolloutRecord(prompt="ab", completion="x",
                               finish_reason="length",
                               weight_version="1", group=0),
    ]
    batch = roll_lib.RolloutBatch(records=recs)
    out = roll_lib.to_grpo_batch(
        batch, encode, lambda p, c: float(len(c)), seq_len=8)
    ids, mask, adv = (out["input_ids"], out["loss_mask"],
                      out["advantage"])
    assert ids.shape == (2, 8) and mask.shape == (2, 8)
    assert ids.dtype == np.int32 and mask.dtype == np.float32
    # row 0: 2 prompt ids + 3 completion ids, mask on exactly the 3
    np.testing.assert_array_equal(ids[0, :5],
                                  encode("ab") + encode("cde"))
    np.testing.assert_array_equal(mask[0], [0, 0, 1, 1, 1, 0, 0, 0])
    np.testing.assert_array_equal(mask[1], [0, 0, 1, 0, 0, 0, 0, 0])
    assert (ids[0, 5:] == 0).all()  # pad_id
    # rewards 3 vs 1 → advantages normalize to +1 / -1 in record order
    assert adv[0] > 0 > adv[1]
    assert abs(float(adv.sum())) < 1e-5

    # truncation: a long row clips to seq_len, mask clipped with it
    long = roll_lib.RolloutBatch(records=[
        roll_lib.RolloutRecord(prompt="abcdef", completion="ghijkl",
                               finish_reason="length",
                               weight_version="1", group=0)])
    out2 = roll_lib.to_grpo_batch(
        long, encode, lambda p, c: 0.0, seq_len=8)
    assert (out2["input_ids"][0] != 0).all()
    np.testing.assert_array_equal(out2["loss_mask"][0],
                                  [0] * 6 + [1, 1])


# ------------------------------------------------------------ the loss
def _np_log_softmax(x):
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


def _oracle_grpo(logits, batch, clip_eps=0.2, behavior=None):
    ids = batch["input_ids"]
    mask = batch["loss_mask"][:, 1:]
    lp = _np_log_softmax(logits[:, :-1].astype(np.float64))
    logp = np.take_along_axis(lp, ids[:, 1:, None], axis=-1)[..., 0]
    adv = batch["advantage"][:, None]
    if behavior is not None:
        ratio = np.exp(logp - behavior[:, 1:])
        surr = np.minimum(
            ratio * adv,
            np.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv)
        per_tok = -surr
    else:
        per_tok = -adv * logp
    return float((per_tok * mask).sum() / max(mask.sum(), 1.0))


def _grpo_case(seed=0, B=2, S=6, V=11):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((B, S, V)).astype(np.float32)
    batch = {
        "input_ids": rng.integers(0, V, (B, S)).astype(np.int32),
        "loss_mask": (rng.random((B, S)) < 0.6).astype(np.float32),
        "advantage": rng.standard_normal(B).astype(np.float32),
    }
    return logits, batch


def test_grpo_loss_reinforce_matches_oracle():
    logits, batch = _grpo_case(seed=1)
    fn = losses_lib.make_grpo_loss()
    loss, metrics = fn(jnp.asarray(logits),
                       {k: jnp.asarray(v) for k, v in batch.items()})
    assert abs(float(loss) - _oracle_grpo(logits, batch)) < 1e-4
    assert float(metrics["sampled_tokens"]) == batch["loss_mask"][:, 1:].sum()
    # zero advantage → zero gradient signal, loss exactly 0
    flat = dict(batch, advantage=np.zeros_like(batch["advantage"]))
    loss0, _ = fn(jnp.asarray(logits),
                  {k: jnp.asarray(v) for k, v in flat.items()})
    assert float(loss0) == 0.0


def test_grpo_loss_clipped_matches_oracle():
    logits, batch = _grpo_case(seed=2)
    rng = np.random.default_rng(3)
    behavior = rng.standard_normal(
        batch["input_ids"].shape).astype(np.float32) - 2.0
    batch_b = dict(batch, behavior_logprobs=behavior)
    fn = losses_lib.make_grpo_loss(clip_eps=0.2)
    loss, _ = fn(jnp.asarray(logits),
                 {k: jnp.asarray(v) for k, v in batch_b.items()})
    want = _oracle_grpo(logits, batch, behavior=behavior)
    assert abs(float(loss) - want) < 1e-4
    with pytest.raises(ValueError):
        losses_lib.make_grpo_loss(clip_eps=-0.1)
