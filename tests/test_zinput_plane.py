"""Input-pipeline plane (ISSUE 12): shared-memory decode pool, packed
pre-decoded cache, device-side augmentation.

The invariants pinned here are the ones the whole plane is allowed to
exist under:

- batch streams are BYTE-identical across process models (in-process vs
  mp pool, eager vs packed) for both loaders;
- ``start_batch`` resume and the elastic-shards union invariant hold on
  every new path;
- packed shards are CRC-protected and the pack tool's output trains;
- the device crop/flip/normalize kernel equals the host reference
  bit-for-bit under shared draws (the deterministic subset — RandAugment
  shares the op space, not the pixels, and is only required to be
  jit-clean and rng-deterministic).
"""

import os
import sys

import numpy as np
import pytest
from tiny import set_flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pytorch_distributed_train_tpu.config import DataConfig  # noqa: E402
from pytorch_distributed_train_tpu.data.datasets import (  # noqa: E402
    CIFAR_MEAN,
    CIFAR_STD,
    U8ImageDataset,
)
from pytorch_distributed_train_tpu.data.pipeline import (  # noqa: E402
    HostDataLoader,
)

pytestmark = pytest.mark.filterwarnings(
    "ignore:.*os.fork.*:RuntimeWarning")


def _u8_dataset(n=96, size=12, raw_u8=False, seed=0):
    rng = np.random.default_rng(seed)
    return U8ImageDataset(
        rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8),
        np.arange(n, dtype=np.int32),  # distinct labels = record identity
        CIFAR_MEAN, CIFAR_STD, augment=True, raw_u8=raw_u8)


def _batches(loader, epoch=0, start_batch=0):
    out = list(loader.epoch(epoch, start_batch=start_batch))
    close = getattr(loader, "close", None)
    if close:
        close()
    return out


def _assert_stream_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert set(x) == set(y)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


# ---------------------------------------------------------------- mp pool


def test_mp_pool_byte_identity_and_resume_threads_loader():
    ds = _u8_dataset()
    base = HostDataLoader(ds, DataConfig(batch_size=16),
                          train=True, num_hosts=1, host_id=0)
    pooled = HostDataLoader(ds, DataConfig(batch_size=16, mp_workers=2),
                            train=True, num_hosts=1, host_id=0)
    a = _batches(base)
    assert len(a) == 6
    b = list(pooled.epoch(0))
    _assert_stream_equal(a, b)
    # mid-epoch resume through the pool == tail of the full stream
    r = list(pooled.epoch(0, start_batch=4))
    _assert_stream_equal(a[4:], r)
    # a second epoch reuses the same workers; an abandoned epoch (early
    # break) must not poison it
    it = iter(pooled.epoch(1))
    next(it)
    del it
    a1 = _batches(HostDataLoader(ds, DataConfig(batch_size=16),
                                 train=True, num_hosts=1, host_id=0),
                  epoch=1)
    _assert_stream_equal(a1, list(pooled.epoch(1)))
    pooled.close()


def test_mp_pool_byte_identity_grain_loader():
    from pytorch_distributed_train_tpu.data.grain_pipeline import (
        GrainHostDataLoader,
    )

    ds = _u8_dataset(n=64)
    base = GrainHostDataLoader(
        ds, DataConfig(batch_size=16, num_workers=0),
        train=True, num_hosts=1, host_id=0)
    pooled = GrainHostDataLoader(
        ds, DataConfig(batch_size=16, num_workers=2, mp_workers=2),
        train=True, num_hosts=1, host_id=0)
    a = _batches(base)
    b = list(pooled.epoch(0))
    _assert_stream_equal(a, b)
    r = list(pooled.epoch(0, start_batch=2))
    _assert_stream_equal(a[2:], r)
    pooled.close()


def test_mp_pool_merges_worker_stage_seconds():
    from pytorch_distributed_train_tpu.obs import perf as perf_lib

    ds = _u8_dataset()
    loader = HostDataLoader(ds, DataConfig(batch_size=16, mp_workers=2),
                            train=True, num_hosts=1, host_id=0)
    before = perf_lib.get_input_stats().snapshot()
    _batches(loader)
    after = perf_lib.get_input_stats().snapshot()
    # the augment stage ran INSIDE forked workers; its seconds must have
    # been shipped back and merged into the process-global attribution
    assert after["augment"] > before["augment"]
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    assert (get_registry().family_total("input_worker_batches_total")
            > 0)


def test_pool_budget_and_grain_clamp():
    from pytorch_distributed_train_tpu.data.grain_pipeline import (
        bounded_workers,
    )
    from pytorch_distributed_train_tpu.data.workers import pool_budget

    # the pool keeps one core for the consumer, floor 1 when requested
    assert pool_budget(0) == 0
    assert pool_budget(4, avail=2) == 1
    assert pool_budget(4, avail=1) == 1
    assert pool_budget(4, avail=16) == 4
    # grain clamp: unchanged without the pool ...
    assert bounded_workers(4, avail=1) == 0
    assert bounded_workers(4, avail=16) == 4
    # ... but clamps against the POOL budget (floor 1) when it's on —
    # the 1-core clamp-to-zero must not apply (ISSUE 12 satellite)
    assert bounded_workers(4, avail=1, pool_budget=3) == 3
    assert bounded_workers(2, avail=1, pool_budget=3) == 2
    assert bounded_workers(0, avail=1, pool_budget=3) == 3
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    assert get_registry().get_value(
        "input_effective_workers", labels={"loader": "grain"}) is not None


# ------------------------------------------------------------ packed cache


def _pack_tmp(tmp_path, ds, shard_records=40, split="train"):
    from tools.pack_dataset import pack_arrays

    return pack_arrays(
        ds.arrays["image"], ds.arrays["label"], str(tmp_path),
        split=split, shard_records=shard_records,
        meta={"mean": [float(v) for v in CIFAR_MEAN],
              "std": [float(v) for v in CIFAR_STD], "pad": 4})


def test_packed_shard_roundtrip_and_crc(tmp_path):
    from pytorch_distributed_train_tpu.data import packed_cache as pc

    ds = _u8_dataset(n=32)
    (path,) = _pack_tmp(tmp_path / "a", ds, shard_records=32)
    header, off = pc.read_header(path)
    assert header["n"] == 32 and tuple(header["shape"]) == (12, 12, 3)
    assert pc.verify_shard(path)
    reader = pc.PackedShardReader(path, verify=True)
    np.testing.assert_array_equal(
        np.asarray(reader.images), ds.arrays["image"])
    np.testing.assert_array_equal(reader.labels, ds.arrays["label"])
    # flip one payload byte -> CRC must catch it
    with open(path, "r+b") as f:
        f.seek(off + 100)
        b = f.read(1)
        f.seek(off + 100)
        f.write(bytes([b[0] ^ 0xFF]))
    assert not pc.verify_shard(path)
    with pytest.raises(ValueError):
        pc.PackedShardReader(path, verify=True)
    # not-a-shard file is rejected loudly
    bad = tmp_path / "bad.pdttpack"
    bad.write_bytes(b"definitely not a shard")
    with pytest.raises(ValueError):
        pc.read_header(str(bad))
    # truncated INSIDE the header: still ValueError (never struct.error
    # — cache-or-fallthrough catches ValueError, a half-copied shard
    # must be a MISS, not a crash)
    torn = tmp_path / "torn.pdttpack"
    torn.write_bytes(pc.MAGIC + b"\x10")
    with pytest.raises(ValueError):
        pc.read_header(str(torn))
    # the cache dir now holds only corrupt files: loud MISS, no crash
    assert pc.load_packed_if_present(
        str(tmp_path), "train", augment=True) is None


def test_packed_vs_eager_byte_identical_both_loaders(tmp_path):
    from pytorch_distributed_train_tpu.data.packed_cache import (
        PackedImageDataset,
    )

    ds = _u8_dataset()
    _pack_tmp(tmp_path, ds)  # 3 shards of 40/40/16
    packed = PackedImageDataset(str(tmp_path), augment=True,
                                split="train", verify=True)
    cfg = DataConfig(batch_size=16)
    a = _batches(HostDataLoader(ds, cfg, train=True,
                                num_hosts=1, host_id=0))
    b = _batches(HostDataLoader(packed, cfg, train=True,
                                num_hosts=1, host_id=0))
    _assert_stream_equal(a, b)
    from pytorch_distributed_train_tpu.data.grain_pipeline import (
        GrainHostDataLoader,
    )

    gcfg = DataConfig(batch_size=16, num_workers=0)
    ga = _batches(GrainHostDataLoader(ds, gcfg, train=True,
                                      num_hosts=1, host_id=0))
    gb = _batches(GrainHostDataLoader(packed, gcfg, train=True,
                                      num_hosts=1, host_id=0))
    _assert_stream_equal(ga, gb)


def test_packed_resume_and_elastic_union(tmp_path):
    """start_batch resume on the packed+pool path, and the elastic
    invariant: the union of all hosts' batch b covers the same records
    at any world size (labels are record ids here)."""
    from pytorch_distributed_train_tpu.data.packed_cache import (
        PackedImageDataset,
    )

    ds = _u8_dataset()
    _pack_tmp(tmp_path, ds)
    packed = PackedImageDataset(str(tmp_path), augment=True,
                                split="train")
    full = _batches(HostDataLoader(
        packed, DataConfig(batch_size=16), train=True,
        num_hosts=1, host_id=0))
    pooled = HostDataLoader(packed, DataConfig(batch_size=16,
                                               mp_workers=2),
                            train=True, num_hosts=1, host_id=0)
    _assert_stream_equal(full[3:], list(pooled.epoch(0, start_batch=3)))
    pooled.close()
    # elastic union: world=2 loaders over the SAME packed shards
    w2 = [
        _batches(HostDataLoader(packed, DataConfig(batch_size=16),
                                train=True, num_hosts=2, host_id=h))
        for h in (0, 1)
    ]
    for b, whole in enumerate(full):
        union = np.concatenate([w2[0][b]["label"], w2[1][b]["label"]])
        assert set(union.tolist()) == set(whole["label"].tolist())


def test_build_dataset_packed_cache_dir_hit_and_miss(tmp_path):
    from pytorch_distributed_train_tpu.config import ModelConfig
    from pytorch_distributed_train_tpu.data.datasets import build_dataset
    from pytorch_distributed_train_tpu.data.packed_cache import (
        PackedImageDataset,
    )
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    data_cfg = DataConfig(dataset="cifar10", data_dir="")
    model_cfg = ModelConfig(image_size=12)
    reg = get_registry()
    miss0 = reg.family_total("packed_cache_misses_total")
    # empty cache dir: MISS, falls through to the normal build
    # (data_dir="" -> synthetic fallback stands in for the decode path)
    data_cfg.packed_cache_dir = str(tmp_path / "empty")
    ds = build_dataset(data_cfg, model_cfg, train=True)
    assert not isinstance(ds, PackedImageDataset)
    assert reg.family_total("packed_cache_misses_total") == miss0 + 1
    # valid cache: HIT, packed dataset replaces the decode path
    hit0 = reg.family_total("packed_cache_hits_total")
    _pack_tmp(tmp_path / "cache", _u8_dataset(n=32), shard_records=32)
    data_cfg.packed_cache_dir = str(tmp_path / "cache")
    ds = build_dataset(data_cfg, model_cfg, train=True)
    assert isinstance(ds, PackedImageDataset)
    assert reg.family_total("packed_cache_hits_total") == hit0 + 1


# --------------------------------------------------------- device augment


def test_device_crop_flip_normalize_matches_host_bitwise():
    import jax  # noqa: F401  (CPU backend from conftest)

    from pytorch_distributed_train_tpu.data.datasets import _crop_flip
    from pytorch_distributed_train_tpu.ops import device_augment as da

    rng = np.random.default_rng(3)
    imgs = rng.integers(0, 256, (6, 14, 14, 3), np.uint8)
    ys = rng.integers(0, 9, 6)
    xs = rng.integers(0, 9, 6)
    flips = rng.random(6) < 0.5
    host = _crop_flip(imgs, 4, ys, xs, flips).astype(np.float32)
    host = (host / 255.0 - CIFAR_MEAN) / CIFAR_STD
    dev = np.asarray(da.crop_flip_normalize(
        imgs, ys, xs, flips, 4, CIFAR_MEAN, CIFAR_STD))
    np.testing.assert_array_equal(host, dev)  # bitwise, not approx
    # eval path: plain normalize, also exact
    ev = np.asarray(da.normalize_u8(imgs, CIFAR_MEAN, CIFAR_STD))
    np.testing.assert_array_equal(
        ev, (imgs.astype(np.float32) / 255.0 - CIFAR_MEAN) / CIFAR_STD)


def test_device_augment_transform_jit_deterministic_and_passthrough():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.ops.device_augment import (
        DeviceAugment,
    )

    t = DeviceAugment(mean=tuple(map(float, CIFAR_MEAN)),
                      std=tuple(map(float, CIFAR_STD)), pad=2,
                      randaugment_num_ops=2)
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 256, (4, 10, 10, 3), np.uint8))
    batch = {"image": imgs, "label": jnp.arange(4)}
    f = jax.jit(lambda b, r: t(b, r, True))
    out1 = f(batch, jax.random.PRNGKey(7))
    out2 = f(batch, jax.random.PRNGKey(7))
    assert out1["image"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out1["image"]),
                                  np.asarray(out2["image"]))
    assert not np.array_equal(
        np.asarray(out1["image"]),
        np.asarray(f(batch, jax.random.PRNGKey(8))["image"]))
    # labels ride through untouched; f32 batches pass through untouched
    np.testing.assert_array_equal(np.asarray(out1["label"]), np.arange(4))
    f32 = {"image": jnp.ones((4, 10, 10, 3), jnp.float32),
           "label": jnp.arange(4)}
    np.testing.assert_array_equal(
        np.asarray(f(f32, jax.random.PRNGKey(0))["image"]),
        np.ones((4, 10, 10, 3), np.float32))
    # eval reduces to the deterministic normalize
    ev = t({"image": imgs, "label": jnp.arange(4)}, None, False)
    np.testing.assert_array_equal(
        np.asarray(ev["image"]),
        (np.asarray(imgs).astype(np.float32) / 255.0
         - CIFAR_MEAN) / CIFAR_STD)


def test_raw_u8_mode_collapses_host_augment():
    from pytorch_distributed_train_tpu.obs import perf as perf_lib

    ds = _u8_dataset(raw_u8=True)
    stats = perf_lib.get_input_stats()
    before = stats.snapshot()
    batch = ds.get_batch(np.arange(16), np.random.default_rng(0), True)
    after = stats.snapshot()
    assert batch["image"].dtype == np.uint8
    assert after["augment"] == before["augment"]  # nothing but the read
    assert after["read"] >= before["read"]


def test_build_device_augment_dataset_gating():
    from pytorch_distributed_train_tpu.ops.device_augment import (
        build_device_augment,
    )

    cfg = DataConfig(device_augment=True)
    on = build_device_augment(cfg, _u8_dataset(raw_u8=True))
    assert on is not None and on.crop  # array-style: device crops
    assert build_device_augment(DataConfig(),
                                _u8_dataset(raw_u8=True)) is None
    # datasets that can't ship u8 (synthetic f32) never get a transform
    from pytorch_distributed_train_tpu.data.datasets import (
        synthetic_images,
    )

    assert build_device_augment(cfg, synthetic_images(8, 8, 4)) is None


# -------------------------------------------------- pack tool + training


def _write_image_folder(root, classes=2, per_class=6, size=20):
    from PIL import Image

    rng = np.random.default_rng(0)
    for c in range(classes):
        d = os.path.join(root, f"class_{c}")
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            Image.fromarray(
                rng.integers(0, 256, (size + 6, size + 2, 3), np.uint8)
            ).save(os.path.join(d, f"{i:03d}.jpg"), quality=92)


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
def test_pack_dataset_cli_smoke_and_two_step_train(tmp_path):
    """The satellite drill: pack a tiny synthetic ImageFolder, verify
    CRCs, then train 2 steps FROM the cache — with the device-augment
    and shared-memory-pool paths on, so the whole ISSUE-12 plane runs
    end-to-end in tier-1."""
    src = tmp_path / "src"
    _write_image_folder(str(src))
    from tools.pack_dataset import main as pack_main

    out = tmp_path / "cache"
    rc = pack_main(["--src", str(src), "--out", str(out),
                    "--split", "train", "--size", "16",
                    "--shard-records", "5", "--norm", "cifar"])
    assert rc == 0
    from pytorch_distributed_train_tpu.data import packed_cache as pc

    shards = pc.find_shards(str(out), "train")
    assert len(shards) == 3  # 12 records / 5 per shard
    assert all(pc.verify_shard(s) for s in shards)
    # val split: reuse the same shards under the val- prefix
    pack_main(["--src", str(src), "--out", str(out), "--split", "val",
               "--size", "16", "--shard-records", "12",
               "--norm", "cifar"])

    # fresh process-global stage stats: earlier tests in this process
    # ran host-side augment; the "augment collapsed" assertion below is
    # about THIS run's summary
    from pytorch_distributed_train_tpu.obs import perf as perf_lib

    perf_lib._reset_for_tests()
    import train

    rc = train.main([
        "--config", "resnet18_cifar10", "--steps", "2",
        "--resume", "none",
        *set_flags("data.dataset=packed_images", f"data.data_dir={out}",
                   "data.batch_size=8", "data.device_augment=true",
                   "data.mp_workers=2", "model.image_size=16",
                   "model.num_classes=2", f"checkpoint.dir={tmp_path}/run",
                   "checkpoint.save_every_steps=0"),
    ])
    assert rc == 0
    import json

    rows = [json.loads(line) for line in
            open(tmp_path / "run" / "metrics.jsonl") if line.strip()]
    steps = [r for r in rows if r.get("tag") == "train"]
    assert len(steps) == 2 and np.isfinite(steps[-1]["loss"])
    summary = [r for r in rows if r.get("tag") == "summary"][-1]
    # augment collapsed: the summary's staged split has no augment key
    assert "input_stage_s_augment" not in summary
    assert summary.get("packed_cache_records_read", 0) > 0
    assert summary.get("input_worker_batches", 0) > 0
