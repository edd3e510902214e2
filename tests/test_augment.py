"""MixUp/CutMix (device-side, ops/mixup.py) and RandAugment (host-side,
data/augment.py) — the torchvision/timm recipe augmentations."""

import numpy as np
import pytest
from tiny import tiny_cfg

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.data.augment import (
    RandAugment, apply_randaugment_u8,
)
from pytorch_distributed_train_tpu.losses import get_loss_fn
from pytorch_distributed_train_tpu.ops.mixup import MixupCutmix, partner


def _np_partner(x):
    out = x.copy()
    out[0::2], out[1::2] = x[1::2], x[0::2]
    return out


def _batch(B=8, H=16, W=16, n_cls=10, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "image": jnp.asarray(rng.standard_normal((B, H, W, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, n_cls, B), jnp.int32),
    }


# ------------------------------------------------------------------- mixup

def test_mixup_is_convex_combination_with_partner_batch():
    batch = _batch()
    mix = MixupCutmix(mixup_alpha=0.8, num_classes=10)
    out = jax.jit(mix)(batch, jax.random.PRNGKey(0))

    x = np.asarray(batch["image"])
    mixed = np.asarray(out["image"], np.float32)
    # Recover lam from one pixel, then check the whole tensor.
    part = _np_partner(x)
    i = np.argmax(np.abs(x[0] - part[0]))  # a pixel where the two differ
    lam = (mixed[0].flat[i] - part[0].flat[i]) / (x[0].flat[i] - part[0].flat[i])
    assert 0.0 <= lam <= 1.0
    np.testing.assert_allclose(mixed, lam * x + (1 - lam) * part, atol=1e-5)

    targets = np.asarray(out["target_probs"])
    one_hot = np.eye(10, dtype=np.float32)[np.asarray(batch["label"])]
    np.testing.assert_allclose(
        targets, lam * one_hot + (1 - lam) * _np_partner(one_hot), atol=1e-5)
    np.testing.assert_allclose(targets.sum(-1), 1.0, atol=1e-6)
    # original hard labels are preserved for the accuracy metric
    np.testing.assert_array_equal(np.asarray(out["label"]),
                                  np.asarray(batch["label"]))


def test_cutmix_box_semantics():
    batch = _batch(B=4, H=32, W=32)
    mix = MixupCutmix(cutmix_alpha=1.0, num_classes=10)
    out = jax.jit(mix)(batch, jax.random.PRNGKey(7))

    x = np.asarray(batch["image"])
    mixed = np.asarray(out["image"])
    # Every pixel is either the original or the pairwise partner...
    from_orig = np.isclose(mixed, x).all(-1)          # (B, H, W)
    from_flip = np.isclose(mixed, _np_partner(x)).all(-1)
    assert (from_orig | from_flip).all()
    # ...and the cut region is the SAME rectangle for every batch element.
    inside = ~from_orig  # True where the flipped partner was pasted
    for b in range(1, inside.shape[0]):
        np.testing.assert_array_equal(inside[b], inside[0])
    rows = np.where(inside[0].any(1))[0]
    cols = np.where(inside[0].any(0))[0]
    if rows.size:  # a degenerate (clipped-to-empty) box is legal
        assert inside[0][rows[0]:rows[-1] + 1, cols[0]:cols[-1] + 1].all()
        # lam matches the realized box area
        lam = float(out["target_probs"][0][int(batch["label"][0])])
        area_frac = inside[0].mean()
        if int(batch["label"][0]) != int(batch["label"][1]):
            np.testing.assert_allclose(lam, 1.0 - area_frac, atol=1e-5)


def test_mixup_switch_and_determinism():
    batch = _batch()
    mix = MixupCutmix(mixup_alpha=0.8, cutmix_alpha=1.0, switch_prob=0.5,
                      num_classes=10)
    a = jax.jit(mix)(batch, jax.random.PRNGKey(3))
    b = jax.jit(mix)(batch, jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(a["image"]), np.asarray(b["image"]))
    # across keys, both branches occur
    kinds = set()
    for k in range(12):
        out = jax.jit(mix)(batch, jax.random.PRNGKey(k))
        mixed = np.asarray(out["image"])
        x = np.asarray(batch["image"])
        binary = (np.isclose(mixed, x) | np.isclose(mixed, _np_partner(x))).all()
        kinds.add("cutmix" if binary else "mixup")
    assert kinds == {"cutmix", "mixup"}


def test_mixup_disabled_is_identity_and_loss_uses_soft_targets():
    batch = _batch()
    assert MixupCutmix()(batch, jax.random.PRNGKey(0)) is batch

    mix = MixupCutmix(mixup_alpha=0.8, num_classes=10, label_smoothing=0.1)
    out = mix(batch, jax.random.PRNGKey(1))
    logits = jnp.asarray(np.random.default_rng(2).standard_normal((8, 10)),
                         jnp.float32)
    loss, _ = get_loss_fn("softmax_xent")(logits, out)
    # soft-target CE oracle
    logp = jax.nn.log_softmax(logits)
    ref = float((-np.asarray(out["target_probs"]) * np.asarray(logp)).sum(-1).mean())
    np.testing.assert_allclose(float(loss), ref, rtol=1e-6)
    # smoothing folded into targets: rows still sum to 1, no zero entries
    t = np.asarray(out["target_probs"])
    np.testing.assert_allclose(t.sum(-1), 1.0, atol=1e-6)
    assert (t > 0).all()


def test_mixup_in_train_step_trains():
    """The full jitted train step accepts the mixup transform (8-dev mesh)."""
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import (
        MeshConfig, OptimConfig, PrecisionConfig,
    )
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import rules_for_model
    from pytorch_distributed_train_tpu.train_state import TrainState

    mesh = build_mesh(MeshConfig(data=-1))
    model = build_model(tiny_cfg().model,
                        PrecisionConfig(compute_dtype="float32"))
    tx, _ = make_optimizer(OptimConfig(name="momentum", learning_rate=0.1),
                           total_steps=10)

    def init_state(rng):
        variables = model.init({"params": rng}, jnp.zeros((2, 8, 8, 3)),
                               train=False)
        return TrainState.create(params=variables["params"], tx=tx)

    shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    sharding = steps_lib.state_shardings(mesh, rules_for_model("vit_b16"),
                                         shape)
    state = jax.jit(init_state, out_shardings=sharding)(jax.random.PRNGKey(0))
    mix = MixupCutmix(mixup_alpha=0.2, cutmix_alpha=1.0, num_classes=10)
    step = steps_lib.jit_train_step(
        steps_lib.make_train_step(model, get_loss_fn("softmax_xent"), tx,
                                  mixup=mix),
        mesh, sharding)
    batch = _batch(B=16, H=8, W=8)
    state, metrics = step(state, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0


# -------------------------------------------------------------- randaugment

def _pil_img(seed=0, size=24):
    from PIL import Image

    rng = np.random.default_rng(seed)
    return Image.fromarray(rng.integers(0, 256, (size, size, 3), np.uint8))


def test_randaugment_deterministic_and_shape_preserving():
    aug = RandAugment(num_ops=2, magnitude=9)
    im = _pil_img()
    a = np.asarray(aug(im, np.random.default_rng(5)))
    b = np.asarray(aug(im, np.random.default_rng(5)))
    c = np.asarray(aug(im, np.random.default_rng(6)))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (24, 24, 3) and a.dtype == np.uint8
    assert not np.array_equal(a, c)  # different seed → different augment


@pytest.mark.parametrize("magnitude", [0, 9, 30])
def test_randaugment_every_op_runs(magnitude):
    from pytorch_distributed_train_tpu.data import augment as aug_mod

    im = _pil_img(seed=magnitude)
    table = aug_mod._op_table(*im.size)
    assert len(table) == 14  # the torchvision RandAugment op space
    for name, fn, mags, signed in table:
        mag = float(mags[magnitude]) if mags is not None else 0.0
        out = fn(im, mag, np.random.default_rng(0))
        assert out.size == im.size, name
        if signed:
            out2 = fn(im, -mag, np.random.default_rng(0))
            assert out2.size == im.size, name


def test_randaugment_op_semantics():
    """Spot-check ops with closed-form behavior vs numpy oracles."""
    from pytorch_distributed_train_tpu.data.augment import (
        _posterize, _solarize, _translate_x,
    )

    im = _pil_img(seed=1)
    x = np.asarray(im).astype(np.int32)

    post = np.asarray(_posterize(im, 4, None))
    np.testing.assert_array_equal(post, (x & ~0x0F).astype(np.uint8))

    sol = np.asarray(_solarize(im, 128, None))
    expect = np.where(x >= 128, 255 - x, x).astype(np.uint8)
    np.testing.assert_array_equal(sol, expect)

    # translate by +3 px: columns shift right, vacated columns are 0-fill
    tr = np.asarray(_translate_x(im, -3.0, None))  # PIL affine: out(x)=in(x+c)
    np.testing.assert_array_equal(tr[:, 3:], np.asarray(im)[:, :-3])
    assert (tr[:, :3] == 0).all()

    # magnitude-0 enhancement ops are identity
    from pytorch_distributed_train_tpu.data.augment import _enhance

    for cls in ("Brightness", "Color", "Contrast"):
        np.testing.assert_array_equal(
            np.asarray(_enhance(cls)(im, 0.0, None)), np.asarray(im))


def test_randaugment_u8_adapter_and_imagefolder_wiring(tmp_path):
    img = np.random.default_rng(0).integers(0, 256, (24, 24, 3), np.uint8)
    out = apply_randaugment_u8(img, RandAugment(2, 9),
                               np.random.default_rng(1))
    assert out.shape == img.shape and out.dtype == np.uint8

    # build_dataset wires RandAugment into the ImageFolder train path
    from PIL import Image

    from pytorch_distributed_train_tpu.config import DataConfig, ModelConfig
    from pytorch_distributed_train_tpu.data.datasets import build_dataset

    root = tmp_path / "train" / "cat"
    root.mkdir(parents=True)
    Image.fromarray(img).save(root / "a.png")
    cfg = DataConfig(dataset="imagenet_folder", data_dir=str(tmp_path),
                     randaugment_num_ops=2, randaugment_magnitude=9)
    ds = build_dataset(cfg, ModelConfig(image_size=16), train=True)
    assert ds.randaugment is not None
    item = ds.get_item(0, np.random.default_rng(0))
    assert item["image"].shape == (16, 16, 3)

    cfg0 = DataConfig(dataset="imagenet_folder", data_dir=str(tmp_path))
    assert build_dataset(cfg0, ModelConfig(image_size=16),
                         train=True).randaugment is None


def test_partner_is_shard_local_and_handles_odd_batches():
    # odd batch → documented fallback to the full reverse
    x_odd = jnp.arange(5 * 2.0).reshape(5, 2)
    np.testing.assert_array_equal(np.asarray(partner(x_odd)),
                                  np.asarray(x_odd)[::-1])
    # even batch → pairwise swap, and under 'data'-sharding the lowered
    # program contains NO cross-device communication (the reason partner()
    # exists instead of timm's x.flip(0))
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(data=-1))
    sh = NamedSharding(mesh, PartitionSpec("data"))
    x = jnp.zeros((16, 8, 8, 3))
    for fn, comm_free in ((partner, True), (lambda a: a[::-1], False)):
        hlo = (
            jax.jit(fn, in_shardings=(sh,), out_shardings=sh)
            .lower(x).compile().as_text()
        )
        has_comm = ("collective-permute" in hlo) or ("all-to-all" in hlo)
        assert has_comm != comm_free, f"{fn}: comm_free={comm_free}\n{hlo[:500]}"


def test_build_mixup_validates_workload():
    from pytorch_distributed_train_tpu.config import DataConfig, ModelConfig
    from pytorch_distributed_train_tpu.ops.mixup import build_mixup

    data = DataConfig(mixup_alpha=0.2)
    model = ModelConfig(num_classes=10)
    assert build_mixup(DataConfig(), model, 0.0) is None  # disabled
    assert build_mixup(data, model, 0.0, loss="softmax_xent") is not None
    with pytest.raises(ValueError, match="softmax_xent"):
        build_mixup(data, model, 0.0, loss="causal_lm_xent")


def test_randaugment_nonsquare_translate_axes():
    """TranslateX bins scale with width, TranslateY with height, and the
    op-table cache distinguishes sizes with equal width (torchvision
    semantics — regression for the width-only table bug)."""
    from PIL import Image

    from pytorch_distributed_train_tpu.data import augment as aug_mod

    aug = RandAugment(num_ops=1, magnitude=30)
    wide = Image.fromarray(np.zeros((32, 64, 3), np.uint8))   # H=32, W=64
    tall = Image.fromarray(np.zeros((128, 64, 3), np.uint8))  # H=128, W=64
    aug(wide, np.random.default_rng(0))
    aug(tall, np.random.default_rng(0))
    assert set(aug._tables) == {(64, 32), (64, 128)}

    def mags(table, name):
        return dict((r[0], r[2]) for r in table)[name]

    for size, table in aug._tables.items():
        w, h = size
        np.testing.assert_allclose(mags(table, "TranslateX")[-1],
                                   150.0 / 331.0 * w)
        np.testing.assert_allclose(mags(table, "TranslateY")[-1],
                                   150.0 / 331.0 * h)


def test_u8_dataset_randaugment_recipe_order_and_determinism():
    """CIFAR u8 path: crop → flip → RandAugment → normalize, threaded,
    deterministic under the batch rng, and picklable (grain workers)."""
    import pickle

    from pytorch_distributed_train_tpu.data.datasets import (
        CIFAR_MEAN, CIFAR_STD, U8ImageDataset,
    )

    rng0 = np.random.default_rng(0)
    imgs = rng0.integers(0, 256, (8, 32, 32, 3), np.uint8)
    labels = np.arange(8, dtype=np.int32)
    ds = U8ImageDataset(imgs, labels, CIFAR_MEAN, CIFAR_STD, augment=True,
                        randaugment=RandAugment(2, 9))
    idx = np.arange(8)
    a = ds.get_batch(idx, np.random.default_rng(1), train=True)
    b = ds.get_batch(idx, np.random.default_rng(1), train=True)
    np.testing.assert_array_equal(a["image"], b["image"])
    assert a["image"].dtype == np.float32 and a["image"].shape == imgs.shape
    # differs from the no-RA path under the same draws
    ds_plain = U8ImageDataset(imgs, labels, CIFAR_MEAN, CIFAR_STD,
                              augment=True)
    c = ds_plain.get_batch(idx, np.random.default_rng(1), train=True)
    assert not np.array_equal(a["image"], c["image"])
    # eval path ignores RA entirely
    ev = ds.get_batch(idx, np.random.default_rng(1), train=False)
    np.testing.assert_array_equal(ev["image"],
                                  ds_plain.get_batch(idx, np.random.default_rng(1),
                                                     train=False)["image"])
    # picklable after use (the lazy thread pool must not be captured)
    clone = pickle.loads(pickle.dumps(ds))
    d = clone.get_batch(idx, np.random.default_rng(1), train=True)
    np.testing.assert_array_equal(a["image"], d["image"])
