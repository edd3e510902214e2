"""Optimizer chain: no-decay param groups (decay_exclude) and LARS.

The torch-recipe pattern under test: BERT/ViT/GPT recipes build two param
groups — decay (matmul weights) and no_decay (biases, norm scales) — and
pass weight_decay only to the first. Here that split is a regex mask on the
optax weight-decay transform (optim.decay_mask_fn).
"""

import numpy as np
import pytest
from tiny import tiny_cfg

import jax
import jax.numpy as jnp

from pytorch_distributed_train_tpu.config import OptimConfig
from pytorch_distributed_train_tpu.optim import decay_mask_fn, make_optimizer


def _params():
    return {
        "dense": {"kernel": jnp.ones((4, 4)), "bias": jnp.ones((4,))},
        "norm": {"scale": jnp.ones((4,)), "bias": jnp.ones((4,))},
        "embed": {"embedding": jnp.ones((8, 4))},
    }


def _zero_grads(params):
    return jax.tree.map(jnp.zeros_like, params)


def test_decay_mask_fn_paths():
    mask = decay_mask_fn(r"bias$,scale$")(_params())
    assert mask["dense"]["kernel"] is True
    assert mask["dense"]["bias"] is False
    assert mask["norm"]["scale"] is False
    assert mask["norm"]["bias"] is False
    assert mask["embed"]["embedding"] is True
    assert decay_mask_fn("") is None
    assert decay_mask_fn("  ,  ") is None


def _decayed_which(opt_cfg):
    """Apply one update with ZERO grads: any param change is weight decay."""
    params = _params()
    tx, _ = make_optimizer(opt_cfg, total_steps=10)
    state = tx.init(params)
    updates, _ = tx.update(_zero_grads(params), state, params)
    new = jax.tree.map(lambda p, u: p + u, params, updates)
    return jax.tree.map(
        lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))), params, new
    )


def test_adamw_and_sgd_and_lamb_respect_decay_exclude():
    for name in ("adamw", "lamb", "momentum"):
        changed = _decayed_which(OptimConfig(
            name=name, learning_rate=0.1, weight_decay=0.1,
            decay_exclude=r"bias$,scale$", schedule="constant"))
        assert changed["dense"]["kernel"], name
        assert changed["embed"]["embedding"], name
        assert not changed["dense"]["bias"], name
        assert not changed["norm"]["scale"], name
        assert not changed["norm"]["bias"], name
        # without the mask, everything decays
        changed_all = _decayed_which(OptimConfig(
            name=name, learning_rate=0.1, weight_decay=0.1,
            schedule="constant"))
        assert all(jax.tree_util.tree_leaves(changed_all)), name


def test_lars_trains_and_masks():
    params = _params()
    cfg = OptimConfig(name="lars", learning_rate=0.1, weight_decay=1e-4,
                      momentum=0.9, decay_exclude=r"bias$,scale$",
                      schedule="constant")
    tx, _ = make_optimizer(cfg, total_steps=10)
    state = tx.init(params)
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.01), params)
    updates, _ = tx.update(grads, state, params)
    new = jax.tree.map(lambda p, u: p + u, params, updates)
    # every param moves against the gradient
    for leaf, old in zip(jax.tree_util.tree_leaves(new),
                         jax.tree_util.tree_leaves(params)):
        assert np.all(np.asarray(leaf) < np.asarray(old))
    # zero-grad probe: only unmasked params decay
    changed = _decayed_which(cfg)
    assert changed["dense"]["kernel"]
    assert not changed["dense"]["bias"]


def test_decay_exclude_composes_with_accumulation():
    """MultiSteps wrapping must not break the mask (mask sees the same
    param tree)."""
    cfg = OptimConfig(name="adamw", learning_rate=0.1, weight_decay=0.1,
                      decay_exclude=r"bias$", accum_steps=2,
                      schedule="constant")
    params = _params()
    tx, _ = make_optimizer(cfg, total_steps=10)
    state = tx.init(params)
    for _ in range(2):  # two micro-steps → one real update
        updates, state = tx.update(_zero_grads(params), state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
    assert np.all(np.asarray(params["dense"]["kernel"]) != 1.0)
    assert np.all(np.asarray(params["dense"]["bias"]) == 1.0)


def test_presets_carry_decay_exclude():
    from pytorch_distributed_train_tpu.config import get_preset

    for preset, expect in (("bert_base_mlm", True), ("vit_b16_imagenet", True),
                           ("llama2_7b", True), ("gpt2_small", True),
                           ("resnet50_imagenet", False)):
        cfg = get_preset(preset)
        assert bool(cfg.optim.decay_exclude) is expect, preset


def test_adam_applies_coupled_weight_decay():
    """torch.optim.Adam(weight_decay=) is coupled L2; the 'adam' branch
    must decay (regression: it silently ignored weight_decay)."""
    changed = _decayed_which(OptimConfig(
        name="adam", learning_rate=0.1, weight_decay=0.1,
        decay_exclude=r"bias$,scale$", schedule="constant"))
    assert changed["dense"]["kernel"]
    assert not changed["dense"]["bias"]


def test_vit_preset_excludes_cls_and_pos_embed():
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.optim import decay_mask_fn

    cfg = get_preset("vit_b16_imagenet")
    mask = decay_mask_fn(cfg.optim.decay_exclude)({
        "cls_token": jnp.zeros((1, 1, 4)),
        "pos_embed": jnp.zeros((1, 5, 4)),
        "blk": {"kernel": jnp.zeros((4, 4)), "bias": jnp.zeros((4,))},
    })
    assert mask == {"cls_token": False, "pos_embed": False,
                    "blk": {"kernel": True, "bias": False}}


def test_onecycle_schedule_shape():
    from pytorch_distributed_train_tpu.optim import make_schedule

    cfg = OptimConfig(name="adamw", learning_rate=1.0, schedule="onecycle",
                      onecycle_pct_start=0.25)
    sched = make_schedule(cfg, total_steps=100)
    lrs = [float(sched(t)) for t in range(100)]
    peak = int(np.argmax(lrs))
    assert 20 <= peak <= 30            # ramps up for pct_start of the run
    assert lrs[0] < 0.1 and max(lrs) == pytest.approx(1.0, abs=1e-6)
    assert lrs[-1] < 0.01              # anneals far below the peak
    with pytest.raises(ValueError, match="onecycle"):
        make_schedule(OptimConfig(schedule="onecycle", warmup_steps=10),
                      total_steps=100)


def test_cosine_restarts_schedule():
    from pytorch_distributed_train_tpu.optim import make_schedule

    cfg = OptimConfig(name="momentum", learning_rate=1.0,
                      schedule="cosine_restarts", restart_period=20,
                      restart_mult=1.0)
    sched = make_schedule(cfg, total_steps=60)
    lrs = np.array([float(sched(t)) for t in range(60)])
    # restarts at 20 and 40: LR jumps back to ~base
    assert lrs[0] == pytest.approx(1.0)
    for boundary in (20, 40):
        assert lrs[boundary] > 0.95, boundary
        assert lrs[boundary - 1] < 0.05, boundary
    # restart_mult grows cycles: second cycle twice as long
    cfg2 = OptimConfig(name="momentum", learning_rate=1.0,
                       schedule="cosine_restarts", restart_period=10,
                       restart_mult=2.0)
    sched2 = make_schedule(cfg2, total_steps=70)
    lrs2 = np.array([float(sched2(t)) for t in range(70)])
    assert lrs2[10] > 0.95 and lrs2[30] > 0.95  # cycles at 10, 10+20


def test_cosine_restarts_validation():
    from pytorch_distributed_train_tpu.optim import make_schedule

    with pytest.raises(ValueError, match="restart_mult"):
        make_schedule(OptimConfig(schedule="cosine_restarts",
                                  restart_mult=0.5), total_steps=100)
    with pytest.raises(ValueError, match="restart_period"):
        make_schedule(OptimConfig(schedule="cosine_restarts",
                                  restart_period=-5), total_steps=100)


def _leaf_dtypes(tree):
    return {jnp.asarray(x).dtype.name for x in jax.tree.leaves(tree)}


def test_moment_dtype_narrows_first_moment_only():
    """moment_dtype="bfloat16" stores adam mu in bf16 but keeps nu fp32,
    and the resulting update stays close to the fp32-state update."""
    params = _params()
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.01), params)

    def one_update(moment_dtype):
        tx, _ = make_optimizer(OptimConfig(
            name="adamw", learning_rate=0.1, weight_decay=0.0,
            schedule="constant", moment_dtype=moment_dtype), total_steps=10)
        state = tx.init(params)
        updates, state = tx.update(grads, state, params)
        return updates, state

    up32, st32 = one_update("")
    up16, st16 = one_update("bfloat16")
    flat16 = [x for x in jax.tree.leaves(st16)]
    assert any(jnp.asarray(x).dtype == jnp.bfloat16 for x in flat16), \
        "no bf16 accumulator found in adamw state"
    assert any(jnp.asarray(x).dtype == jnp.float32 and x.ndim > 0
               for x in flat16), "nu should remain fp32"
    for a, b in zip(jax.tree.leaves(up32), jax.tree.leaves(up16)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.02, atol=1e-6)


def test_moment_dtype_lamb_matches_fp32_closely():
    params = _params()
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.02), params)

    def one_update(moment_dtype):
        tx, _ = make_optimizer(OptimConfig(
            name="lamb", learning_rate=0.1, weight_decay=0.01,
            decay_exclude=r"bias$,scale$", schedule="constant",
            moment_dtype=moment_dtype), total_steps=10)
        state = tx.init(params)
        updates, _ = tx.update(grads, state, params)
        return updates

    up32 = one_update("")
    up16 = one_update("bfloat16")
    for a, b in zip(jax.tree.leaves(up32), jax.tree.leaves(up16)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.02, atol=1e-6)


def test_adafactor_state_is_factored_and_small():
    """Adafactor: a (256,512) matrix keeps only row+col second-moment
    vectors (no O(n*m) state, no first moment by default)."""
    params = {"dense": {"kernel": jnp.ones((256, 512))},
              "norm": {"scale": jnp.ones((512,))}}
    tx, _ = make_optimizer(OptimConfig(
        name="adafactor", learning_rate=0.01, schedule="constant"),
        total_steps=10)
    state = tx.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    state_floats = sum(
        jnp.asarray(x).size for x in jax.tree.leaves(state)
        if hasattr(x, "size") and jnp.asarray(x).ndim > 0)
    assert state_floats < 0.05 * n_params, (
        f"adafactor state {state_floats} floats vs {n_params} params — "
        "expected factored (row+col) statistics only")
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.01), params)
    updates, state = tx.update(grads, state, params)
    new = jax.tree.map(lambda p, u: p + u, params, updates)
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(new))
    assert any(np.any(np.asarray(a) != np.asarray(b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(new)))


def test_adafactor_momentum_off_by_default_on_by_knob():
    params = {"w": jnp.ones((256, 512))}  # >= 128 per dim → factored

    def state_size(**kw):
        tx, _ = make_optimizer(OptimConfig(
            name="adafactor", learning_rate=0.01, schedule="constant", **kw),
            total_steps=10)
        state = tx.init(params)
        return sum(jnp.asarray(x).size for x in jax.tree.leaves(state)
                   if hasattr(x, "size") and jnp.asarray(x).ndim > 0)

    # momentum=0.9 (the SGD-oriented default) must NOT create a buffer;
    # only the dedicated adafactor_momentum knob does.
    assert state_size(momentum=0.9) < 256 * 512
    assert state_size(adafactor_momentum=0.9) >= 256 * 512


def test_polynomial_schedule_shape():
    from pytorch_distributed_train_tpu.optim import make_schedule

    cfg = OptimConfig(schedule="polynomial", learning_rate=1e-3,
                      warmup_steps=10, poly_power=1.0, end_lr_factor=0.0)
    sched = make_schedule(cfg, total_steps=110)
    lrs = np.array([float(sched(t)) for t in range(110)])
    np.testing.assert_allclose(lrs[10], 1e-3, rtol=1e-5)  # warmup peak
    # power=1 → linear decay to 0 over the remaining 100 steps
    np.testing.assert_allclose(lrs[60], 0.5e-3, rtol=1e-4)
    assert lrs[-1] < 2e-5
    # power=2 decays slower early: at the midpoint (1-0.5)^2 = 0.25
    cfg2 = OptimConfig(schedule="polynomial", learning_rate=1e-3,
                       warmup_steps=0, poly_power=2.0, end_lr_factor=0.0)
    sched2 = make_schedule(cfg2, total_steps=100)
    np.testing.assert_allclose(float(sched2(50)), 0.25e-3, rtol=1e-3)


def test_reduce_on_plateau_scales_updates():
    """torch ReduceLROnPlateau analogue: after `patience` updates without
    the loss improving, the update magnitude drops by plateau_factor; an
    improving loss keeps it unscaled."""
    from pytorch_distributed_train_tpu.optim import plateau_scale

    cfg = OptimConfig(name="sgd", learning_rate=1.0, momentum=0.0,
                      weight_decay=0.0, schedule="constant",
                      plateau_factor=0.5, plateau_patience=2)
    tx, _ = make_optimizer(cfg, total_steps=100)
    params = {"w": jnp.zeros((3,))}
    state = tx.init(params)
    g = {"w": jnp.ones((3,))}
    assert float(plateau_scale(state)) == 1.0

    # constant (non-improving) loss: patience 2 → scale halves, and the
    # actual update halves with it
    for _ in range(4):
        updates, state = tx.update(g, state, params, value=jnp.float32(5.0))
    assert float(plateau_scale(state)) == 0.5
    np.testing.assert_allclose(np.asarray(updates["w"]), -0.5, rtol=1e-6)

    # improving loss: scale stays where it is (no further decay)
    for v in (4.0, 3.0, 2.0, 1.0):
        updates, state = tx.update(g, state, params, value=jnp.float32(v))
    assert float(plateau_scale(state)) == 0.5

    # no plateau in the chain → helper reports None
    tx2, _ = make_optimizer(OptimConfig(name="sgd", schedule="constant"),
                            total_steps=10)
    assert plateau_scale(tx2.init(params)) is None


def test_plateau_trains_end_to_end(tmp_path):
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = tiny_cfg(
        "data.synthetic_size=64", "optim.plateau_factor=0.5",
        "optim.plateau_patience=1", "total_steps=3",
        f"checkpoint.dir={tmp_path}/ckpt",
        f"checkpoint.save_every_steps={10**9}",
        f"obs.jsonl_path={tmp_path}/m.jsonl")
    t = Trainer(cfg)
    t.fit()
    t.close()
    import json as _json

    rows = [_json.loads(line) for line in open(tmp_path / "m.jsonl")]
    train_rows = [r for r in rows if r.get("tag") == "train"]
    assert train_rows and all("lr_plateau_scale" in r for r in train_rows)


def test_muon_orthogonalizes_matrix_updates():
    """Muon: matrix params get Newton-Schulz-orthogonalized momentum (the
    update's singular values cluster near a constant), vectors fall to the
    adam branch; training step composes via make_optimizer."""
    params = {"w": jnp.zeros((32, 48)), "b": jnp.zeros((48,))}
    tx, _ = make_optimizer(OptimConfig(
        name="muon", learning_rate=1.0, weight_decay=0.0,
        schedule="constant"), total_steps=10)
    state = tx.init(params)
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.standard_normal((32, 48)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((48,)), jnp.float32)}
    updates, state = tx.update(grads, state, params)
    uw = np.asarray(updates["w"], np.float64)
    s = np.linalg.svd(uw, compute_uv=False)
    # orthogonalized: singular values cluster (optax's 5-step NS lands
    # them in ~[0.7, 1.4] — a plateau, not exact 1.0), far tighter than
    # the raw gaussian grad's spread
    assert s[0] / s[min(32, 48) - 1] < 1.8, s[:5]
    g = np.linalg.svd(np.asarray(grads["w"]), compute_uv=False)
    assert g[0] / g[31] > 2.0  # sanity: input really was ill-conditioned
    assert np.all(np.isfinite(np.asarray(updates["b"])))

    # embedding tables are 2D but must take the ADAM branch (the Muon
    # recipe routes embeddings/head to adam): their update is NOT
    # orthogonalized — sign-ish adam steps, all magnitudes ~lr
    # rectangular kernel: square gaussians are too ill-conditioned for a
    # tight 5-step NS bound (near-zero singular directions converge slowly)
    params2 = {"embed": {"embedding": jnp.zeros((64, 32))},
               "blk": {"kernel": jnp.zeros((32, 48))}}
    state2 = tx.init(params2)
    grads2 = {"embed": {"embedding": jnp.asarray(
                  rng.standard_normal((64, 32)), jnp.float32)},
              "blk": {"kernel": jnp.asarray(
                  rng.standard_normal((32, 48)), jnp.float32)}}
    up2, _ = tx.update(grads2, state2, params2)
    se = np.linalg.svd(np.asarray(up2["embed"]["embedding"], np.float64),
                       compute_uv=False)
    sk = np.linalg.svd(np.asarray(up2["blk"]["kernel"], np.float64),
                       compute_uv=False)
    assert sk[0] / sk[-1] < 1.8          # kernel: orthogonalized
    assert se[0] / se[-1] > 3.0, se[:3]  # embedding: plain adam spread


def test_schedule_free_adamw_trains_and_evals(tmp_path):
    """Schedule-Free AdamW: rejects a decay schedule, trains end-to-end,
    and eval routes through schedule_free_eval_params (the x-iterate, not
    the z-sequence the train step carries)."""
    with pytest.raises(ValueError, match="schedule"):
        make_optimizer(OptimConfig(name="schedule_free_adamw",
                                   schedule="cosine"), total_steps=10)

    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = tiny_cfg(
        "data.synthetic_size=64", "optim.name=schedule_free_adamw",
        "optim.learning_rate=1e-3", "total_steps=3", "eval_every_steps=2",
        f"checkpoint.dir={tmp_path}/ckpt",
        f"checkpoint.save_every_steps={10**9}", "obs.log_every_steps=10",
        f"obs.jsonl_path={tmp_path}/m.jsonl")
    t = Trainer(cfg)
    t.fit()  # eval_every_steps=2 → eval (through schedule_free_eval) ran
    t.close()
    import json as _json

    rows = [_json.loads(line) for line in open(tmp_path / "m.jsonl")]
    evals = [r for r in rows if r.get("tag") == "eval"]
    assert evals and all(np.isfinite(r["loss"]) for r in evals)

    # Incompatible combinations are rejected at optimizer construction
    # (before any model/data resources are built):
    for kw, msg in ((dict(ema_decay=0.99), "EMA"),
                    (dict(plateau_factor=0.5), "plateau"),
                    (dict(decay_exclude="bias$"), "decay mask"),
                    (dict(moment_dtype="bfloat16"), "moment")):
        with pytest.raises(ValueError, match=msg):
            make_optimizer(OptimConfig(name="schedule_free_adamw",
                                       schedule="constant", **kw),
                           total_steps=10)


def test_layer_lr_decay_scales_by_depth():
    """timm-style layer decay: update magnitude ratio between adjacent
    layers equals the decay factor; head keeps full LR, embeddings get
    the slowest rate; validation rejects nonsense factors."""
    params = {
        "tok_embed": {"embedding": jnp.zeros((16, 8))},
        "layer0": {"mlp": {"kernel": jnp.zeros((8, 8))}},
        "layer1": {"mlp": {"kernel": jnp.zeros((8, 8))}},
        "lm_head": {"kernel": jnp.zeros((8, 16))},
    }
    cfg = OptimConfig(name="sgd", learning_rate=1.0, momentum=0.0,
                      weight_decay=0.0, schedule="constant",
                      layer_lr_decay=0.5)
    tx, _ = make_optimizer(cfg, total_steps=10)
    state = tx.init(params)
    grads = jax.tree.map(lambda p: jnp.ones_like(p), params)
    updates, _ = tx.update(grads, state, params)

    def mag(x):
        return float(np.abs(np.asarray(x)).mean())

    l0, l1 = mag(updates["layer0"]["mlp"]["kernel"]), mag(
        updates["layer1"]["mlp"]["kernel"])
    np.testing.assert_allclose(l0 / l1, 0.5, rtol=1e-6)  # one layer apart
    np.testing.assert_allclose(mag(updates["lm_head"]["kernel"]), 1.0,
                               rtol=1e-6)  # head: full LR
    np.testing.assert_allclose(
        mag(updates["tok_embed"]["embedding"]),
        0.5 ** 2, rtol=1e-6)  # embeddings: one below layer0

    with pytest.raises(ValueError, match="layer_lr_decay"):
        make_optimizer(OptimConfig(name="sgd", schedule="constant",
                                   layer_lr_decay=1.5), total_steps=10)

    # ViT-style block<i> paths are recognized too
    from pytorch_distributed_train_tpu.optim import layer_lr_decay_transform

    vit_params = {"patch_embed": {"kernel": jnp.zeros((4, 4))},
                  "block0": {"kernel": jnp.zeros((4, 4))},
                  "block3": {"kernel": jnp.zeros((4, 4))},
                  "head": {"kernel": jnp.zeros((4, 4))}}
    scales = layer_lr_decay_transform(0.5).init(vit_params)["scales"]
    assert float(scales["block3"]["kernel"]) == 1.0
    assert float(scales["block0"]["kernel"]) == 0.5 ** 3
    assert float(scales["head"]["kernel"]) == 1.0
    assert float(scales["patch_embed"]["kernel"]) == 0.5 ** 4

    # depthless trees fail loudly instead of becoming a uniform LR cut
    with pytest.raises(ValueError, match="depth-indexed"):
        layer_lr_decay_transform(0.5).init({"w": jnp.zeros((4, 4))})


def test_lion_sign_updates_and_single_moment():
    """Lion: updates are sign-valued (every parameter moves by exactly
    +-lr when weight decay is off) and the state carries ONE moment
    buffer — half of adam's optimizer memory."""
    from pytorch_distributed_train_tpu.optim import make_optimizer

    lr = 1e-2
    cfg = OptimConfig(name="lion", learning_rate=lr, schedule="constant",
                      warmup_steps=0, weight_decay=0.0, beta1=0.9,
                      beta2=0.99)
    tx, _ = make_optimizer(cfg, total_steps=10)
    params = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(
        (8, 4)), jnp.float32)}
    state = tx.init(params)
    grads = {"w": jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 4)), jnp.float32)}
    updates, state = tx.update(grads, state, params)
    mags = np.abs(np.asarray(updates["w"]))
    np.testing.assert_allclose(mags, lr, rtol=1e-6)

    lion_elems = sum(int(np.prod(l.shape)) for l in
                     jax.tree_util.tree_leaves(state)
                     if getattr(l, "ndim", 0) >= 2)
    adam_tx, _ = make_optimizer(
        OptimConfig(name="adamw", learning_rate=lr, schedule="constant",
                    warmup_steps=0), total_steps=10)
    adam_elems = sum(int(np.prod(l.shape)) for l in
                     jax.tree_util.tree_leaves(adam_tx.init(params))
                     if getattr(l, "ndim", 0) >= 2)
    assert lion_elems == adam_elems // 2


# --------------------------------------------------------------- SWA

def test_swa_mirror_is_exact_running_mean():
    """From swa_start on, the mirror must equal the arithmetic mean of
    the params after every swa_every-th optimizer step — checked exactly
    against host-side snapshots."""
    import numpy as np
    import optax

    from pytorch_distributed_train_tpu.train_state import TrainState

    tx = optax.sgd(0.1)
    params = {"w": jnp.asarray([1.0, 2.0])}
    state = TrainState.create(params=params, tx=tx, swa=True)
    grads = {"w": jnp.asarray([1.0, -1.0])}
    snapshots = []
    for i in range(6):
        state = state.apply_gradients(tx, grads, swa_start=3, swa_every=1)
        snapshots.append(np.asarray(state.params["w"]))
    want = np.mean(snapshots[2:], axis=0)  # steps 3..6 inclusive
    np.testing.assert_allclose(np.asarray(state.ema_params["w"]), want,
                               rtol=1e-6)
    assert int(state.swa_count) == 4
    # eval runs on the mirror
    np.testing.assert_allclose(np.asarray(state.eval_params["w"]), want,
                               rtol=1e-6)


def test_swa_every_strides_the_snapshots():
    import numpy as np
    import optax

    from pytorch_distributed_train_tpu.train_state import TrainState

    tx = optax.sgd(0.5)
    state = TrainState.create(params={"w": jnp.asarray(0.0)}, tx=tx,
                              swa=True)
    grads = {"w": jnp.asarray(-1.0)}  # params: 0.5, 1.0, 1.5, ...
    snaps = []
    for i in range(8):
        state = state.apply_gradients(tx, grads, swa_start=2, swa_every=3)
        snaps.append(float(state.params["w"]))
    # qualifying steps: 2, 5, 8 → params 1.0, 2.5, 4.0 → mean 2.5
    np.testing.assert_allclose(float(state.ema_params["w"]), 2.5,
                               rtol=1e-6)
    assert int(state.swa_count) == 3


def test_swalr_holds_constant_after_start():
    from pytorch_distributed_train_tpu.config import OptimConfig
    from pytorch_distributed_train_tpu.optim import make_optimizer

    cfg = OptimConfig(name="sgd", learning_rate=1.0, schedule="cosine",
                      warmup_steps=0, swa_start_step=50, swa_lr=0.05)
    _, sched = make_optimizer(cfg, total_steps=100)
    assert float(sched(10)) > 0.5          # cosine still high early
    assert abs(float(sched(60)) - 0.05) < 1e-9
    assert abs(float(sched(99)) - 0.05) < 1e-9


def test_swa_and_ema_mutually_exclusive():
    import pytest

    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import OptimConfig
    from pytorch_distributed_train_tpu.losses import get_loss_fn
    from pytorch_distributed_train_tpu.optim import make_optimizer

    tx, _ = make_optimizer(OptimConfig(name="sgd", learning_rate=0.1,
                                       schedule="constant",
                                       warmup_steps=0), total_steps=10)
    with pytest.raises(ValueError, match="mutually exclusive"):
        steps_lib.make_train_step(None, get_loss_fn("softmax_xent"), tx,
                                  ema_decay=0.9, swa_start=5)


def test_swa_stride_counts_optimizer_updates_under_accumulation():
    """accum=2, swa_every=2: snapshots fold at UPDATES 2, 4 (micro-steps
    4, 8), never at intermediate micro-steps — the stride is denominated
    in optimizer updates, immune to accumulation aliasing."""
    import numpy as np
    import optax

    from pytorch_distributed_train_tpu.train_state import TrainState

    tx = optax.MultiSteps(optax.sgd(0.5), 2)
    state = TrainState.create(params={"w": jnp.asarray(0.0)}, tx=tx,
                              swa=True)
    grads = {"w": jnp.asarray(-1.0)}
    counts = []
    for i in range(8):
        state = state.apply_gradients(tx, grads, swa_start=2, swa_every=2)
        counts.append(int(state.swa_count))
    # updates complete at micro-steps 2,4,6,8 (gradient_step 1..4);
    # qualifying updates are 2 and 4 -> folds land at micro 4 and 8
    assert counts == [0, 0, 0, 1, 1, 1, 1, 2]


def test_swa_mirror_keeps_param_dtype():
    import optax

    from pytorch_distributed_train_tpu.train_state import TrainState

    tx = optax.sgd(0.1)
    params = {"w": jnp.asarray([1.0, 2.0], jnp.bfloat16)}
    state = TrainState.create(params=params, tx=tx, swa=True)
    for _ in range(4):
        state = state.apply_gradients(
            tx, {"w": jnp.asarray([1.0, -1.0], jnp.bfloat16)},
            swa_start=2, swa_every=1)
    assert state.ema_params["w"].dtype == jnp.bfloat16
