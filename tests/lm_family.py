"""What the decoder families' test files share (test_hybrid_lm, test_laguna_lm,
test_ouro_lm, test_solar_lm, test_kanana_lm): the loader of a configuration's
plain reference from ``benchmark/references/``, ``close``, the exact-products
fixture, the builder of (configuration, reference, program's config and model
at the rehearsal's sizes) cached a worker process, and the body of every test
two or more of the files hold, written once. A family's file gives each body
its own numbers (tolerances, shapes, hashes) from a test of its own name and
keeps what only it has.

A new configuration's test file starts from these: import ``exact_products``
(autouse in the importing module), build with ``family(name)``, and call the
bodies below from its tests.
"""

import functools
import hashlib
import importlib.util
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_train_tpu import losses, steps
from pytorch_distributed_train_tpu.config import get_preset
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.optim import decay_mask_fn, make_optimizer
from pytorch_distributed_train_tpu.train_state import TrainState

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PLAIN = lambda t: t  # noqa: E731 - the references' float32 rounder


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tol=2e-5):
    """max |a - b| under ``tol`` of max |b|, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = np.abs(b).max() + 1e-30
    assert np.abs(a - b).max() < tol * scale, (np.abs(a - b).max() / scale, tol)


def flat(tree):
    """{leaf path as text: leaf}."""
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def router_biases(params):
    """{expert layer: its router's selection bias, on the host}."""
    return {k: np.asarray(v["moe"]["router"]["bias"])
            for k, v in params.items() if "moe" in v}


def signature(tree):
    return [(jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


@functools.lru_cache(maxsize=None)
def load(name):
    """(configuration file, its reference's module)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        name + "_reference",
        os.path.join(BENCH, "references", config["reference"] + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return config, mod


def rehearsal_cfg(name, *overrides):
    """The configuration's preset at its rehearsal's sizes, a fresh copy."""
    cfg = get_preset(load(name)[0]["preset"])
    cfg.apply_overrides([*load(name)[0]["rehearsal_overrides"], *overrides])
    return cfg


@functools.lru_cache(maxsize=None)
def family(name):
    """The configuration's file, its reference's module, the Reference and
    the program's config and model at the rehearsal's sizes: built once a
    worker, whichever files' tests the worker is handed."""
    config, mod = load(name)
    cfg = rehearsal_cfg(name)
    return types.SimpleNamespace(
        config=config, mod=mod, ref=mod.Reference(config, rehearsal=True),
        cfg=cfg, model=build_model(cfg.model, cfg.precision))


def shapes_of(model, ids):
    return jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, ids, train=False)["params"])


@functools.lru_cache(maxsize=None)
def seeded(name):
    """The reference's parameters from seed 17 and one batch (2, 128): what
    the whole-model tests run on."""
    fam = family(name)
    return (fam.ref.init_variables(17)["params"],
            jax.random.randint(jax.random.PRNGKey(18), (2, 128), 0,
                               fam.cfg.model.vocab_size))


def reference_logits(name, params, ids):
    """The reference's logits a row, one jitted program for all rows."""
    one_row = jax.jit(lambda row: family(name).ref._logits(
        params, row, PLAIN)[0])
    return jnp.stack([one_row(row) for row in ids])


# ------------------------------------------------ the bodies the files share

def logits_match_the_reference(name):
    """The program's tree is the reference's by name, shape and dtype, and
    its logits on the reference's seeded weights are the reference's.
    Returns the parameters."""
    model = family(name).model
    params, ids = seeded(name)
    assert signature(shapes_of(model, ids)) == signature(params)
    got = jax.jit(lambda p: model.apply({"params": p}, ids, train=False))(
        params)
    close(got, reference_logits(name, params, ids))
    return params


def logits_and_gradients_match_the_reference(name, mutable, chosen_shape):
    """Logits, the summed next-token loss and every leaf's gradient of the
    PROGRAM in one piece against the reference's layer-by-layer sweep (a
    router's bias gets no gradient in either). Returns (what the model
    sowed into ``mutable``, the reference's chosen experts)."""
    fam = family(name)
    params, ids = seeded(name)
    assert signature(shapes_of(fam.model, ids)) == signature(params)

    def loss(p):
        logits, sown = fam.model.apply({"params": p}, ids, train=True,
                                       mutable=mutable)
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        return -jnp.sum(jnp.take_along_axis(
            logp, ids[:, 1:, None], -1)), (logits, sown)

    (got_loss, (logits, sown)), got = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params)
    close(logits, reference_logits(name, params, ids))
    want_loss, grads, chosen = fam.ref._sweep("float32", params, ids, True)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert chosen.shape == chosen_shape
    got, grads = flat(got), flat(grads)
    assert set(got) == set(grads)
    for leaf, w in grads.items():
        close(got[leaf], w, tol=2e-4)
        if leaf.endswith("['router']['bias']"):  # no gradient reaches it
            assert float(jnp.max(jnp.abs(got[leaf]))) == 0.0
    return sown, chosen


def sweep_is_the_whole_models_gradient(name, chosen_shape):
    """The REFERENCE against itself: ``_sweep`` takes the backward pass a
    layer at a time from the host, with programs shared by the layers of
    one kind; the same model in one piece under ``jax.grad`` gives the same
    loss and the same gradient, leaf by leaf (a router's bias gets none in
    either)."""
    fam = family(name)
    params = fam.ref.init_variables(23)["params"]
    ids = jax.random.randint(jax.random.PRNGKey(24), (2, 64), 0,
                             fam.cfg.model.vocab_size)

    def loss(p):
        total = 0.0
        for row in ids:
            logp = jax.nn.log_softmax(
                fam.ref._logits(p, row, PLAIN)[0][:-1], -1)
            total -= jnp.sum(jnp.take_along_axis(logp, row[1:, None], -1))
        return total

    want_loss, want = jax.jit(jax.value_and_grad(loss))(params)
    got_loss, got, chosen = fam.ref._sweep("float32", params, ids, True)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * float(want_loss)
    assert chosen.shape == chosen_shape
    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    for leaf, w in want.items():
        if leaf.endswith("['router']['bias']"):
            assert not np.any(np.asarray(got[leaf])) and not np.any(
                np.asarray(w))
        else:
            close(got[leaf], w, tol=1e-4)


def preset_tree(preset):
    """(the preset's config, its model, its parameter tree's shapes, the
    parameter count) at the PUBLISHED sizes: abstract, nothing runs."""
    cfg = get_preset(preset)
    model = build_model(cfg.model, cfg.precision)
    shapes = shapes_of(model, jnp.zeros((1, 64), jnp.int32))
    return cfg, model, shapes, sum(
        int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))


def decay_mask(cfg, shapes):
    """{leaf: decayed}, having held it to the rule every family's preset
    follows: exactly the kernels and the embeddings decay."""
    mask = flat(decay_mask_fn(cfg.optim.decay_exclude)(shapes))
    for leaf, decayed in mask.items():
        assert decayed == (leaf.endswith("['kernel']")
                           or leaf.endswith("['embedding']")), leaf
    return mask


def train_state(cfg, params, tx=None):
    """(the config's optimizer, a fresh TrainState on ``params``)."""
    if tx is None:
        tx, _ = make_optimizer(cfg.optim, 10, 0)
    return tx, TrainState.create(params=params, tx=tx, batch_stats={},
                                 dynamic_scale=None, ema=False, swa=False)


def tree_and_lowered_step_are_the_parents(cfg, step_overrides, leaves,
                                          want_tree, want_step):
    """sha256 of json.dumps([(leaf path, shape, dtype), ...]) of ``cfg``'s
    parameter tree, and of the lowered StableHLO of its train step under
    ``step_overrides`` on a (2, 128) batch, as a run lowers it: a preset
    that sets none of a new family's fields builds the parent's tree and
    lowers the parent's step, byte for byte."""
    ids = jnp.zeros((2, 128), jnp.int32)
    sig = signature(shapes_of(build_model(cfg.model, cfg.precision), ids))
    assert len(sig) == leaves
    tree = hashlib.sha256(json.dumps(sig).encode()).hexdigest()
    assert tree == want_tree, f"parameter tree moved: sha256 {tree}"

    cfg.apply_overrides(step_overrides)
    model = build_model(cfg.model, cfg.precision)
    tx, _ = make_optimizer(cfg.optim, 10, 0)

    def init(rng):
        return train_state(cfg, model.init(
            {"params": rng}, ids, train=False)["params"], tx)[1]

    step = steps.make_train_step(model, losses.get_loss_fn(cfg.loss), tx)
    with jax.default_matmul_precision("default"):  # as a run lowers it
        text = jax.jit(step).lower(
            jax.eval_shape(init, jax.random.PRNGKey(0)),
            {"input_ids": jax.ShapeDtypeStruct((2, 128), jnp.int32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == want_step, f"lowered step moved: sha256 {got}"
