"""models/hybrid.py and ops/moe.py's held-experts layer against the plain
reference the benchmark keeps (benchmark/references/ling3_flash_lm_ep64.py,
which imports nothing of the program): the router, each mixer and the whole
model on seeded weights at tiny sizes; no token dropped; the shares of an
expert-parallel layer add up to the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lm_family import close as _close
from lm_family import exact_products  # noqa: F401 - autouse here
from lm_family import (
    decay_mask,
    family,
    logits_match_the_reference,
    preset_tree,
    rehearsal_cfg,
    sweep_is_the_whole_models_gradient,
    train_state,
)

from pytorch_distributed_train_tpu.models import hybrid
from pytorch_distributed_train_tpu.models.llama import LlamaMLP
from pytorch_distributed_train_tpu.ops import moe

LING3 = "ling3_flash_lm_ep64"
F32 = jnp.float32


@pytest.fixture(scope="module")
def bench():
    """(configuration file, its Reference at the rehearsal's sizes, the
    program's config at the same sizes)."""
    fam = family(LING3)
    return fam.config, fam.ref, fam.cfg


def _spec(**kw):
    base = dict(num_experts=32, top_k=4, n_groups=4, topk_groups=2,
                routed_scale=2.5, held_first=0, held=4, capacity_factor=4.0)
    return moe.HeldExpertsSpec(**{**base, **kw})


# ------------------------------------------------------------- the router

def test_router_groups_bias_for_selection_only_weights_over_all_chosen():
    spec = _spec()
    key = jax.random.PRNGKey(0)
    scores = jax.nn.sigmoid(jax.random.normal(key, (64, 32)))
    bias = jnp.zeros((32,)).at[5].set(10.0)  # expert 5 is always chosen
    ids, w = moe.group_limited_topk(scores, bias, spec)
    assert ids.shape == w.shape == (64, 4)
    _close(jnp.sum(w, -1), jnp.full((64,), 2.5))       # over ALL chosen
    assert bool(jnp.all(jnp.any(ids == 5, -1)))
    chosen = jnp.take_along_axis(scores, ids, 1)        # scores, no bias
    _close(w, 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True))
    # at most topk_groups of the groups (8 experts each) are used a token
    groups_used = jax.vmap(lambda r: jnp.sum(jnp.bincount(
        r // 8, length=4) > 0))(ids)
    assert int(jnp.max(groups_used)) <= 2
    # a group's score is the sum of its two largest selection scores
    sel = (scores + bias).reshape(64, 4, 8)
    want = jnp.argsort(-jnp.sum(jnp.sort(sel, -1)[..., -2:], -1), -1)[:, :2]
    used = jnp.sort(jax.vmap(lambda r: jnp.unique(
        r // 8, size=2, fill_value=99))(ids), -1)
    full = groups_used == 2  # a token may fill its 4 from one group only
    assert bool(jnp.all(jnp.where(full[:, None],
                                  used == jnp.sort(want, -1), True)))


def test_router_matches_the_reference(bench):
    _, ref, cfg = bench
    m = cfg.model
    spec = _spec(held=m.experts_held)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k1, (96, m.hidden_size))
    p = {"kernel": 0.3 * jax.random.normal(k2, (m.hidden_size, 32)),
         "bias": 0.05 * jax.random.normal(k3, (32,))}
    ids, w = moe.group_limited_topk(jax.nn.sigmoid(x @ p["kernel"]),
                                    p["bias"], spec)
    dense = jnp.zeros((96, 32)).at[jnp.arange(96)[:, None], ids].set(w)
    _close(dense[:, :m.experts_held], ref._route(p, x))
    with_bias = np.asarray(ids)
    without, _ = moe.group_limited_topk(jax.nn.sigmoid(x @ p["kernel"]),
                                        jnp.zeros((32,)), spec)
    assert (np.sort(with_bias, -1) != np.sort(np.asarray(without), -1)).any()


# ------------------------------------------------------ the expert layer

def _layer(spec, width=24):
    return moe.HeldExpertsMLP(spec, LlamaMLP, width, F32, F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def test_every_token_on_one_held_expert_comes_back_exact_none_dropped():
    spec = _spec()
    layer = _layer(spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 16))
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    # equal scores (0.5) everywhere; the bias picks held expert 2 and seven
    # absent ones in two groups for every token
    params["router"]["kernel"] = jnp.zeros_like(params["router"]["kernel"])
    params["router"]["bias"] = jnp.zeros((32,)).at[
        jnp.array([2, 9, 10, 11])].set(1.0)
    y, stats = layer.apply({"params": params}, x)
    e = {k: v["kernel"][2] for k, v in params["experts"].items()}
    s = {k: v["kernel"] for k, v in params["shared"].items()}
    want = _swiglu(x, s["gate_proj"], s["up_proj"], s["down_proj"]) \
        + 2.5 / 4 * _swiglu(x, e["gate_proj"], e["up_proj"], e["down_proj"])
    _close(y, want)
    # (80 rows of one expert in one row tile, and a step each for the three
    # held experts of no rows: four grid steps for one tile of real rows)
    np.testing.assert_allclose(np.asarray(stats), [80.0, 20.0, 0.0, 4.0])


def test_pairs_past_the_row_bound_are_counted_never_silent():
    spec = _spec(capacity_factor=0.5)   # 80 tokens: bound 24 < 80 pairs
    assert spec.row_bound(80) == 24
    ids = jnp.tile(jnp.array([[2, 9, 10, 11]]), (80, 1))
    token, weight, sizes, counts, over = moe.held_rows(
        ids, jnp.full((80, 4), 0.25), spec, 24)
    assert int(over) == 80 - 24 and int(jnp.sum(sizes)) == 24
    # by expert, inside an expert by token: the first 24 tokens of expert 2
    np.testing.assert_array_equal(np.asarray(token), np.arange(24))
    np.testing.assert_array_equal(np.asarray(weight), np.full(24, 0.25))
    np.testing.assert_array_equal(np.asarray(counts), [0, 0, 80, 0])
    # the worst case is never exceeded by the bound itself
    assert _spec(capacity_factor=1e9).row_bound(80) == 80 * 4


@pytest.mark.parametrize("routing", ["balanced", "skewed_onto_one_expert"])
def test_the_layer_at_a_wide_expert_is_the_plain_sum_of_its_held_experts(
        monkeypatch, capfd, routing):
    """The bank has ONE form at every width: the layer with matrices of whole
    tiles of 128, its grouped products in the Pallas kernels (interpreted,
    as on a TPU), against every held expert applied to EVERY token and
    summed under the router's weights, no grouped product anywhere: the
    output and every gradient. ``skewed``: most tokens lean towards one held
    expert, which gets more pairs than ``row_bound / held`` and stays inside
    the layer's bound (the bound is the layer's, not an expert's): nothing
    is dropped, nothing skipped."""
    from pytorch_distributed_train_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "unsupported", lambda K, N: None)
    monkeypatch.setattr(moe, "_moe_logged", set())
    spec = _spec(score="softmax", n_groups=1, topk_groups=1, held_first=4)
    D, F, N = 128, 256, 128
    layer = moe.HeldExpertsMLP(spec, LlamaMLP, F, F32, F32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, N // 2, D))
    params = layer.init(jax.random.PRNGKey(7), x)["params"]
    if routing != "balanced":
        x = x + 40.0 * params["router"]["kernel"][:, 5]
    w = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def by_the_layer(p, x):
        y, stats = layer.apply({"params": p}, x)
        return jnp.sum(y * w), (y, stats)

    def by_every_expert_on_every_token(p, x):
        xf = x.reshape(N, D)
        ids, wts = moe.group_limited_topk(
            jax.nn.softmax(xf @ p["router"]["kernel"], -1), None, spec)
        e, s = ({k: v["kernel"] for k, v in p[part].items()}
                for part in ("experts", "shared"))
        y = _swiglu(xf, s["gate_proj"], s["up_proj"], s["down_proj"])
        for held in range(spec.n_held):
            share = jnp.sum(jnp.where(ids == spec.held_first + held, wts, 0),
                            1)
            y = y + share[:, None] * _swiglu(
                xf, e["gate_proj"][held], e["up_proj"][held],
                e["down_proj"][held])
        return jnp.sum(y.reshape(x.shape) * w), y.reshape(x.shape)

    (_, (y, stats)), got = jax.value_and_grad(
        by_the_layer, argnums=(0, 1), has_aux=True)(params, x)
    rows = spec.row_bound(N)
    assert f" row_bound={rows} bank=grouped-kernel tiles=128x{D}x{F}," \
        f"128x{F}x{D} " in capfd.readouterr().err
    (_, want_y), want = jax.value_and_grad(
        by_every_expert_on_every_token, argnums=(0, 1), has_aux=True)(
            params, x)
    fullest, _, over, _ = np.asarray(stats)
    assert over == 0
    assert (fullest > rows // spec.n_held) == (routing != "balanced")
    _close(y, want_y)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert np.abs(np.asarray(b)).max() > 0, path
        _close(a, b)


def test_the_head_share_cells_layer_holds_no_cond_and_one_held_rows():
    """The jaxpr of ``HeldExpertsMLP`` at the head-share cell's shape (8192
    tokens, 8 of 320 experts held, 4096 x 1280; abstract: nothing compiles
    or runs): no ``cond`` (a second form of the bank behind one kept both
    branches' residuals, 3 GiB of the step's program: PERF.md section 6,
    PR 47), ONE ``held_rows`` (its two products once) and the bank's three
    grouped products."""
    spec = moe.HeldExpertsSpec(num_experts=320, top_k=8, score="softmax",
                               held_first=0, held=8)
    assert spec.row_bound(8192) == 6560 and spec.mean_rows(8192) == 204
    layer = moe.HeldExpertsMLP(spec, LlamaMLP, 1280, jnp.bfloat16, F32)
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    found = list(eqns(jax.make_jaxpr(layer.apply)(params, x).jaxpr))
    names = [e.primitive.name for e in found]
    assert "cond" not in names and "while" not in names, set(names)
    assert names.count("ragged_dot_general") \
        + names.count("ragged_dot") == 3, set(names)
    in_held_rows = [e.primitive.name for e in found
                    if "held_rows" in str(e.source_info.name_stack)]
    assert in_held_rows.count("dot_general") == 2, in_held_rows


def _pairs_by_numpy(ids, wts, spec, rows):
    """``held_rows`` in plain NumPy: the pairs on held experts sorted by
    (expert, token), packed up to ``rows``."""
    ids, wts, held = np.asarray(ids), np.asarray(wts), spec.n_held
    pairs = sorted((int(e) - spec.held_first, t, float(wts[t, c]))
                   for (t, c), e in np.ndenumerate(ids)
                   if 0 <= e - spec.held_first < held)
    counts = np.bincount([e for e, _, _ in pairs], minlength=held)
    token, weight = np.zeros(rows, np.int32), np.zeros(rows, np.float32)
    ends = np.minimum(np.cumsum(counts), rows)
    sizes = np.diff(ends, prepend=0)
    kept = pairs[:rows]
    token[:len(kept)] = [p[1] for p in kept]
    weight[:len(kept)] = [p[2] for p in kept]
    return token, weight, sizes, counts, counts.sum() - sizes.sum()


@pytest.mark.parametrize("case", [
    *(f"nth_set-fill{fill}-{order}" for fill in (0, 0.1, 0.9, 1)
      for order in ("in_order", "shuffled")),
    "held_rows-packed", "held_rows-packed-past_the_bound"])
def test_the_rth_row_is_the_rth_set_entry_of_the_expert_token_table(case):
    """``_nth_set`` against NumPy's ``flatnonzero`` (tables of several
    blocks whose length is no multiple of 128, empty, sparse, dense and
    full; ranks past the count give L), and ``held_rows`` through it against
    the pairs sorted by (expert, token)."""
    kind, *how = case.split("-")
    rng = np.random.default_rng(42)
    if kind == "nth_set":
        L, fill = 700, float(how[0][4:])     # five blocks and 60 flags
        flags = rng.random(L) < fill
        ranks = np.arange(1, L + 41, dtype=np.int32)  # 40 or more past it
        if how[1] == "shuffled":
            ranks = rng.permutation(ranks)
        set_at = np.flatnonzero(flags)
        want = np.append(set_at, np.full(L + 40, L))[ranks - 1]
        got = jax.jit(moe._nth_set)(flags, ranks)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want)
        return
    past = len(how) == 2
    spec = _spec(capacity_factor=0.5 if past else 4.0)
    # four distinct experts of 32 a token, as a router's top-k gives them
    ids = rng.permuted(np.tile(np.arange(32, dtype=np.int32), (200, 1)),
                       axis=1)[:, :4]
    wts = rng.random((200, 4), dtype=np.float32)
    rows = spec.row_bound(200)           # 400, or 56: under the 90-odd pairs
    got = jax.jit(lambda i, w: moe.held_rows(i, w, spec, rows))(ids, wts)
    want = _pairs_by_numpy(ids, wts, spec, rows)
    assert (int(want[4]) > 0) == past
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_held_rows_at_the_all_latent_cells_shape_holds_no_loop_and_no_sort():
    """The jaxpr of ``held_rows`` at 16384 tokens x 6 choices, 16 of 128
    experts held (abstract: nothing compiles or runs): no ``while`` /
    ``scan`` (a binary search is 19 dependent passes of scalar gathers
    there: PERF.md section 6, PR 42) and no ``sort``."""
    spec = moe.HeldExpertsSpec(num_experts=128, top_k=6, held_first=16,
                               held=16)
    rows = spec.row_bound(16384)
    assert rows == 49152

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    names = set(primitives(jax.make_jaxpr(
        lambda i, w: moe.held_rows(i, w, spec, rows))(
            jax.ShapeDtypeStruct((16384, 6), jnp.int32),
            jax.ShapeDtypeStruct((16384, 6), F32)).jaxpr))
    assert "dot_general" in names
    assert not names & {"while", "scan", "sort"}, names


def test_an_overflowing_step_keeps_its_state_and_reports_update_skipped():
    from pytorch_distributed_train_tpu import losses, steps
    from pytorch_distributed_train_tpu.models.registry import build_model

    # one dense and one routed layer are all this contract needs: two
    # whole steps compile here, and a third layer is a third of each
    cfg = rehearsal_cfg(LING3, "model.num_layers=2",
                        "model.layer_group_size=2")
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 256)
    # (a step inside its bound reporting 0 is the benchmark rehearsal's
    # `failed` 0: tests/benchmark/test_bench_rehearsal_ling3.py)
    cfg.model.expert_capacity_factor = 0.05
    model = build_model(cfg.model, cfg.precision)
    tx, state = train_state(cfg, jax.jit(lambda key: model.init(
        {"params": key}, ids, train=False)["params"])(jax.random.PRNGKey(5)))
    step = steps.make_train_step(model, losses.get_loss_fn(cfg.loss), tx)
    new, metrics = jax.jit(step)(state, {"input_ids": ids},
                                 jax.random.PRNGKey(6))
    assert float(metrics["update_skipped"]) == 1.0
    assert float(metrics["moe_rows_over_bound"]) > 0
    assert "update_invalid" not in metrics  # the step's own name, folded
    # the flag tells the truth: the old parameters and moments are kept,
    # the step counter advances (as under the numeric guard)
    assert int(new.step) == int(state.step) + 1
    for kept, old in ((new.params, state.params),
                      (new.opt_state, state.opt_state)):
        for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(old)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # inside the bound the same step applies its update and reports 0
    cfg.model.expert_capacity_factor = 4.0
    step = steps.make_train_step(build_model(cfg.model, cfg.precision),
                                 losses.get_loss_fn(cfg.loss), tx)
    new, metrics = jax.jit(step)(state, {"input_ids": ids},
                                 jax.random.PRNGKey(6))
    assert float(metrics["update_skipped"]) == 0.0
    # (the warm-up's first rate is 0: the moments move, the parameters not)
    assert any(np.any(np.asarray(a) != np.asarray(b)) for a, b in zip(
        jax.tree.leaves(new.opt_state), jax.tree.leaves(state.opt_state)))
    assert float(metrics["moe_rows_fullest"]) \
        >= float(metrics["moe_rows_mean"]) > 0


@pytest.mark.parametrize("E,held,score,kw", [
    (16, 4, "sigmoid", dict(top_k=4, n_groups=4, topk_groups=2)),
    (256, 8, "softmax", dict(top_k=10, n_groups=1, topk_groups=1)),
    (128, 16, "sigmoid", dict(top_k=6, n_groups=1, topk_groups=1,
                              shared_mlp_dim=48)),
], ids=["sigmoid-16-over-4-chips", "softmax-256-over-32-chips",
        "sigmoid-128-over-8-chips-shared-of-its-own-width"])
def test_the_shares_add_up_to_the_uncut_layer(E, held, score, kw):
    """16 experts over 4 chips, 4 a chip, under the sigmoid rule with its
    groups and bias; 256 experts over 32 chips, 8 a chip, 10 a token, under
    the softmax rule with neither; 128 experts over 8 chips, 16 a chip, 6 a
    token of ONE group under the sigmoid rule, beside two shared experts
    (one SwiGLU of twice the routed width): the routed parts that all the
    shares compute, plus the shared expert ONCE, are the whole layer as a
    plain loop over all the experts computes it."""
    D, F, N = 16, 24, 48
    Fs = kw.get("shared_mlp_dim", F)
    ks = jax.random.split(jax.random.PRNGKey(7), 9)
    x = jax.random.normal(ks[0], (1, N, D))
    router = {"kernel": 0.5 * jax.random.normal(ks[1], (D, E))}
    if score == "sigmoid":
        router["bias"] = 0.05 * jax.random.normal(ks[2], (E,))
    full = {n: 0.3 * jax.random.normal(k, shape) for n, k, shape in (
        ("gate_proj", ks[3], (E, D, F)), ("up_proj", ks[4], (E, D, F)),
        ("down_proj", ks[5], (E, F, D)))}
    shared = {n: {"kernel": 0.3 * jax.random.normal(k, shape)}
              for n, k, shape in (("gate_proj", ks[6], (D, Fs)),
                                  ("up_proj", ks[7], (D, Fs)),
                                  ("down_proj", ks[8], (Fs, D)))}
    kw = dict(num_experts=E, routed_scale=2.5, held=held, score=score, **kw)
    # the uncut layer, by hand
    logits = x[0] @ router["kernel"]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    ids, w = moe.group_limited_topk(s, router.get("bias"), _spec(**kw))
    dense_w = jnp.zeros((N, E)).at[jnp.arange(N)[:, None], ids].set(w)
    shared_out = _swiglu(x[0], *(shared[n]["kernel"] for n in (
        "gate_proj", "up_proj", "down_proj")))
    whole = shared_out + sum(
        dense_w[:, e:e + 1] * _swiglu(x[0], full["gate_proj"][e],
                                      full["up_proj"][e],
                                      full["down_proj"][e])
        for e in range(E))
    routed_parts = 0.0
    for first in range(0, E, held):
        layer = _layer(_spec(**kw, held_first=first), F)
        params = {"router": router, "shared": shared, "experts": {
            n: {"kernel": v[first:first + held]} for n, v in full.items()}}
        y, stats = layer.apply({"params": params}, x)
        assert float(stats[2]) == 0.0
        routed_parts = routed_parts + (y[0] - shared_out)
    _close(routed_parts + shared_out, whole)
    # and one share alone is NOT the layer: the cut leaves something out
    assert float(jnp.max(jnp.abs(y[0] - whole))) > 1e-3


# ------------------------------------------------ mixers and whole model

def _mixer_params(ref, kind, seed):
    params = ref.init_variables(seed)["params"]
    name = next(n for n, p in params.items() if kind in p)
    return params[name][kind]


def test_mla_layer_matches_the_reference(bench):
    _, ref, cfg = bench
    m = cfg.model
    p = _mixer_params(ref, "mla", 11)
    p["q_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), p["q_norm"]["scale"].shape)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 64, m.hidden_size))
    mixer = hybrid.MLAMixer(m.num_heads, m.head_dim, m.rope_head_dim,
                            m.kv_lora_rank, m.rope_theta, m.max_seq_len,
                            m.rms_norm_eps, F32, F32, attn_impl="xla")
    got = mixer.apply({"params": p}, x)
    want = jnp.stack([ref._mla(p, x[b], lambda t: t) for b in range(2)])
    _close(got, want)


def test_kda_layer_matches_the_reference_token_by_token(bench):
    _, ref, cfg = bench
    m = cfg.model
    p = _mixer_params(ref, "kda", 13)
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 128, m.hidden_size))
    mixer = hybrid.KDAMixer(m.num_heads, m.head_dim, m.conv_kernel_size,
                            m.kda_gate_lower_bound, m.rms_norm_eps, F32,
                            F32)
    got, stats = mixer.apply({"params": p}, x)
    assert stats is None  # a bounded gate's extremes are its own
    want = jnp.stack([ref._kda(p, x[b], lambda t: t) for b in range(2)])
    _close(got, want)


def _wide_kda_layer(seed):
    """A KDA layer at heads the kernels take (two heads of 128, a tile of
    128 tokens), its parameters and an input."""
    mixer = hybrid.KDAMixer(2, 128, 4, -5.0, 1e-6, F32, F32)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 128, 64))
    params = mixer.init({"params": jax.random.PRNGKey(seed + 1)},
                        x)["params"]
    return mixer, params, x


def _steer_kda(monkeypatch):
    """The dispatch sees a TPU, the kernels run interpreted (as
    tests/test_kda.py and tests/test_kda_inputs.py steer it)."""
    from pytorch_distributed_train_tpu.ops import attention, kda

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "_interpret", lambda: True)
    monkeypatch.setattr(kda, "_logged", set())


def test_kda_layer_in_its_kernels_is_the_layer_on_the_xla_path(
        monkeypatch, capfd):
    """Where the gate lets the kernels run, the shaping's pair feeds the
    core's pair: the same outputs and the same gradient of every
    parameter as the XLA chain and scan, and one ``[kda]`` line a shape
    for each of the two, ``inputs=`` beside ``impl=``."""
    from pytorch_distributed_train_tpu.ops import kda

    mixer, params, x = _wide_kda_layer(31)
    w = jax.random.normal(jax.random.PRNGKey(33), x.shape)
    run = lambda p: mixer.apply({"params": p}, x)[0]  # noqa: E731
    both = jax.value_and_grad(lambda p: jnp.sum(run(p) * w))
    monkeypatch.setattr(kda, "_logged", set())
    capfd.readouterr()  # what building the layer said
    want_out, (want_loss, want) = run(params), both(params)
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[kda]")]
    assert [ln.split(" reason=")[0].split()[-1] for ln in lines] \
        == ["inputs=xla", "impl=xla"], lines
    _steer_kda(monkeypatch)
    got_out, (got_loss, got) = run(params), both(params)
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[kda]")]
    assert len(lines) == 2, lines
    assert lines[0].endswith("inputs=pallas tile=128 heads_per_step=2")
    assert " impl=pallas tile=128 " in lines[1]
    _close(got_out, want_out)
    assert abs(float(got_loss - want_loss)) < 2e-5 * abs(float(want_loss))
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in  # noqa: E731
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    got, want = flat(got), flat(want)
    assert set(got) == set(want) and len(want) == 13
    for leaf, value in want.items():
        _close(got[leaf], value, tol=1e-4)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_programs_map_names_the_shaping_on_both_paths(path, monkeypatch):
    """``jax.named_scope("kda_inputs")`` stands round the shaping whichever
    path runs, forward and backward: the map of a compiled step
    (obs/step_program.py) holds instructions under it, which the
    benchmark's ``kda_inputs_ms_per_step`` sums."""
    from pytorch_distributed_train_tpu.obs import step_program

    if path == "pallas":
        _steer_kda(monkeypatch)
    mixer, params, x = _wide_kda_layer(35)

    def forward(p):
        return jnp.sum(mixer.apply({"params": p}, x)[0] ** 2)

    text = jax.jit(jax.grad(forward)).lower(params).compile().as_text()
    built = step_program.scope_map(text)
    under = [op for op in built.scopes.values()
             if "kda_inputs" in op.split("/")]
    assert any("transpose(jvp(" in op for op in under), under[:5]
    assert any("transpose(jvp(" not in op for op in under), under[:5]
    assert all("/KDAMixer/" in op or "kda_inputs" in op for op in under)


def test_model_logits_match_the_reference_on_its_seeded_weights():
    params = logits_match_the_reference(LING3)
    # the layer pattern: KDA, KDA, then latent attention; dense FFN first
    assert [("mla" in params[f"layer{i}"], "moe" in params[f"layer{i}"])
            for i in range(3)] == [(False, False), (False, True),
                                   (True, True)]


def test_the_references_layer_by_layer_sweep_is_the_whole_models_gradient():
    # routed layers, rows, S, held
    sweep_is_the_whole_models_gradient(LING3, chosen_shape=(2, 2, 64, 4))


def test_preset_counts_decay_mask_flops_and_partition_rules():
    from pytorch_distributed_train_tpu.parallel.partition import (
        P,
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    cfg, _, shapes, count = preset_tree(LING3)
    assert count == 714_990_240  # ISSUE 26's table: 714.99 M, 11.44 GB
    flat = decay_mask(cfg, shapes)
    assert not flat["['layer1']['moe']['router']['bias']"]
    assert not flat["['layer0']['kda']['q_conv']"]
    assert not flat["['layer0']['kda']['A_log']"]
    assert not flat["['layer0']['kda']['dt_bias']"]
    per_token = flops.train_flops_per_item(cfg.model, cfg.data.seq_len)
    assert 3.0e9 < per_token < 3.3e9  # 3 x forward, attention un-masked
    specs = rules_for_model("hybrid_lm").tree_specs(shapes)
    assert specs["layer1"]["moe"]["experts"]["gate_proj"]["kernel"] \
        == P("expert", "fsdp", "tensor")
    assert specs["layer5"]["mla"]["kv_up"]["kernel"] \
        == P("fsdp", "tensor", None)
    assert specs["layer0"]["kda"]["dt_bias"] == P()
