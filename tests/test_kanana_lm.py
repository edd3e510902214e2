"""models/hybrid.py with latent attention in EVERY layer in DeepSeek-V3's
plain form (no head norms, no gate, the rotated dims in pairs), shared
experts at a width of their own, a 128-wide ungrouped sigmoid router, the
selection bias's balancing update as state the step moves outside the
gradient, and the preset ``kanana2_lm_ep8``, against the plain reference the
benchmark keeps (benchmark/references/kanana2_lm_ep8.py, which imports
nothing of the program) on seeded weights at tiny sizes; and the share tied
to the model: the expert shares' routed parts, the shared experts counted
once, add up to the uncut reference's layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lm_family import PLAIN, decay_mask, family, preset_tree
from lm_family import close as _close
from lm_family import exact_products  # noqa: F401 - autouse here
from lm_family import load as _load
from lm_family import logits_and_gradients_match_the_reference
from lm_family import router_biases as _biases
from lm_family import train_state as _state

from pytorch_distributed_train_tpu import losses, steps
from pytorch_distributed_train_tpu.models import hybrid
from pytorch_distributed_train_tpu.models.llama import LlamaMLP
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.ops import moe
from pytorch_distributed_train_tpu.optim import make_optimizer

KANANA = "kanana2_lm_ep8"
F32 = jnp.float32
RATE = 1e-3


@pytest.fixture(scope="module")
def bench():
    """(configuration file, its Reference at the rehearsal's sizes: 4 of 16
    experts, 3 a token, two shared experts of 32; the program's config at
    the same sizes)."""
    fam = family(KANANA)
    return fam.config, fam.ref, fam.cfg


def _mla(m, **kw):
    form = dict(qk_norm=m.mla_qk_norm, out_gate=m.mla_out_gate,
                rope=m.mla_rope)
    return hybrid.MLAMixer(
        m.num_heads, m.head_dim, m.rope_head_dim, m.kv_lora_rank,
        m.rope_theta, m.max_seq_len, m.rms_norm_eps, F32, F32,
        **{**form, **kw})


# ------------------------------------------------- the mixer's plain form

def test_plain_latent_mixer_matches_the_reference(bench):
    """No norm over a head, no gate, the pairs (2i, 2i+1) rotated (in the
    reference in place, in the program side by side: the same scores),
    k_pe rotated once for all heads; its tree has no ``q_norm``, ``k_norm``
    or ``g_proj``."""
    _, ref, cfg = bench
    m = cfg.model
    p = ref.init_variables(13)["params"]["layer1"]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 128, m.hidden_size))
    mixer = _mla(m)
    got = jax.jit(mixer.apply)({"params": p}, x)
    rows = jax.jit(lambda r: ref._mla(p, r, PLAIN))
    _close(got, jnp.stack([rows(x[b]) for b in range(2)]))
    made = jax.eval_shape(lambda: mixer.init(
        {"params": jax.random.PRNGKey(0)}, x)["params"])
    assert sorted(made) == sorted(p) == [
        "k_rope_proj", "kv_down", "kv_norm", "kv_up", "o_proj", "q_proj"]


@pytest.mark.parametrize("field,value,leaves", [
    ("rope", "halves", []),
    ("qk_norm", True, ["q_norm", "k_norm"]),
    ("out_gate", "head", ["g_proj"]),
])
def test_each_field_of_the_latent_form_is_its_own(bench, field, value,
                                                  leaves):
    """Each of the three fields alone changes the mixer: the rotation's
    pairing moves the output on the same leaves (the fault the benchmark
    plants), the head norms and the gate each bring their leaves."""
    _, ref, cfg = bench
    m = cfg.model
    p = dict(ref.init_variables(13)["params"]["layer1"]["mla"])
    x = jax.random.normal(jax.random.PRNGKey(14), (1, 128, m.hidden_size))
    plain = jax.jit(_mla(m).apply)({"params": p}, x)
    other = _mla(m, **{field: value})
    made = jax.jit(other.init)({"params": jax.random.PRNGKey(1)}, x)["params"]
    assert sorted(set(made) - set(p)) == sorted(leaves)
    p.update({k: made[k] for k in leaves})
    assert float(jnp.max(jnp.abs(
        jax.jit(other.apply)({"params": p}, x) - plain))) > 1e-4


def test_unknown_latent_forms_and_a_rate_on_a_softmax_router_are_refused():
    x = jnp.zeros((1, 16, 32))
    for kw in (dict(rope="thirds"), dict(out_gate="channel")):
        with pytest.raises(ValueError, match="latent attention"):
            jax.eval_shape(hybrid.MLAMixer(
                2, 16, 8, 32, 1e4, 16, 1e-6, F32, F32, **kw).init,
                {"params": jax.random.PRNGKey(0)}, x)
    spec = moe.HeldExpertsSpec(num_experts=8, top_k=2, score="softmax",
                               held=4, bias_rate=1e-3)
    with pytest.raises(ValueError, match="no selection bias"):
        jax.eval_shape(moe.HeldExpertsMLP(spec, LlamaMLP, 16, F32, F32).init,
                       {"params": jax.random.PRNGKey(0)}, x)


# -------------------------------------------- the share tied to the model

def test_the_expert_shares_add_up_to_the_uncut_references_layer(bench):
    """One whole residual block of the UNCUT reference (all 16 experts
    held, the rehearsal's stand-in for 128) from the program's four shares
    of 4: latent attention (every head on every chip), then each share's
    routed part of the expert layer, the shared experts (one SwiGLU of
    twice the routed width) counted ONCE."""
    config, mod = _load(KANANA)
    whole = dict(config)
    whole["rehearsal"] = {**config["rehearsal"], "n_routed_experts": 16}
    ref = mod.Reference(whole, rehearsal=True)
    _, _, cfg = bench
    m = cfg.model
    p = ref.init_variables(5)["params"]["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(31), (1, 128, m.hidden_size))
    norm = lambda t, name: t * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(t * t, -1, keepdims=True) + m.rms_norm_eps) \
        * p[name]["scale"]
    x1 = x + jax.jit(_mla(m).apply)({"params": p["mla"]},
                                    norm(x, "input_norm"))
    h = norm(x1, "post_attn_norm")
    shared = LlamaMLP(m.moe_shared_mlp_dim, F32, F32).apply(
        {"params": p["moe"]["shared"]}, h)
    assert p["moe"]["shared"]["gate_proj"]["kernel"].shape \
        == (m.hidden_size, 2 * m.moe_mlp_dim)
    routed, loads = 0.0, []
    for first in range(0, 16, 4):
        spec = moe.HeldExpertsSpec(
            num_experts=16, top_k=m.expert_top_k,
            routed_scale=m.moe_routed_scale, held_first=first, held=4,
            capacity_factor=8.0, shared_mlp_dim=m.moe_shared_mlp_dim,
            bias_rate=RATE)
        share = {"router": p["moe"]["router"], "shared": p["moe"]["shared"],
                 "experts": jax.tree.map(lambda w: w[first:first + 4],
                                         p["moe"]["experts"])}
        (y, stats), sown = jax.jit(lambda v, h, spec=spec: moe.HeldExpertsMLP(
            spec, LlamaMLP, m.moe_mlp_dim, F32, F32).apply(
                v, h, mutable=["router_load"]))({"params": share}, h)
        assert float(stats[2]) == 0.0  # no pair past the row bound
        routed = routed + (y - shared)
        loads.append(np.asarray(sown["router_load"]["counts"]))
    want, chosen = jax.jit(lambda r: ref._layer(1, p, r, PLAIN))(x[0])
    _close(x1 + routed + shared, want[None], tol=5e-5)
    assert float(jnp.max(jnp.abs(x1 + y - want[None]))) > 1e-3
    # every share counts the SAME load, over all 16 outputs: the reference's
    for load in loads:
        np.testing.assert_array_equal(load, np.asarray(jnp.sum(chosen, 0)))
    assert loads[0].sum() == 128 * m.expert_top_k


# ------------------------------------------------- the whole model, the step

def test_model_logits_and_gradients_match_the_reference():
    sown, chosen = logits_and_gradients_match_the_reference(
        KANANA, mutable=["step_metrics", "router_load"],
        chosen_shape=(2, 2, 128, 16))  # layers, rows, S, ALL outputs
    # the routers' load, a layer's counts where its `router` sits
    assert set(sown["step_metrics"]) == {
        "moe_rows_fullest", "moe_rows_mean", "moe_rows_over_bound",
        "moe_tile_visits_ratio",
        "update_invalid"}
    for i, layer in enumerate(("layer1", "layer2")):
        np.testing.assert_array_equal(
            np.asarray(sown["router_load"][layer]["moe"]["counts"]),
            np.asarray(jnp.sum(chosen[i], (0, 1))))


def test_three_steps_with_the_bias_update_match_the_reference(bench):
    """Three AdamW steps from the seeded weights through the program's own
    train step against the reference's ``follow``: each loss, every bias
    entry after every step (its sign from that step's counts), the other
    leaves' change; and the step's metrics of the update."""
    _, ref, cfg = bench
    model = family(KANANA).model
    batches = ref.make_batches(17, {"rehearsal_batch": 2, "seq_len": 128}, 3)
    want = ref.follow(17, batches)
    tx, state = _state(cfg, ref.init_variables(17)["params"])
    step = jax.jit(steps.make_train_step(
        model, losses.get_loss_fn(cfg.loss), tx))
    start = state.params
    for n, batch in enumerate(batches):
        before = _biases(state.params)
        state, metrics = step(state, batch, jax.random.PRNGKey(0))
        assert abs(float(metrics["loss"]) - want["losses"][n]) < 2e-5
        after = _biases(state.params)
        for i, layer in enumerate(("layer1", "layer2")):
            c = want["counts"][n][i]
            np.testing.assert_allclose(
                after[layer] - before[layer],
                RATE * np.sign(c.mean() - c), atol=1e-7)
            np.testing.assert_allclose(after[layer],
                                       want["bias_after"][n][i], atol=1e-7)
        assert float(metrics["moe_load_mean"]) == 2 * 128 * 3 / 16
        assert float(metrics["moe_load_fullest"]) == np.mean(
            [want["counts"][n][i].max() for i in range(2)])
        assert float(metrics["moe_bias_abs_max"]) == pytest.approx(
            max(np.abs(b).max() for b in after.values()))
        assert "router_load" not in metrics
    # the optimizer's moments never saw the bias; the other leaves moved as
    # the reference's did
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in  # noqa: E731
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    for moment in ("mu", "nu"):
        field = [getattr(s, moment) for s in jax.tree.leaves(
            state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, moment)][0]
        for leaf, v in flat(field).items():
            if leaf.endswith("['router']['bias']"):
                assert float(jnp.max(jnp.abs(v))) == 0.0
    change = flat(jax.tree.map(jnp.subtract, state.params, start))
    for leaf, norm in want["param_change"].items():
        got = float(jnp.sqrt(jnp.sum(change[leaf] ** 2)))
        assert got == pytest.approx(float(norm), rel=2e-3, abs=1e-9), leaf
    assert step.__wrapped__.resolved["router_bias_rate"] == RATE


@pytest.fixture(scope="module")
def two_layers(bench):
    """The dense layer and ONE expert layer (all that the step's contracts
    below need, and a third of a step's compile less) with its state, a
    batch of four rows, and the one-device step over that batch: the bias
    before and after it, and its metrics."""
    _, _, cfg = bench
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, num_layers=2, layer_kinds=("mla", "mla")))
    model = build_model(cfg.model, cfg.precision)
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 64), 0, 256)
    params = jax.jit(lambda key: model.init(  # (eager, an op at a time: 20 s)
        {"params": key}, ids, train=False)["params"])(jax.random.PRNGKey(5))
    host = jax.device_get(params)  # (the mesh's steps donate their state)
    fresh = lambda: _state(cfg, jax.tree.map(jnp.array, host))  # noqa: E731
    tx, state = fresh()
    batch, rng = {"input_ids": ids}, jax.random.PRNGKey(6)
    one, metrics = jax.jit(steps.make_train_step(
        model, losses.get_loss_fn(cfg.loss), tx))(state, batch, rng)
    return (cfg, model, tx, lambda: fresh()[1], batch, rng,
            _biases(params)["layer1"], _biases(one.params)["layer1"],
            {k: float(v) for k, v in metrics.items()})


def test_a_skipped_step_leaves_the_bias_where_it_was(two_layers):
    """``update_invalid`` (pairs past the expert layer's row bound): the
    step keeps its old state, the bias with it; inside the bound the same
    step moves every entry by the rate."""
    cfg, _, tx, state, batch, rng, start, moved, metrics = two_layers
    model = build_model(dataclasses.replace(
        cfg.model, expert_capacity_factor=0.05), cfg.precision)
    step = steps.make_train_step(model, losses.get_loss_fn(cfg.loss), tx)
    new, skipped = jax.jit(step)(state(), batch, rng)
    assert float(skipped["update_skipped"]) == 1.0
    np.testing.assert_array_equal(_biases(new.params)["layer1"], start)
    assert float(skipped["moe_bias_abs_max"]) == np.abs(start).max()
    # (an output that sits exactly on the mean stays: sign(0) = 0)
    assert metrics["update_skipped"] == 0.0
    assert set(np.round(np.abs(moved - start) / RATE).astype(int)) <= {0, 1}
    assert np.abs(moved - start).max() == pytest.approx(RATE, rel=1e-3)
    assert metrics["moe_bias_abs_max"] == pytest.approx(np.abs(moved).max())


@pytest.mark.parametrize("path", ["gspmd", "shard_map", "accumulated",
                                  "fused_epilogue"])
def test_the_update_over_a_split_batch_is_the_whole_batchs(two_layers, path):
    """The counts are the GLOBAL batch's: on ``data=2`` virtual devices
    under the partitioner (its own reduction), inside ``shard_map`` (the
    step sums them over the batch axes), and over two accumulated
    microbatches (they add up), the bias moves exactly as on one device
    over the same batch, and the load reads the same; the one-pass fused
    epilogue (``train.fused_epilogue``) applies the same rule."""
    from pytorch_distributed_train_tpu.config import MeshConfig
    from pytorch_distributed_train_tpu.parallel.mesh import build_mesh
    from pytorch_distributed_train_tpu.parallel.partition import (
        rules_for_model,
    )

    cfg, model, tx, state, batch, rng, _, one, want = two_layers
    loss_fn = losses.get_loss_fn(cfg.loss)
    if path == "accumulated":
        got, metrics = jax.jit(steps.make_train_step(
            model, loss_fn, tx, grad_accum_steps=2))(state(), batch, rng)
    elif path == "fused_epilogue":
        from pytorch_distributed_train_tpu.optim import make_fused_update

        _, sched = make_optimizer(cfg.optim, 10, 0)
        got, metrics = jax.jit(steps.make_train_step(
            model, loss_fn, tx, fused_update=make_fused_update(
                cfg.optim, sched)))(state(), batch, rng)
    else:
        mesh = build_mesh(MeshConfig(data=2), jax.devices()[:2])
        sharding = steps.state_shardings(
            mesh, rules_for_model("hybrid_lm"), jax.eval_shape(state))
        if path == "gspmd":
            step = steps.jit_train_step(
                steps.make_train_step(model, loss_fn, tx), mesh, sharding)
        else:
            axes = ("data", "fsdp")
            step = steps.jit_overlap_train_step(
                steps.make_train_step(
                    model, loss_fn, tx,
                    reduce_grads_accum=steps.monolithic_grad_reducer(axes),
                    reduce_metrics=steps.metrics_reducer(axes)),
                mesh, sharding)
        got, metrics = step(jax.device_put(state(), sharding), batch, rng)
    np.testing.assert_array_equal(_biases(got.params)["layer1"], one)
    for name in ("moe_load_fullest", "moe_load_mean", "moe_bias_abs_max"):
        assert float(metrics[name]) == want[name], name


# ------------------------------------------------------------- the preset

def test_preset_builds_its_share_counts_flops_decay_mask_and_lines(capfd):
    from pytorch_distributed_train_tpu.parallel.partition import (
        P,
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    hybrid._built_logged.clear()
    moe._moe_logged.clear()
    cfg, _, shapes, count = preset_tree(KANANA)
    err = capfd.readouterr().err.splitlines()
    assert next(ln for ln in err if ln.startswith("[hybrid]")) == (
        "[hybrid] layers=6 kinds=mla,mla,mla,mla,mla,mla "
        "heads=32,32,32,32,32,32 kv_heads=32 window=0 dense_layers=1 "
        "mla=plain rope=pairs")
    assert next(ln for ln in err if ln.startswith("[moe]")) == (
        "[moe] experts=128 held=16 ids=0-15 top_k=6 groups=1/1 "
        "score=sigmoid tokens=64 row_bound=192 shared=1536 bias_rate=0.001")
    assert hybrid.MixerVariants().mla_form == "mla=normed+gated rope=halves"
    # a mixer 26.35 M, the dense layer 64.10 M, an expert layer 111.55 M,
    # embedding and head 65.67 M: 11.0 GB at 16 B a parameter
    assert count == 687_502_976
    assert shapes["layer1"]["moe"]["shared"]["up_proj"]["kernel"].shape \
        == (2048, 1536)
    assert shapes["layer1"]["moe"]["experts"]["up_proj"]["kernel"].shape \
        == (16, 2048, 768)
    decay_mask(cfg, shapes)
    # what this chip computes a token, by hand: six latent mixers (q, the
    # latent and k_pe, [k_nope | v], o; the un-masked scores and values),
    # the dense FFN, five expert layers (router 128 wide, the shared
    # experts' 1536, 6 x 16 / 128 routed experts a token), the head
    d, h, s = 2048, 32, 8192
    mla = (2.0 * d * h * 192 + 2.0 * d * (512 + 64) + 2.0 * 512 * h * 256
           + 2.0 * h * 128 * d + 2.0 * s * h * 192 + 2.0 * s * h * 128)
    moe_ = 2.0 * d * 128 + 6.0 * d * 1536 + 6.0 * d * 768 * 6 * 16 / 128
    want = 6 * mla + 6.0 * d * 6144 + 5 * moe_ + 2.0 * d * 16032
    assert flops.fwd_flops_per_item(cfg.model, s) == pytest.approx(want)
    specs = rules_for_model("hybrid_lm").tree_specs(shapes)
    assert specs["layer1"]["moe"]["shared"]["up_proj"]["kernel"] \
        == P("fsdp", "tensor")
    assert specs["layer1"]["moe"]["router"]["bias"] == P()
