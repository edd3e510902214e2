"""models/hybrid.py with LFM2's layers (the gated short-convolution mixer,
grouped-query attention with an RMSNorm over each head's q and k and no
output gate, a 32-wide sigmoid router with NO shared expert beside it, the
head tied to the input table) and the preset ``lfm2_8b_a1b_lm_ep4``, against
the plain reference the benchmark keeps
(benchmark/references/lfm2_8b_a1b_lm_ep4.py, which imports nothing of the
program) on seeded weights at tiny sizes; and the shares tied to the model:
the four expert shares' routed sums add up to the uncut reference's layer,
the four vocabulary slices' logits are the uncut head's columns."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lm_family import PLAIN, decay_mask, family, flat, preset_tree
from lm_family import close as _close
from lm_family import exact_products  # noqa: F401 - autouse here
from lm_family import load as _load
from lm_family import router_biases as _biases
from lm_family import (
    logits_and_gradients_match_the_reference,
    sweep_is_the_whole_models_gradient,
)
from lm_family import train_state as _state

from pytorch_distributed_train_tpu import losses, steps
from pytorch_distributed_train_tpu.models import hybrid
from pytorch_distributed_train_tpu.models.llama import LlamaMLP
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.ops import moe

LFM2 = "lfm2_8b_a1b_lm_ep4"
F32 = jnp.float32
RATE = 1e-3


@pytest.fixture(scope="module")
def bench():
    """(configuration file, its Reference at the rehearsal's sizes: conv,
    attention, conv; 4 of 16 experts, 4 a token; the program's config at
    the same sizes)."""
    fam = family(LFM2)
    return fam.config, fam.ref, fam.cfg


def _conv(m):
    return hybrid.ConvMixer(m.conv_kernel_size, F32, F32)


def _gqa(m, **kw):
    form = dict(out_gate=m.gqa_out_gate, qk_norm=m.gqa_qk_norm,
                rms_norm_eps=m.rms_norm_eps)
    return hybrid.GQAMixer(
        m.num_heads, m.num_kv_heads, m.head_dim, 0,
        hybrid.Rotation(m.head_dim, m.rope_theta), F32, F32,
        **{**form, **kw})


# ------------------------------------------------------- the conv mixer

def test_conv_mixer_matches_the_three_term_sum(bench):
    """[B | C | u] = W_in x, z = B * u, the three taps a channel as the
    reference's explicit sum of shifted products, C *, W_out; its tree is
    two kernels and the taps."""
    _, ref, cfg = bench
    m = cfg.model
    p = ref.init_variables(13)["params"]["layer0"]["conv"]
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 128, m.hidden_size))
    got = jax.jit(_conv(m).apply)({"params": p}, x)
    rows = jax.jit(lambda r: ref._conv(p, r, PLAIN))
    _close(got, jnp.stack([rows(x[b]) for b in range(2)]))
    made = jax.eval_shape(lambda: _conv(m).init(
        {"params": jax.random.PRNGKey(0)}, x)["params"])
    assert {k: v.shape if k == "taps" else v["kernel"].shape
            for k, v in made.items()} == {
        "in_proj": (64, 192), "taps": (3, 64), "out_proj": (64, 64)}


def test_the_first_token_sees_zeros_and_a_sequence_only_its_own_past(bench):
    """Token 0 meets the LAST tap alone (zeros stand before the sequence's
    start); token t reads tokens t-2..t of its own sequence: a change to a
    later token, or to the batch's other sequence, leaves it as it was."""
    _, ref, cfg = bench
    m, d = cfg.model, cfg.model.hidden_size
    p = ref.init_variables(13)["params"]["layer0"]["conv"]
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 32, d))
    apply = jax.jit(_conv(m).apply)
    got = apply({"params": p}, x)
    bcu = x[:, 0] @ p["in_proj"]["kernel"]
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    _close(got[:, 0], (c * (p["taps"][-1] * b * u)) @ p["out_proj"]["kernel"])
    # a sequence does not see the other, nor its own future
    other = apply({"params": p}, x.at[1].set(-x[1]).at[0, 20:].set(0.0))
    np.testing.assert_array_equal(np.asarray(other[0, :20]),
                                  np.asarray(got[0, :20]))
    assert float(jnp.max(jnp.abs(other[0, 20:] - got[0, 20:]))) > 1e-4
    # ... and its reach is K - 1 tokens back: token 22 reads 20, 23 does not
    moved = apply({"params": p}, x.at[0, 20].set(0.0))[0] - got[0]
    assert [bool(jnp.any(moved[t] != 0)) for t in (19, 20, 22, 23)] \
        == [False, True, True, False]


# ----------------------------------------- attention: a norm a head, no gate

def test_normed_ungated_attention_matches_the_reference(bench):
    """An RMSNorm over each head's q and k before the rotation (halves),
    query head h on KV head h // 2, no output gate: its tree has ``q_norm``
    and ``k_norm`` (one 16-vector each) and no ``g_proj``."""
    _, ref, cfg = bench
    m = cfg.model
    p = dict(ref.init_variables(13)["params"]["layer1"]["gqa"])
    # norms off their initial 1, so that the scale's place shows
    for i, name in enumerate(("q_norm", "k_norm")):
        p[name] = {"scale": 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(40 + i), (m.head_dim,))}
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 128, m.hidden_size))
    got = jax.jit(_gqa(m).apply)({"params": p}, x)
    rows = jax.jit(lambda r: ref._gqa(p, r, PLAIN))
    _close(got, jnp.stack([rows(x[b]) for b in range(2)]))
    made = jax.eval_shape(lambda: _gqa(m).init(
        {"params": jax.random.PRNGKey(0)}, x)["params"])
    assert sorted(made) == sorted(p) == [
        "k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]
    assert made["q_norm"]["scale"].shape == (m.head_dim,)


@pytest.mark.parametrize("field,value,gone,come", [
    ("qk_norm", False, ["k_norm", "q_norm"], []),
    ("out_gate", "head", [], ["g_proj"]),
    ("out_gate", "channel", [], ["gc_proj"]),
])
def test_each_field_of_the_attention_form_is_its_own(bench, field, value,
                                                     gone, come):
    """The head norms and the gate each bring their own leaves and move the
    output on the leaves they share."""
    _, ref, cfg = bench
    m = cfg.model
    p = dict(ref.init_variables(13)["params"]["layer1"]["gqa"])
    p["q_norm"] = {"scale": jnp.full((m.head_dim,), 1.5)}
    x = jax.random.normal(jax.random.PRNGKey(16), (1, 64, m.hidden_size))
    plain = jax.jit(_gqa(m).apply)({"params": p}, x)
    other = _gqa(m, **{field: value})
    made = jax.jit(other.init)({"params": jax.random.PRNGKey(1)}, x)["params"]
    assert sorted(set(p) - set(made)) == gone
    assert sorted(set(made) - set(p)) == come
    q = {k: p.get(k, made[k]) for k in made}
    assert float(jnp.max(jnp.abs(
        jax.jit(other.apply)({"params": q}, x) - plain))) > 1e-4


def test_unknown_forms_and_a_share_of_a_conv_layers_channels_are_refused():
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match="unknown output gate"):
        jax.eval_shape(hybrid.GQAMixer(
            2, 1, 16, 0, hybrid.Rotation(16, 1e4), F32, F32,
            out_gate="row").init, {"params": jax.random.PRNGKey(0)}, x)
    cfg = family(LFM2).cfg
    with pytest.raises(ValueError, match="no share of channels"):
        build_model(dataclasses.replace(cfg.model, heads_held=2),
                    cfg.precision)
    with pytest.raises(ValueError, match="model.layer_kinds"):
        build_model(dataclasses.replace(
            cfg.model, layer_kinds=("conv", "gqa_full", "convolution")),
            cfg.precision)


# ------------------------------------------------ the expert layer's forms

def test_a_spec_without_a_shared_expert_has_no_shared_leaf():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 16))
    trees = {}
    for shared in (True, False):
        spec = moe.HeldExpertsSpec(num_experts=8, top_k=2, held=4,
                                   shared_mlp_dim=0 if shared else -1)
        layer = moe.HeldExpertsMLP(spec, LlamaMLP, 8, F32, F32)
        params = jax.jit(layer.init)({"params": jax.random.PRNGKey(3)},
                                     x)["params"]
        trees[shared] = params
        assert sorted(params) == ["experts", "router"] + ["shared"] * shared
    # the same leaves give the routed sum alone: what the shared expert
    # added is exactly what is missing
    apply = lambda shared: moe.HeldExpertsMLP(  # noqa: E731
        moe.HeldExpertsSpec(num_experts=8, top_k=2, held=4,
                            shared_mlp_dim=0 if shared else -1),
        LlamaMLP, 8, F32, F32).apply
    with_it, _ = apply(True)({"params": trees[True]}, x)
    routed, _ = apply(False)({"params": {
        k: trees[True][k] for k in ("experts", "router")}}, x)
    _close(with_it - routed, LlamaMLP(8, F32, F32).apply(
        {"params": trees[True]["shared"]}, x), tol=1e-5)


def test_the_familys_eps_in_the_weights_sum_moves_nothing_compared():
    """The family's code divides by (the sum of the chosen scores + 1e-6);
    the program divides by the sum alone (`assumed`, `norm_topk_prob`) and
    the reference keeps the published form. Over sigmoid scores as wide as
    +-3 sigma the four largest of 32 sum to more than 2, so the two forms
    stand under 5e-7 of a weight apart: a few units in a float32's last
    place, a two-thousandth of the tightest limit `correct` holds (1e-3)
    and a ten-thousandth of a bfloat16's rounding."""
    scores = jax.nn.sigmoid(3.0 * jax.random.normal(jax.random.PRNGKey(5),
                                                    (4096, 32)))
    spec = moe.HeldExpertsSpec(num_experts=32, top_k=4)
    ids, plain = moe.group_limited_topk(scores, None, spec)
    chosen = np.take_along_axis(np.asarray(scores, np.float64),
                                np.asarray(ids), 1)
    total = chosen.sum(-1, keepdims=True)
    assert total.min() > 2.0
    published = chosen / (total + 1e-6)
    np.testing.assert_allclose(plain, chosen / total, rtol=1e-6)
    assert np.abs(chosen / total / published - 1.0).max() < 5e-7


# -------------------------------------------- the shares tied to the model

def test_the_four_expert_shares_add_up_to_the_uncut_references_layer(bench):
    """One whole expert block of the UNCUT reference (all 32 router outputs
    held) from the program's four shares of 8 (ids 0-7, 8-15, 16-23,
    24-31): attention (every head on every chip), then the shares' routed
    sums; there is no shared expert to count once."""
    config, mod = _load(LFM2)
    whole = dict(config)
    whole["rehearsal"] = {**config["rehearsal"], "router_num_experts": 32,
                          "num_experts": 32}
    ref = mod.Reference(whole, rehearsal=True)
    _, _, cfg = bench
    m = cfg.model
    p = ref.init_variables(5)["params"]["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(31), (1, 128, m.hidden_size))
    norm = lambda t, name: t * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(t * t, -1, keepdims=True) + m.rms_norm_eps) \
        * p[name]["scale"]
    x1 = x + jax.jit(_gqa(m).apply)({"params": p["gqa"]},
                                    norm(x, "input_norm"))
    h = norm(x1, "post_attn_norm")
    routed, loads = 0.0, []
    for first in range(0, 32, 8):
        spec = moe.HeldExpertsSpec(
            num_experts=32, top_k=m.expert_top_k, held_first=first, held=8,
            capacity_factor=4.0, shared_mlp_dim=-1, bias_rate=RATE)
        share = {"router": p["moe"]["router"],
                 "experts": jax.tree.map(lambda w: w[first:first + 8],
                                         p["moe"]["experts"])}
        (y, stats), sown = jax.jit(lambda v, h, spec=spec: moe.HeldExpertsMLP(
            spec, LlamaMLP, m.moe_mlp_dim, F32, F32).apply(
                v, h, mutable=["router_load"]))({"params": share}, h)
        assert float(stats[2]) == 0.0  # no pair past the row bound
        routed = routed + y
        loads.append(np.asarray(sown["router_load"]["counts"]))
    want, chosen = jax.jit(lambda r: ref._layer(1, p, r, PLAIN))(x[0])
    _close(x1 + routed, want[None], tol=5e-5)
    assert float(jnp.max(jnp.abs(x1 + y - want[None]))) > 1e-3
    # every share counts the SAME load, over all 32 outputs: the reference's
    for load in loads:
        np.testing.assert_array_equal(load, np.asarray(jnp.sum(chosen, 0)))
    assert loads[0].sum() == 128 * m.expert_top_k


def test_the_four_vocabulary_slices_logits_are_the_uncut_heads_columns(
        bench):
    """The uncut model's table has four slices' rows; a chip that holds
    slice k (rows 64k..64k+63 as ITS table, ids counted from 64k) reads
    the same hidden states from it and gives the uncut logits' columns
    64k..64k+63: the tied head is sliced with the table."""
    _, _, cfg = bench
    uncut = build_model(cfg.model, cfg.precision)          # 256 rows
    sliced = build_model(dataclasses.replace(cfg.model, vocab_size=64),
                         cfg.precision)
    params = jax.jit(lambda key: uncut.init(
        {"params": key}, jnp.zeros((1, 64), jnp.int32),
        train=False)["params"])(jax.random.PRNGKey(7))
    for k in range(4):
        ids = jax.random.randint(jax.random.PRNGKey(50 + k), (2, 64),
                                 64 * k, 64 * (k + 1))
        want = jax.jit(lambda i: uncut.apply({"params": params}, i,
                                             train=False))(ids)
        part = {**params, "tok_embed": {"embedding": params["tok_embed"][
            "embedding"][64 * k:64 * (k + 1)]}}
        got = jax.jit(lambda p, i: sliced.apply({"params": p}, i,
                                                train=False))(part,
                                                              ids - 64 * k)
        _close(got, want[..., 64 * k:64 * (k + 1)])


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(bench):
    """No ``lm_head`` leaf; the table's gradient is the lookup's plus the
    head's, each read off the same model with a head of its own whose
    kernel is the table transposed."""
    _, ref, cfg = bench
    tied = family(LFM2).model
    untied = build_model(dataclasses.replace(
        cfg.model, tie_word_embeddings=False), cfg.precision)
    params = ref.init_variables(9)["params"]
    assert "lm_head" not in params
    ids = jax.random.randint(jax.random.PRNGKey(10), (2, 64), 0,
                             cfg.model.vocab_size)

    def loss(model, p):
        logp = jax.nn.log_softmax(model.apply({"params": p}, ids,
                                              train=True)[:, :-1], -1)
        return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1))

    got = jax.jit(jax.grad(lambda p: loss(tied, p)))(params)
    table = params["tok_embed"]["embedding"]
    two = jax.jit(jax.grad(lambda p: loss(untied, p)))(
        {**params, "lm_head": {"kernel": table.T}})
    lookup, head = two["tok_embed"]["embedding"], two["lm_head"]["kernel"].T
    _close(got["tok_embed"]["embedding"], lookup + head, tol=1e-5)
    for part in (lookup, head):  # neither use alone is the gradient
        assert float(jnp.linalg.norm(part)) > 0.05 * float(
            jnp.linalg.norm(lookup + head))
    _close(got["layer1"]["gqa"]["q_proj"]["kernel"],
           two["layer1"]["gqa"]["q_proj"]["kernel"], tol=1e-5)


# ------------------------------------------------- the whole model, the step

def test_model_logits_and_gradients_match_the_reference():
    sown, chosen = logits_and_gradients_match_the_reference(
        LFM2, mutable=["step_metrics", "router_load"],
        chosen_shape=(2, 2, 128, 16))  # layers, rows, S, ALL outputs
    assert set(sown["step_metrics"]) == {
        "moe_rows_fullest", "moe_rows_mean", "moe_rows_over_bound",
        "moe_tile_visits_ratio",
        "update_invalid"}
    for i, layer in enumerate(("layer1", "layer2")):
        np.testing.assert_array_equal(
            np.asarray(sown["router_load"][layer]["moe"]["counts"]),
            np.asarray(jnp.sum(chosen[i], (0, 1))))


def test_the_references_sweep_is_its_whole_models_gradient():
    sweep_is_the_whole_models_gradient(LFM2, chosen_shape=(2, 2, 64, 16))


def test_three_steps_with_the_bias_update_match_the_reference(bench):
    """Three AdamW steps from the seeded weights through the program's own
    train step against the reference's ``follow``: each loss, every bias
    entry after every step (its sign from that step's counts), every other
    leaf's change (the tied table's and the conv taps' among them)."""
    _, ref, cfg = bench
    model = family(LFM2).model
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
        assert float(metrics["moe_load_mean"]) == 2 * 128 * 4 / 16
        assert float(metrics["moe_rows_over_bound"]) == 0.0
    change = flat(jax.tree.map(jnp.subtract, state.params, start))
    assert "['layer0']['conv']['taps']" in want["param_change"]
    for leaf, norm in want["param_change"].items():
        got = float(jnp.sqrt(jnp.sum(change[leaf] ** 2)))
        assert got == pytest.approx(float(norm), rel=2e-3, abs=1e-9), leaf
    assert step.__wrapped__.resolved["router_bias_rate"] == RATE


# ------------------------------------------------------------- the preset

def test_preset_builds_its_share_counts_flops_decay_mask_and_lines(capfd):
    from pytorch_distributed_train_tpu.parallel.partition import (
        P,
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    hybrid._built_logged.clear()
    moe._moe_logged.clear()
    cfg, _, shapes, count = preset_tree(LFM2)
    err = capfd.readouterr().err.splitlines()
    assert next(ln for ln in err if ln.startswith("[hybrid]")) == (
        "[hybrid] layers=5 kinds=conv,gqa_full,conv,conv,conv "
        "heads=32,32,32,32,32 kv_heads=8 window=0 dense_layers=1 head=tied")
    assert next(ln for ln in err if ln.startswith("[moe]")) == (
        "[moe] experts=32 held=8 ids=0-7 top_k=4 groups=1/1 score=sigmoid "
        "tokens=64 row_bound=128 shared=none bias_rate=0.001")
    # a conv mixer 16.78 M, the attention mixer 10.49 M, the dense SwiGLU
    # 44.04 M, 8 experts 88.08 M and a router 65,568 a layer, the table's
    # quarter 33.55 M: 8.13 GB at 16 B a parameter
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    gqa = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 8 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert count == 507_820_288 == (
        (conv + 3 * 2048 * 7168) + (gqa + experts) + 3 * (conv + experts)
        + 5 * 2 * 2048 + 2048 + 16384 * 2048)
    assert "lm_head" not in shapes and "shared" not in shapes["layer1"]["moe"]
    assert shapes["layer1"]["moe"]["experts"]["up_proj"]["kernel"].shape \
        == (8, 2048, 1792)
    mask = decay_mask(cfg, shapes)
    assert mask["['layer0']['conv']['taps']"] is False
    assert mask["['tok_embed']['embedding']"] is True
    # what this chip computes a token at S = 8192, by hand
    d, s = 2048, 8192
    conv_mixer = 2.0 * d * 3 * d + 2.0 * d * d + 2.0 * 3 * d + 4.0 * d
    attention = (4.0 * d * 32 * 64 + 4.0 * d * 8 * 64
                 + 4.0 * 32 * 64 * (s + 1) / 2)
    routed = 2.0 * d * 32 + 6.0 * d * 1792 * 4 * 8 / 32
    want = ((conv_mixer + 6.0 * d * 7168) + (attention + routed)
            + 3 * (conv_mixer + routed) + 2.0 * d * 16384)
    assert flops.fwd_flops_per_item(cfg.model, s) == pytest.approx(want)
    specs = rules_for_model("hybrid_lm").tree_specs(shapes)
    assert specs["layer0"]["conv"]["in_proj"]["kernel"] == P("fsdp", None)
    assert specs["layer0"]["conv"]["taps"] == P()
    assert specs["layer1"]["gqa"]["q_norm"]["scale"] == P()
    assert specs["tok_embed"]["embedding"] == P("fsdp", None)
