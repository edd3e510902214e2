"""models/hybrid.py with Mellum2's layers (window and full grouped-query
attention at 8 query heads a KV head with an RMSNorm over each head's q and
k and no output gate, the window layers turned by plain rope and the full
ones by YaRN over the WHOLE head with positions past the original length, a
64-wide softmax router whose eight chosen scores are renormalised, NO shared
expert, no dense layer, an untied head) and the preset
``mellum2_12b_a2_5b_lm_ep4``, against the plain reference the benchmark keeps
(benchmark/references/mellum2_12b_a2_5b_lm_ep4.py, which imports nothing of
the program) on seeded weights at tiny sizes; the shares tied to the model:
the four expert shares' routed sums add up to the uncut reference's layer;
and two faults that must show: a window layer without its window, the
rotation's tables swapped between the layer kinds."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lm_family import PLAIN, decay_mask, family, flat, preset_tree, seeded
from lm_family import close as _close
from lm_family import exact_products  # noqa: F401 - autouse here
from lm_family import load as _load
from lm_family import (
    logits_and_gradients_match_the_reference,
    reference_logits,
    sweep_is_the_whole_models_gradient,
)
from lm_family import train_state as _state

from pytorch_distributed_train_tpu import losses, steps
from pytorch_distributed_train_tpu.models import hybrid
from pytorch_distributed_train_tpu.models.llama import (
    LlamaMLP,
    rope_frequencies,
)
from pytorch_distributed_train_tpu.ops import moe

MELLUM2 = "mellum2_12b_a2_5b_lm_ep4"
F32 = jnp.float32
YARN = dict(scaling=16.0, scaling_type="yarn", original_max_len=8192,
            beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.2772588722239782)


@pytest.fixture(scope="module")
def bench():
    """(configuration file, its Reference at the rehearsal's sizes: window,
    full, window; a window of 32 and 64 "original" positions in sequences
    of 128; 4 of 16 experts, 4 a token; the program's config at the same
    sizes)."""
    fam = family(MELLUM2)
    return fam.config, fam.ref, fam.cfg


def _gqa(m, window, rotation):
    return hybrid.GQAMixer(
        m.num_heads, m.num_kv_heads, m.head_dim, window, rotation, F32, F32,
        out_gate=m.gqa_out_gate, qk_norm=m.gqa_qk_norm,
        rms_norm_eps=m.rms_norm_eps)


# ------------------------------------------------ the rotation, by hand

def test_yarn_over_the_whole_head_against_numbers_worked_by_hand():
    """The published numbers on all 128 dims: theta 5e5, factor 16 from
    8192, beta 32 / 1. Correction dims 128 ln(8192 / (b 2 pi)) / (2 ln
    5e5): 18.08 at b = 32 (floor 18), 34.98 at b = 1 (ceiling 35). Pairs
    0-18 keep f_i = 5e5^(-i/64), pairs 35-63 take f_i / 16, between them
    the ramp (i - 18) / 17 mixes the two; at 16384 positions half of them
    lie past the 8192 the frequencies were trained on."""
    assert math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(5e5))) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(5e5))) == 35
    cos, sin = rope_frequencies(128, 4, 5e5, **YARN)
    assert cos.shape == sin.shape == (4, 64)
    af = 1.2772588722239782
    np.testing.assert_allclose(np.asarray(cos[0]), af, rtol=1e-6)
    angle = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))
    f = lambda i: 5e5 ** (-i / 64)  # noqa: E731
    ramp = 8 / 17
    for pair, want in ((0, 1.0), (18, f(18)),
                       (26, f(26) / 16 * ramp + f(26) * (1 - ramp)),
                       (35, f(35) / 16), (63, f(63) / 16)):
        assert angle[pair] == pytest.approx(want, rel=2e-5), pair
    # the factor the config states is YaRN's own 0.1 ln(16) + 1
    assert af == pytest.approx(0.1 * math.log(16.0) + 1.0, rel=1e-12)
    # the sliding layers' tables: the same theta, plain, factor 1
    plain = rope_frequencies(128, 4, 5e5)
    assert float(plain[0][1, 26]) == pytest.approx(math.cos(f(26)), rel=1e-6)
    np.testing.assert_allclose(np.asarray(plain[0][0]), 1.0)


def test_the_programs_tables_are_the_references_past_the_original_length(
        bench):
    """Both kinds' cos and sin at the rehearsal's 128 positions, half of
    them past its 64 "original" ones: the program's ``Rotation.tables``
    against the reference's ``_angles``; the two kinds differ (the ramp and
    the factor), which the swapped-tables test below leans on."""
    _, ref, cfg = bench
    model = family(MELLUM2).model
    tables = {}
    for window, rotation in ((True, model.window_rotation),
                             (False, model.full_rotation)):
        assert rotation.width == cfg.model.head_dim == 16
        cos, sin = rotation.tables(128)
        want_cos, want_sin = ref._angles(window, 128)
        _close(cos, want_cos[:, 0], tol=1e-5)
        _close(sin, want_sin[:, 0], tol=1e-5)
        tables[window] = np.asarray(cos)
    assert model.full_rotation.original_max_len == 64
    assert np.abs(tables[True][100] - tables[False][100]).max() > 0.2


# ------------------------------------------- the mixer, both kinds

@pytest.mark.parametrize("kind", ["gqa_window", "gqa_full"])
def test_mixer_with_head_norms_and_no_gate_matches_the_reference(bench, kind):
    """One mixer of each kind against the reference's ``_mix``: four leaves
    and two head norms, no gate leaf; the window (32) is shorter than the
    sequence (128) and the positions run past the 64 original ones."""
    _, ref, cfg = bench
    m = cfg.model
    model = family(MELLUM2).model
    i, name, window, rotation = (0, "swa", m.attention_window,
                                 model.window_rotation) \
        if kind == "gqa_window" else (1, "gqa", 0, model.full_rotation)
    p = ref.init_variables(13)["params"][f"layer{i}"][name]
    p = {k: {kk: (vv if kk == "scale" else 10.0 * vv)
             for kk, vv in v.items()} for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 128, m.hidden_size))
    mixer = _gqa(m, window, rotation)
    made = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), x)["params"])
    assert sorted(made) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj",
                            "v_proj"]
    got = jax.jit(mixer.apply)({"params": p}, x)
    want = jnp.stack([jax.jit(lambda r: ref._mix(i, p, r, PLAIN))(row)
                      for row in x])
    _close(got, want, tol=5e-5)


# -------------------------------------------- the shares tied to the model

def test_the_four_expert_shares_add_up_to_the_uncut_references_layer(bench):
    """One whole block of the UNCUT reference (all 64 router outputs held)
    from the program's four shares of 16 (ids 0-15, 16-31, 32-47, 48-63):
    attention (every head on every chip), then the shares' routed sums;
    there is no shared expert to count once. One share alone is not the
    layer."""
    config, mod = _load(MELLUM2)
    whole = dict(config)
    whole["rehearsal"] = {**config["rehearsal"], "router_num_experts": 64,
                          "num_experts": 64, "num_experts_per_tok": 8}
    ref = mod.Reference(whole, rehearsal=True)
    _, _, cfg = bench
    m = cfg.model
    model = family(MELLUM2).model
    p = ref.init_variables(5)["params"]["layer0"]
    x = jax.random.normal(jax.random.PRNGKey(31), (1, 128, m.hidden_size))
    norm = lambda t, name: t * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(t * t, -1, keepdims=True) + m.rms_norm_eps) \
        * p[name]["scale"]
    x1 = x + jax.jit(_gqa(m, m.attention_window,
                          model.window_rotation).apply)(
        {"params": p["swa"]}, norm(x, "input_norm"))
    h = norm(x1, "post_attn_norm")
    routed, rows = 0.0, []
    for first in range(0, 64, 16):
        spec = moe.HeldExpertsSpec(
            num_experts=64, top_k=8, score="softmax", held_first=first,
            held=16, capacity_factor=4.0, shared_mlp_dim=-1)
        share = {"router": p["moe"]["router"],
                 "experts": jax.tree.map(lambda w: w[first:first + 16],
                                         p["moe"]["experts"])}
        y, stats = jax.jit(lambda v, h, spec=spec: moe.HeldExpertsMLP(
            spec, LlamaMLP, m.moe_mlp_dim, F32, F32).apply(v, h))(
                {"params": share}, h)
        assert float(stats[2]) == 0.0  # no pair past the row bound
        routed = routed + y
        rows.append(16 * float(stats[1]))
    want, chosen = jax.jit(lambda r: ref._layer(0, p, r, PLAIN))(x[0])
    _close(x1 + routed, want[None], tol=5e-5)
    assert float(jnp.max(jnp.abs(x1 + y - want[None]))) > 1e-3
    # every (token, choice) pair falls on exactly one share
    assert sum(rows) == pytest.approx(128 * 8)
    assert int(jnp.sum(chosen)) == 128 * 8


# ------------------------------------------------- the whole model, the step

def test_model_logits_and_gradients_match_the_reference():
    sown, chosen = logits_and_gradients_match_the_reference(
        MELLUM2, mutable=["step_metrics"],
        chosen_shape=(3, 2, 128, 16))  # layers, rows, S, ALL outputs
    assert set(sown["step_metrics"]) == {
        "moe_rows_fullest", "moe_rows_mean", "moe_rows_over_bound",
        "moe_tile_visits_ratio", "update_invalid"}
    params, _ = seeded(MELLUM2)
    assert [sorted(set(params[f"layer{i}"]) - {"input_norm", "post_attn_norm"})
            for i in range(3)] == [["moe", "swa"], ["gqa", "moe"],
                                   ["moe", "swa"]]
    assert sorted(params["layer0"]["moe"]) == ["experts", "router"]
    assert sorted(params["layer0"]["moe"]["router"]) == ["kernel"]
    assert "lm_head" in params


def test_the_references_sweep_is_its_whole_models_gradient(bench):
    """Programs shared by the layers of one kind: two kinds here."""
    _, ref, _ = bench
    assert sorted({ref.kind(i) for i in range(ref.L)}) == ["full", "window"]
    sweep_is_the_whole_models_gradient(MELLUM2, chosen_shape=(3, 2, 64, 16))


@pytest.mark.parametrize("fault", ["no_window", "tables_swapped"])
def test_a_fault_in_the_window_or_the_rotation_is_not_the_reference(fault):
    """The sound model's logits are the reference's to 2e-5 (the test
    above; 3e-7 as read); with the window layers run WITHOUT their window
    (every earlier key) or with the two kinds' rotary tables swapped (YaRN
    on the window layers, plain rope on the full one) they stand at 2e-3,
    a hundred times that tolerance, although the seeded draw keeps a
    mixer's output small beside the table's rows (the reference's
    ``OUT_STD``): what the reference is there to catch."""
    model = family(MELLUM2).model
    params, ids = seeded(MELLUM2)
    broken = model.clone(window=ids.shape[1]) \
        if fault == "no_window" else model.clone(
            full_rotation=model.window_rotation,
            window_rotation=model.full_rotation)
    got = jax.jit(lambda p: broken.apply({"params": p}, ids, train=False))(
        params)
    want = np.asarray(reference_logits(MELLUM2, params, ids), np.float64)
    gap = np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()
    assert gap > 1e-3, gap


def test_three_steps_match_the_reference(bench):
    """Three AdamW steps from the seeded weights through the program's own
    train step against the reference's ``follow``: each loss, every leaf's
    change, and the pairs on the held experts the reference counts."""
    _, ref, cfg = bench
    model = family(MELLUM2).model
    batches = ref.make_batches(17, {"rehearsal_batch": 2, "seq_len": 128}, 3)
    want = ref.follow(17, batches)
    tx, state = _state(cfg, ref.init_variables(17)["params"])
    step = jax.jit(steps.make_train_step(
        model, losses.get_loss_fn(cfg.loss), tx))
    start = state.params
    for n, batch in enumerate(batches):
        state, metrics = step(state, batch, jax.random.PRNGKey(0))
        assert abs(float(metrics["loss"]) - want["losses"][n]) < 2e-5
        assert float(metrics["moe_rows_over_bound"]) == 0.0
        # the mean held expert's rows, a mean over the layers
        assert 4 * float(metrics["moe_rows_mean"]) == pytest.approx(
            want["held_rows"][n].mean())
    assert want["held_rows"].shape == (3, 3)
    change = flat(jax.tree.map(jnp.subtract, state.params, start))
    assert "['layer1']['gqa']['q_norm']['scale']" in want["param_change"]
    for leaf, norm in want["param_change"].items():
        got = float(jnp.sqrt(jnp.sum(change[leaf] ** 2)))
        assert got == pytest.approx(float(norm), rel=2e-3, abs=1e-9), leaf


# ------------------------------------------------------------- the preset

def test_preset_builds_its_share_counts_flops_decay_mask_and_lines(capfd):
    from pytorch_distributed_train_tpu.parallel.partition import (
        P,
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    hybrid._built_logged.clear()
    moe._moe_logged.clear()
    cfg, model, shapes, count = preset_tree(MELLUM2)
    err = capfd.readouterr().err.splitlines()
    assert next(ln for ln in err if ln.startswith("[hybrid]")) == (
        "[hybrid] layers=4 kinds=gqa_window,gqa_window,gqa_window,gqa_full "
        "heads=32,32,32,32 kv_heads=4 window=1024 dense_layers=0")
    # two held rows a token: 64 x 8 x 16 / 64 x 1.25
    assert next(ln for ln in err if ln.startswith("[moe]")) == (
        "[moe] experts=64 held=16 ids=0-15 top_k=8 groups=1/1 score=softmax "
        "tokens=64 row_bound=160 shared=none")
    # a mixer 21.23 M and 256 in its head norms, 16 experts 99.09 M and a
    # router 147,456 a layer, the table's and the head's quarter 56.62 M
    # each: 9.52 GB at 16 B a parameter
    gqa = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 128
    experts = 16 * 3 * 2304 * 896 + 2304 * 64
    assert count == 595_154_176 == (
        4 * (gqa + experts + 2 * 2304) + 2304 + 2 * 24576 * 2304)
    assert "shared" not in shapes["layer0"]["moe"]
    assert "bias" not in shapes["layer0"]["moe"]["router"]
    assert [sorted(set(shapes[f"layer{i}"]) & {"swa", "gqa"})
            for i in range(4)] == [["swa"], ["swa"], ["swa"], ["gqa"]]
    assert shapes["layer3"]["moe"]["experts"]["up_proj"]["kernel"].shape \
        == (16, 2304, 896)
    mask = decay_mask(cfg, shapes)
    assert mask["['layer3']['gqa']['q_norm']['scale']"] is False
    assert mask["['lm_head']['kernel']"] is True
    # the two kinds' rotations: the same theta over the whole head, YaRN on
    # the full kind alone, half of the cell's positions past the original
    assert model.window_rotation == hybrid.Rotation(128, 5e5)
    assert model.full_rotation == hybrid.Rotation(
        128, 5e5, 16.0, "yarn", 8192, 32.0, 1.0, 1.2772588722239782)
    assert cfg.data.seq_len == cfg.model.max_seq_len == 2 * 8192
    # the row bound at the cell's tokens: 1.25 x two rows a token, where
    # the habitual 4.0 is the worst case itself
    spec = model.moe
    assert (spec.row_bound(16384), spec.mean_rows(16384)) == (40960, 2048)
    assert dataclasses.replace(spec, capacity_factor=4.0).row_bound(16384) \
        == 8 * 16384
    # what this chip computes a token at S = 16384, by hand
    d, s = 2304, 16384
    projections = 4.0 * d * 32 * 128 + 4.0 * d * 4 * 128
    assert flops.band_pairs_per_token(s, 1024) == pytest.approx(992.03125)
    window = projections + 4.0 * 32 * 128 * 992.03125
    full = projections + 4.0 * 32 * 128 * (s + 1) / 2
    routed = 2.0 * d * 64 + 6.0 * d * 896 * 8 * 16 / 64
    want = 3 * window + full + 4 * routed + 2.0 * d * 24576
    assert flops.fwd_flops_per_item(cfg.model, s) == pytest.approx(want)
    assert want == pytest.approx(566.37184e6)
    specs = rules_for_model("hybrid_lm").tree_specs(shapes)
    assert specs["layer0"]["swa"]["q_proj"]["kernel"] \
        == P("fsdp", "tensor", None)
    assert specs["layer3"]["gqa"]["q_norm"]["scale"] == P()
    assert specs["layer0"]["moe"]["router"]["kernel"] == P()
    assert specs["layer0"]["moe"]["experts"]["down_proj"]["kernel"] \
        == P("expert", "tensor", "fsdp")
