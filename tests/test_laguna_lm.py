"""models/hybrid.py's grouped-query kinds (window and full attention mixed
in one decoder, per-layer heads, a per-head gate), llama.py's YaRN tables
and partial rotation, and ops/moe.py's softmax router, against plain
``jax.numpy`` formulas, numbers worked by hand and the plain reference the
benchmark keeps (benchmark/references/laguna_s_lm_ep32.py, which imports
nothing of the program); and the other hybrid preset, whose parameter tree
and lowered step this work must not move."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lm_family import close as _close
from lm_family import exact_products  # noqa: F401 - autouse here
from lm_family import (
    decay_mask,
    family,
    load,
    logits_match_the_reference,
    preset_tree,
    sweep_is_the_whole_models_gradient,
    tree_and_lowered_step_are_the_parents,
)

from pytorch_distributed_train_tpu.config import get_preset
from pytorch_distributed_train_tpu.models import hybrid
from pytorch_distributed_train_tpu.models.llama import (
    apply_rope,
    rope_frequencies,
)
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.ops import moe

LAGUNA = "laguna_s_lm_ep32"
F32 = jnp.float32
YARN = dict(scaling=128.0, scaling_type="yarn", original_max_len=8192,
            beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.4852030263919618)


@pytest.fixture(scope="module")
def bench():
    """(configuration file, its Reference at the rehearsal's sizes, the
    program's config at the same sizes)."""
    fam = family(LAGUNA)
    return fam.config, fam.ref, fam.cfg


# ------------------------------------------------ the rotation, by hand

def test_yarn_frequencies_against_numbers_worked_by_hand():
    """The published numbers on the 64 rotated dims: theta 5e5, factor 128
    from 8192, beta 32 / 1. Correction dims 64 ln(8192 / (b 2 pi)) /
    (2 ln 5e5): 9.04 at b = 32 (floor 9), 17.49 at b = 1 (ceiling 18).
    Pairs 0-9 keep f_i = 5e5^(-i/32), pairs 18-31 take f_i / 128, between
    them the ramp (i - 9) / 9 mixes the two."""
    assert math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(5e5))) == 9
    assert math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(5e5))) == 18
    cos, sin = rope_frequencies(64, 4, 5e5, **YARN)
    assert cos.shape == sin.shape == (4, 32)
    af = 1.4852030263919618
    # position 0: cos = the attention factor, sin = 0
    np.testing.assert_allclose(np.asarray(cos[0]), af, rtol=1e-6)
    assert not np.any(np.asarray(sin[0]))
    # position 1 holds each pair's frequency: angle = inv_freq
    angle = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))
    # pair 0: f = 1 kept; pair 9: the last kept, 5e5^(-9/32) = 0.0249554
    # pair 13: ramp 4/9: f = 5e5^(-13/32) = 0.00483942;
    #   f/128 * 4/9 + f * 5/9 = 0.00270537
    # pair 18: the first divided, 5e5^(-18/32) / 128 = 4.86541e-6
    # pair 31: 5e5^(-31/32) / 128 = 2.35458e-8
    for pair, want in ((0, 1.0), (9, 0.0249554), (13, 0.00270537),
                       (18, 4.86541e-6), (31, 2.35458e-8)):
        assert angle[pair] == pytest.approx(want, rel=2e-5), pair
    # cos^2 + sin^2 is the attention factor squared at every position
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), af * af,
                               rtol=1e-5)
    # the factor the config states is YaRN's own 0.1 ln(128) + 1
    derived = rope_frequencies(64, 4, 5e5, **{**YARN, "attention_factor": 0.0})
    np.testing.assert_allclose(np.asarray(derived[0]), np.asarray(cos),
                               rtol=1e-6)
    # the recipes that were there read what they read
    plain = rope_frequencies(64, 4, 5e5)
    assert float(plain[0][1, 13]) == pytest.approx(math.cos(0.00483942),
                                                   rel=1e-6)
    with pytest.raises(ValueError, match="yarn"):
        rope_frequencies(64, 4, 5e5, 2.0, "nope")
    with pytest.raises(ValueError, match="rope_original_max_len"):
        rope_frequencies(64, 4, 5e5, 128.0, "yarn")


def test_partial_rotation_rotates_the_first_half_and_passes_the_rest():
    cos, sin = rope_frequencies(8, 6, 1e4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    y = apply_rope(x, cos, sin)
    assert y.shape == x.shape
    np.testing.assert_array_equal(np.asarray(y[..., 8:]),
                                  np.asarray(x[..., 8:]))  # the other half
    # split halves of the 8 rotated dims: pair (j, j + 4), angle t f_j
    t, j = 5, 2
    f = 1e4 ** (-2 * j / 8)
    a, b = float(x[0, t, 1, j]), float(x[0, t, 1, j + 4])
    assert float(y[0, t, 1, j]) == pytest.approx(
        a * math.cos(t * f) - b * math.sin(t * f), abs=1e-6)
    assert float(y[0, t, 1, j + 4]) == pytest.approx(
        b * math.cos(t * f) + a * math.sin(t * f), abs=1e-6)
    # a whole head still rotates whole
    whole = apply_rope(x[..., :8], cos, sin)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(y[..., :8]))


# ------------------------------------------- the mixer, both kinds

def _plain_gqa(p, x, heads, kv_heads, window, cos, sin, rotated):
    """The layer's equations with explicit loops over heads and an explicit
    mask: x (S, d) -> (S, d)."""
    S, dh = x.shape[0], p["q_proj"]["kernel"].shape[-1]
    i = jnp.arange(S)
    keep = i[:, None] >= i[None, :]
    if window:
        keep = keep & (i[:, None] - i[None, :] < window)

    def rot(t):  # (S, dh)
        a, b = t[:, :rotated // 2], t[:, rotated // 2:rotated]
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, t[:, rotated:]], -1)

    gate = jax.nn.sigmoid(x @ p["g_proj"]["kernel"])
    out = 0.0
    for h in range(heads):
        g = h // (heads // kv_heads)      # the KV head this query head reads
        q = rot(x @ p["q_proj"]["kernel"][:, h])
        k = rot(x @ p["k_proj"]["kernel"][:, g])
        v = x @ p["v_proj"]["kernel"][:, g]
        w = jax.nn.softmax(jnp.where(keep, q @ k.T / math.sqrt(dh),
                                     -jnp.inf), -1)
        out = out + (gate[:, h:h + 1] * (w @ v)) @ p["o_proj"]["kernel"][h]
    return out


@pytest.mark.parametrize("kind,heads", [("gqa_window", 72), ("gqa_full", 48)])
def test_mixer_matches_the_plain_formula_at_the_published_heads(kind, heads):
    """72 heads over 8 (nine query heads a KV head) with the window of 512
    and plain rope over the whole head; 48 over 8 (six a KV head), causal,
    YaRN on half the head. S 640 holds the window's edge."""
    S, d, dh, window = 640, 32, 16, 512 if kind == "gqa_window" else 0
    rotation = hybrid.Rotation(dh, 1e4) if window else hybrid.Rotation(
        dh // 2, 5e5, 128.0, "yarn", 8192, 32.0, 1.0, 1.4852030263919618)
    mixer = hybrid.GQAMixer(heads, 8, dh, window, rotation, F32, F32,
                            attn_impl="xla")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, d))
    p = mixer.init(jax.random.PRNGKey(2), x)["params"]
    p = jax.tree.map(lambda w: 10.0 * w, p)   # scores and gates far from flat
    assert {k: v["kernel"].shape for k, v in p.items()} == {
        "q_proj": (d, heads, dh), "k_proj": (d, 8, dh), "v_proj": (d, 8, dh),
        "g_proj": (d, heads), "o_proj": (heads, dh, d)}
    got = mixer.apply({"params": p}, x)[0]
    cos, sin = rotation.tables(S)
    want = _plain_gqa(p, x[0], heads, 8, window, cos, sin, rotation.width)
    _close(got, want, tol=1e-4)


def test_the_windows_edge_is_at_511_and_512():
    """Key j reaches query j + 511 and not query j + 512: the output's
    derivative with respect to the normed input at position j."""
    S, d, dh = 640, 16, 8
    mixer = hybrid.GQAMixer(4, 2, dh, 512, hybrid.Rotation(dh, 1e4), F32,
                            F32, attn_impl="xla")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, d))
    p = mixer.init(jax.random.PRNGKey(4), x)["params"]
    j = 37

    def reaches(i):
        sens = jax.grad(lambda x: jnp.sum(
            mixer.apply({"params": p}, x)[0, i] ** 2))(x)
        return float(jnp.max(jnp.abs(sens[0, j])))

    assert reaches(j + 511) > 1e-9
    assert reaches(j + 512) == 0.0
    assert reaches(j - 1) == 0.0   # and no query sees a later key


def test_a_query_head_reads_its_own_kv_head():
    """Query head h of 72 reads KV head h // 9: zero one KV head's values
    and exactly its nine query heads' outputs vanish."""
    S, d, dh = 16, 72, 8   # (d = the heads: o_proj can keep them apart)
    mixer = hybrid.GQAMixer(72, 8, dh, 0, hybrid.Rotation(dh, 1e4), F32, F32,
                            attn_impl="xla")
    x = jax.random.normal(jax.random.PRNGKey(5), (1, S, d))
    p = mixer.init(jax.random.PRNGKey(6), x)["params"]
    p["v_proj"]["kernel"] = p["v_proj"]["kernel"].at[:, 3].set(0.0)
    # read each head's contribution through an o_proj that keeps heads apart
    p["o_proj"]["kernel"] = jnp.zeros((72, dh, 72)).at[
        jnp.arange(72), 0, jnp.arange(72)].set(1.0)
    y = mixer.apply({"params": p}, x)[0]          # (S, 72): dim 0 of a head
    dead = np.flatnonzero(~np.any(np.asarray(y) != 0.0, axis=0))
    np.testing.assert_array_equal(dead, np.arange(27, 36))


# ------------------------------------------------------------- the router

def test_softmax_router_has_no_bias_and_matches_the_reference(bench):
    _, ref, cfg = bench
    m = cfg.model
    spec = moe.HeldExpertsSpec(
        num_experts=m.num_experts, top_k=m.expert_top_k, routed_scale=2.5,
        score="softmax", held=m.experts_held)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(k1, (96, m.hidden_size))
    router = moe._Router(m.num_experts, "softmax")
    p = router.init(k2, x)["params"]
    assert set(p) == {"kernel"}                      # no bias leaf
    p = {"kernel": 15.0 * p["kernel"]}
    scores, bias = router.apply({"params": p}, x)
    assert bias is None
    _close(jnp.sum(scores, -1), jnp.ones((96,)))
    ids, w = moe.group_limited_topk(scores, bias, spec)
    _close(jnp.sum(w, -1), jnp.full((96,), 2.5))     # over the chosen ten
    np.testing.assert_array_equal(                   # one group: plain top-k
        np.sort(np.asarray(ids), -1),
        np.sort(np.asarray(jax.lax.top_k(scores, m.expert_top_k)[1]), -1))
    dense = jnp.zeros((96, m.num_experts)).at[
        jnp.arange(96)[:, None], ids].set(w)
    _close(dense[:, :m.experts_held], ref._route(p, x))
    with pytest.raises(ValueError, match="router score"):
        moe._Router(8, "tanh").init(k2, x)


# ------------------------------------------------------ the whole model

def test_model_logits_match_the_reference_on_its_seeded_weights():
    params = logits_match_the_reference(LAGUNA)
    # the layer pattern: full with the dense FFN, window and full with experts
    assert [sorted(set(params[f"layer{i}"]) - {"input_norm", "post_attn_norm"})
            for i in range(3)] == [["gqa", "mlp"], ["moe", "swa"],
                                   ["gqa", "moe"]]
    assert "bias" not in params["layer1"]["moe"]["router"]


def test_the_references_layer_by_layer_sweep_is_the_whole_models_gradient(
        bench):
    """Programs shared by the layers of one kind: three kinds here."""
    _, ref, _ = bench
    assert len({ref.kind(i) for i in range(ref.L)}) == 3
    # routed layers, rows, S, held
    sweep_is_the_whole_models_gradient(LAGUNA, chosen_shape=(2, 2, 64, 4))


def test_preset_counts_decay_mask_flops_and_partition_rules():
    from pytorch_distributed_train_tpu.parallel.partition import (
        P,
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    cfg, _, shapes, count = preset_tree(LAGUNA)
    assert count == 811_017_216  # ISSUE 30's table: 811.0 M, 12.98 GB
    decay_mask(cfg, shapes)
    # a token trained: 3 x forward, the band's pairs only. By hand, forward:
    # full mixer 4*3072*48*128 + 4*3072*8*128 + 2*3072*48 + 4*48*128*4096.5
    #   = 189 050 880; window mixer (72 heads, 496.03 pairs a token)
    #   = 144 557 184; dense FFN 6*3072*12288; an expert layer 2*3072*256 +
    #   6*3072*1024 * (1 + 10*8/256); the head 2*3072*12544
    assert flops.band_pairs_per_token(8192, 512) == pytest.approx(496.03125)
    assert flops.band_pairs_per_token(8192) == 4096.5
    per_token = flops.train_flops_per_item(cfg.model, cfg.data.seq_len)
    by_hand = 3 * (2 * 189050880 + 3 * 144557184 + 6 * 3072 * 12288
                   + 4 * (2 * 3072 * 256 + 6 * 3072 * 1024 * (1 + 80 / 256))
                   + 2 * 3072 * 12544)
    assert per_token == pytest.approx(by_hand, rel=1e-9)
    assert 3.6e9 < per_token < 3.7e9
    specs = rules_for_model("hybrid_lm").tree_specs(shapes)
    assert specs["layer1"]["swa"]["q_proj"]["kernel"] \
        == P("fsdp", "tensor", None)
    assert specs["layer4"]["gqa"]["k_proj"]["kernel"] \
        == P("fsdp", "tensor", None)
    assert specs["layer0"]["gqa"]["o_proj"]["kernel"] \
        == P("tensor", None, "fsdp")
    assert specs["layer1"]["swa"]["g_proj"]["kernel"] == P()
    assert specs["layer1"]["moe"]["router"]["kernel"] == P()
    assert specs["layer1"]["moe"]["experts"]["down_proj"]["kernel"] \
        == P("expert", "tensor", "fsdp")


def test_layer_kinds_and_heads_are_checked_and_derived():
    cfg = get_preset("laguna_s_lm_ep32").model
    assert hybrid.layer_kinds(cfg) == (
        "gqa_full", "gqa_window", "gqa_window", "gqa_window", "gqa_full")
    assert hybrid.layer_heads(cfg) == (48, 72, 72, 72, 48)
    # the per-layer lists survive the config's JSON round trip as tuples
    from pytorch_distributed_train_tpu.config import TrainConfig

    whole = get_preset("laguna_s_lm_ep32")
    again = TrainConfig.from_dict(json.loads(whole.to_json()))
    assert again.model.layer_kinds == cfg.layer_kinds
    assert again.model.layer_heads == cfg.layer_heads
    cfg.layer_heads = ("4", "6", "4", "6", "4")   # an override's strings
    assert hybrid.layer_heads(cfg) == (4, 6, 4, 6, 4)
    cfg.layer_kinds = ("gqa_full", "conv")
    with pytest.raises(ValueError, match="layer_kinds"):
        hybrid.layer_kinds(cfg)
    cfg.layer_kinds, cfg.layer_heads = (), (48,)
    with pytest.raises(ValueError, match="layer_heads"):
        hybrid.layer_heads(cfg)
    # the other hybrid preset says "the last of each group": five then one
    ling = get_preset("ling3_flash_lm_ep64").model
    assert hybrid.layer_kinds(ling) == ("kda",) * 5 + ("mla",)
    assert hybrid.layer_heads(ling) == (32,) * 6
    # a window kind without a window is refused when the model is built
    bad = get_preset("laguna_s_lm_ep32")
    bad.model.attention_window = 0
    with pytest.raises(ValueError, match="attention_window"):
        build_model(bad.model, bad.precision)


# ------------------- the hybrid preset this work shares its decoder with

# sha256 of json.dumps([(leaf path, shape, dtype), ...]) of the preset's
# parameter tree, and of the lowered StableHLO of its train step at the
# benchmark configuration's rehearsal sizes in bfloat16, both taken on the
# PARENT of the PR that gave `hybrid.py` a kind a layer, `_Router` a score
# rule and `rope_frequencies` YaRN (PR 30; commit 89103c9). A PR that
# means to change that model's step replaces them (the lines below print
# how); one that does not and fails here has moved a cell it shares code
# with. PR 38 moved the KDA mixer's input shaping into ops/kda_inputs.py
# (under the rehearsal's small heads the same XLA chain, traced from a
# module's function instead of a closure): the step's text has the parent's
# 11353 lines, the same operations, and its private functions numbered in
# another order (30a291fc... before).
# PR 42 MEANT to change both steps: `ops/moe.py` `held_rows` finds the r-th
# set entry of the (expert, token) table from block counts and two small
# products where it made a binary search, under scope `held_rows` (the same
# integers come out; 7ee85e93... and 3f9f8ef9... before). The trees stand.
# PR 46 MEANT to change both steps again: the expert bank's products sit
# under scope `grouped_product` with the rows of no group zeroed five times
# a pass where it was six (on a TPU the kernels of ops/grouped_matmul.py and
# twice), and the layers' `stats` carry `moe_tile_visits_ratio` (c38c9dac...
# and c0fcfc54... before). The trees stand.
LING3_TREE = "d7a1ed3b5f0c92b2433b404a13d0135278b17a28b15770f912e66d9870df799d"
LING3_STEP = "09084c6b5f023141f7dfbf376f59268fb9d70da580fda6c57cc022e36372cb82"
# PR 39 told the mixers which HEADS they hold (`model.heads_held`, 0 = all)
# and gave them their families' variants as plain fields (the decay gate's
# form, beta's scale, gates a channel, no rotation); under the defaults both
# presets' trees and steps are the parent's (commit 371bc2d), this one's
# taken there the same way.
LAGUNA_TREE = "c281f4f8f2f437f91105ea2fc5bba924ed8de2327ef833c27ef858f6cdb8d43a"
LAGUNA_STEP = "20b63bbd6bd9667621d4abdb353bd9637353d6116673f1711668b466d9947822"


@pytest.mark.parametrize("preset,leaves,want_tree,want_step", [
    ("ling3_flash_lm_ep64", 132, LING3_TREE, LING3_STEP),
    ("laguna_s_lm_ep32", 69, LAGUNA_TREE, LAGUNA_STEP),
])
def test_the_other_hybrid_presets_tree_and_lowered_step_are_the_parents(
        preset, leaves, want_tree, want_step):
    """The tree at the published sizes; the step at the benchmark
    configuration's rehearsal sizes in bfloat16."""
    cfg = get_preset(preset)
    assert cfg.model.heads_held == 0 and cfg.model.kda_gate == "bounded"
    tree_and_lowered_step_are_the_parents(
        cfg, [*load(preset)[0]["rehearsal_overrides"],
              "precision.compute_dtype=bfloat16"],
        leaves, want_tree, want_step)
