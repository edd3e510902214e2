"""models/hybrid.py told which HEADS it holds, its mixers' family variants
(an unbounded softplus decay gate through a low rank, step sizes up to 2,
gates a channel, grouped-query attention with no rotation) and the preset
``solar_open2_lm_ep40_tp8``, against the plain reference the benchmark keeps
(benchmark/references/solar_open2_lm_ep40_tp8.py, which imports nothing of
the program) on seeded weights at tiny sizes; and the share tied to the
model: the head shares' mixer outputs add up to the whole mixer's, and with
the expert shares (the shared expert counted once) to the uncut layer."""

import jax
import jax.numpy as jnp
import pytest
from lm_family import PLAIN, decay_mask, family, preset_tree
from lm_family import close as _close
from lm_family import exact_products  # noqa: F401 - autouse here
from lm_family import load as _load
from lm_family import logits_and_gradients_match_the_reference

from pytorch_distributed_train_tpu.config import get_preset
from pytorch_distributed_train_tpu.models import hybrid
from pytorch_distributed_train_tpu.models.llama import LlamaMLP
from pytorch_distributed_train_tpu.models.registry import build_model
from pytorch_distributed_train_tpu.ops import moe

SOLAR = "solar_open2_lm_ep40_tp8"
F32 = jnp.float32


@pytest.fixture(scope="module")
def bench():
    """(configuration file, its Reference at the rehearsal's sizes: 4 of 8
    heads, 1 of 2 KV heads, 4 of 32 experts; the program's config at the
    same sizes)."""
    fam = family(SOLAR)
    return fam.config, fam.ref, fam.cfg


@pytest.fixture(scope="module")
def uncut():
    """The same model WHOLE at the rehearsal's widths: 8 heads over 2 KV
    heads, all 32 experts held (the reference given the published counts'
    stand-ins), beside its parameters from a seed."""
    config, mod = _load(SOLAR)
    whole = dict(config)
    small = dict(config["rehearsal"])
    small.update(num_attention_heads=8, num_key_value_heads=2,
                 n_routed_experts=32,
                 linear_attn_config={**small["linear_attn_config"],
                                     "num_heads": 8})
    whole["rehearsal"] = small
    ref = mod.Reference(whole, rehearsal=True)
    return ref, ref.init_variables(5)["params"]


def _kda(m, **kw):
    return hybrid.KDAMixer(
        m.num_heads, m.head_dim, m.conv_kernel_size, m.kda_gate_lower_bound,
        m.rms_norm_eps, F32, F32, gate=m.kda_gate,
        beta_scale=m.kda_beta_scale, gate_rank=m.kda_gate_rank,
        out_gate=m.kda_out_gate, **kw)


def _gqa(m, **kw):
    return hybrid.GQAMixer(
        m.num_heads, m.num_kv_heads, m.head_dim, 0,
        hybrid.Rotation(0, m.rope_theta), F32, F32,
        out_gate=m.gqa_out_gate, **kw)


def _heads(tree, lo, hi, group=1):
    """The leaves of a whole mixer's parameters that heads lo..hi-1 hold:
    the head axis of every leaf that has one (KV leaves by ``group`` query
    heads a KV head), everything else whole."""
    def cut(path, x):
        name = jax.tree_util.keystr(path)
        if "a_down" in name or "gc_down" in name or "o_norm" in name:
            return x
        if "o_proj" in name or "A_log" in name or "dt_bias" in name:
            return x[lo:hi]
        if "beta_proj" in name:
            return x[:, lo:hi]
        if name.startswith("['k_proj']") or name.startswith("['v_proj']"):
            # KDA's have as many heads as q; attention's the KV heads
            return x[:, lo // group:max(hi // group, lo // group + 1)] \
                if group > 1 else x[:, lo:hi]
        return x[:, lo:hi]  # q/k/v/a/gc projections (D or r, H, d), conv taps
    return jax.tree_util.tree_map_with_path(cut, tree)


# ----------------------------------------------- mixers against the reference

def test_kda_layer_with_the_unbounded_gate_matches_the_reference(bench):
    """Softplus decay through a low rank, beta to 2, a channel gate, 4 held
    heads: token by token in the reference; the mixer also hands back the
    step's extremes."""
    _, ref, cfg = bench
    m = cfg.model
    p = ref.init_variables(13)["params"]["layer1"]["kda"]
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 128, m.hidden_size))
    mixer = _kda(m, heads_held=m.heads_held)
    got, stats = mixer.apply({"params": p}, x)
    _close(got, jnp.stack([ref._kda(p, x[b], PLAIN) for b in range(2)]))
    assert stats.shape == (2,) and float(stats[0]) < 0.0 \
        and 1.0 < float(stats[1]) <= 2.0  # log-decay min, beta max
    sig = lambda t: [(jax.tree_util.keystr(k), v.shape)  # noqa: E731
                     for k, v in jax.tree_util.tree_flatten_with_path(t)[0]]
    made = jax.eval_shape(lambda: mixer.init(
        {"params": jax.random.PRNGKey(0)}, x)["params"])
    assert sig(made) == sig(p)
    assert made["a_down"]["kernel"].shape == (m.hidden_size, m.kda_gate_rank)
    assert made["a_proj"]["kernel"].shape == (m.kda_gate_rank, 4, m.head_dim)


def test_the_unbounded_gates_seeded_decays_are_mild_and_spread():
    """The public fla layer's rule: exp(A_log) in (1, 16), the step
    softplus(dt_bias) log-uniform in (1e-3, 0.1)."""
    mixer = hybrid.KDAMixer(8, 128, 4, -5.0, 1e-5, F32, F32, gate="softplus")
    p = mixer.init({"params": jax.random.PRNGKey(3)},
                   jnp.zeros((1, 16, 32)))["params"]
    a, dt = jnp.exp(p["A_log"]), jax.nn.softplus(p["dt_bias"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert 1e-3 <= float(dt.min()) < 2e-3 and 0.05 < float(dt.max()) <= 0.1
    assert float(jnp.median(dt)) < 0.02   # log-uniform, not uniform


def test_nope_attention_with_a_channel_gate_matches_the_reference(bench):
    """No rotation (a ``Rotation`` of width 0 builds no tables), 4 held
    query heads on the 1 KV head the grouping gives them, a gate a
    channel."""
    _, ref, cfg = bench
    m = cfg.model
    p = ref.init_variables(15)["params"]["layer0"]["gqa"]
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 128, m.hidden_size))
    mixer = _gqa(m, heads_held=m.heads_held)
    got = mixer.apply({"params": p}, x)
    _close(got, jnp.stack([ref._gqa(p, x[b], PLAIN) for b in range(2)]))
    assert p["k_proj"]["kernel"].shape == (m.hidden_size, 1, m.head_dim)
    assert p["gc_proj"]["kernel"].shape == (m.hidden_size, 4, m.head_dim)
    text = str(jax.make_jaxpr(lambda x: mixer.apply({"params": p}, x))(x))
    assert " cos" not in text and " sin" not in text
    # the same mixer WITH a rotation differs (the tables are applied)
    rotated = hybrid.GQAMixer(m.num_heads, m.num_kv_heads, m.head_dim, 0,
                              hybrid.Rotation(m.head_dim, 1e4), F32, F32,
                              out_gate="channel", heads_held=4)
    assert float(jnp.max(jnp.abs(rotated.apply({"params": p}, x) - got))) \
        > 1e-4


@pytest.mark.parametrize("held,first,kv,why", [
    (4, 0, 1, None), (4, 4, 1, None), (2, 2, 1, None), (8, 0, 2, None),
    (0, 0, 2, None), (4, 2, 0, "straddle"), (6, 0, 0, "straddle"),
    (4, 6, 0, "held of"),
])
def test_the_kv_heads_follow_from_the_grouping_or_the_share_is_refused(
        held, first, kv, why):
    """8 query heads over 2 KV heads (groups of 4): whole groups, or heads
    of one group."""
    if why is None:
        assert hybrid.held_kv_heads(8, 2, held, first) == kv
        return
    with pytest.raises(ValueError, match=why):
        hybrid.held_heads(8, held, first)
        hybrid.held_kv_heads(8, 2, held, first)


# -------------------------------------------- the share tied to the model

def test_the_kda_head_shares_add_up_to_the_whole_mixer(bench, uncut):
    """Two tensor-parallel chips' KDA mixers (heads 0-3, heads 4-7), each
    the PROGRAM's on its own leaves, add up to the uncut REFERENCE's
    mixer of 8 heads: the partial sum the group's all-reduce would
    complete."""
    _, _, cfg = bench
    ref, params = uncut
    m = cfg.model
    p = params["layer1"]["kda"]
    x = jax.random.normal(jax.random.PRNGKey(21), (1, 128, m.hidden_size))
    parts = [_kda(m, heads_held=4, heads_held_first=lo).apply(
        {"params": _heads(p, lo, lo + 4)}, x)[0] for lo in (0, 4)]
    whole = ref._kda(p, x[0], PLAIN)[None]
    _close(parts[0] + parts[1], whole)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


def test_the_attention_head_shares_add_up_to_the_whole_mixer(bench, uncut):
    """The same for the attention layer: heads 0-3 read KV head 0, heads
    4-7 KV head 1, as the uncut grouping has them."""
    _, _, cfg = bench
    ref, params = uncut
    m = cfg.model
    p = params["layer0"]["gqa"]
    x = jax.random.normal(jax.random.PRNGKey(22), (1, 128, m.hidden_size))
    parts = [_gqa(m, heads_held=4, heads_held_first=lo).apply(
        {"params": _heads(p, lo, lo + 4, group=4)}, x) for lo in (0, 4)]
    whole = ref._gqa(p, x[0], PLAIN)[None]
    _close(parts[0] + parts[1], whole)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


@pytest.mark.parametrize("layer,kind", [(0, "gqa"), (1, "kda")])
def test_head_and_expert_shares_add_up_to_the_uncut_layer(bench, uncut,
                                                          layer, kind):
    """One whole residual block of the uncut reference (8 heads, all 32
    experts) from the program's shares: the two head shares' mixer outputs
    summed (the ``tensor`` all-reduce), then the eight expert shares'
    routed parts of the expert layer on that sum, the shared expert counted
    ONCE (the ``expert`` exchange)."""
    _, _, cfg = bench
    ref, params = uncut
    m = cfg.model
    p = params[f"layer{layer}"]
    x = jax.random.normal(jax.random.PRNGKey(31 + layer),
                          (1, 128, m.hidden_size))
    norm = lambda t, name: t * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(t * t, -1, keepdims=True) + m.rms_norm_eps) \
        * p[name]["scale"]
    h = norm(x, "input_norm")
    make = _kda if kind == "kda" else _gqa
    mixed = 0.0
    for lo in (0, 4):
        out = make(m, heads_held=4, heads_held_first=lo).apply(
            {"params": _heads(p[kind], lo, lo + 4,
                              group=4 if kind == "gqa" else 1)}, h)
        mixed = mixed + (out[0] if kind == "kda" else out)
    x1 = x + mixed
    h = norm(x1, "post_attn_norm")
    shared = LlamaMLP(m.moe_mlp_dim, F32, F32).apply(
        {"params": p["moe"]["shared"]}, h)
    routed = 0.0
    for first in range(0, 32, 4):
        spec = moe.HeldExpertsSpec(
            num_experts=32, top_k=m.expert_top_k, score="softmax",
            routed_scale=m.moe_routed_scale, held_first=first, held=4,
            capacity_factor=8.0)
        share = {"router": p["moe"]["router"], "shared": p["moe"]["shared"],
                 "experts": jax.tree.map(lambda w: w[first:first + 4],
                                         p["moe"]["experts"])}
        y, stats = moe.HeldExpertsMLP(spec, LlamaMLP, m.moe_mlp_dim, F32,
                                      F32).apply({"params": share}, h)
        assert float(stats[2]) == 0.0  # no pair past the row bound
        routed = routed + (y - shared)
    want, _ = ref._layer(layer, p, x[0], PLAIN)
    _close(x1 + routed + shared, want[None], tol=5e-5)


# ------------------------------------------------- the whole model, the preset

def test_model_logits_and_gradients_match_the_reference():
    sown, _ = logits_and_gradients_match_the_reference(
        SOLAR, mutable=["step_metrics"],
        chosen_shape=(2, 2, 128, 4))  # layers, rows, S, held experts
    sown = sown["step_metrics"]
    # both counters of the unbounded gate, beside the expert layers' rows
    assert {"kda_log_decay_min", "kda_beta_max", "moe_rows_fullest",
            "update_invalid"} <= set(sown)
    assert -5.0 < float(sown["kda_log_decay_min"]) < 0.0
    assert 1.0 < float(sown["kda_beta_max"]) <= 2.0


def test_preset_builds_its_share_counts_flops_decay_mask_and_rules(capfd):
    from pytorch_distributed_train_tpu.parallel.partition import (
        P,
        rules_for_model,
    )
    from pytorch_distributed_train_tpu.utils import flops

    hybrid._built_logged.clear()
    cfg, model, shapes, count = preset_tree(SOLAR)
    line = next(ln for ln in capfd.readouterr().err.splitlines()
                if ln.startswith("[hybrid]"))
    assert line == ("[hybrid] layers=4 kinds=gqa_full,kda,kda,kda "
                    "heads=64,64,64,64 kv_heads=8 window=0 dense_layers=0 "
                    "heads_held=8/64 kv_held=1/8")
    assert model.moe.num_experts == 320 and model.moe.n_held == 8
    assert model.first_dense_layers == 0 and model.full_rotation.width == 0
    assert count == 840_871_320  # 13.45 GB at 16 B a parameter
    assert "mlp" not in shapes["layer0"] and "moe" in shapes["layer0"]
    decay_mask(cfg, shapes)
    # what this chip computes a token, by hand: the attention layer's held
    # heads (q, o, the channel gate 3 x 2 D 8 128; k, v 2 x 2 D 128; the
    # causal pairs), three KDA layers (q, k, v, o; two gates through 128;
    # beta; the tables at the scan's chunk 32 off a TPU; the state's
    # products), four expert layers (router 320 wide, the shared expert, 8
    # x 8 / 320 routed experts a token), the head over the slice
    d, h, dh, s = 4096, 8, 128, 8192
    gqa = 6.0 * d * h * dh + 4.0 * d * dh + 4.0 * h * dh * (s + 1) / 2
    kda = (8.0 * d * h * dh + 2 * (2.0 * d * 128 + 2.0 * 128 * h * dh)
           + 2.0 * d * h + 6.0 * h * 32 * dh + 6.0 * h * dh * dh)
    expert = 6.0 * d * 1280
    moe_ = 2.0 * d * 320 + expert + expert * 8 * 8 / 320
    want = gqa + 3 * kda + 4 * moe_ + 2.0 * d * 24576
    assert flops.fwd_flops_per_item(cfg.model, s) == pytest.approx(want)
    # the other hybrid presets hold every head: their counts are the parent's
    ling = get_preset("ling3_flash_lm_ep64")
    assert 3.0e9 < flops.train_flops_per_item(ling.model, 8192) < 3.3e9
    specs = rules_for_model("hybrid_lm").tree_specs(shapes)
    kda_specs = specs["layer1"]["kda"]
    assert kda_specs["a_proj"]["kernel"] == kda_specs["gc_proj"]["kernel"] \
        == specs["layer0"]["gqa"]["gc_proj"]["kernel"] \
        == P("fsdp", "tensor", None)
    assert kda_specs["a_down"]["kernel"] == kda_specs["gc_down"]["kernel"] \
        == P()
    assert kda_specs["o_proj"]["kernel"] == P("tensor", None, "fsdp")


def test_a_latent_layer_has_no_share_of_heads_and_unknown_forms_are_refused():
    cfg = get_preset("ling3_flash_lm_ep64")
    cfg.model.heads_held = 8
    with pytest.raises(ValueError, match="heads_held"):
        build_model(cfg.model, cfg.precision)
    x = jnp.zeros((1, 16, 32))
    for kw, match in ((dict(gate="tanh"), "decay gate"),
                      (dict(out_gate="row"), "output gate")):
        with pytest.raises(ValueError, match=match):
            hybrid.KDAMixer(2, 16, 4, -5.0, 1e-5, F32, F32, **kw).init(
                {"params": jax.random.PRNGKey(0)}, x)
