"""The window/full attention cell's tiny CPU rehearsal: the plain reference
(attention a head at a time under an explicit mask, a loop over the held
experts) agrees with the trainer's model through the whole harness; a window
layer run without its window and a routed expert left out read `correct`
false; the fp8 control fails; the new readers and `gqa_flops.py` on a
planted trace; the configuration's per-layer lists against the preset."""

import json
import os
import sys

import pytest
from bench_helpers import BENCH, RESULT_KEYS, load_run, rehearse

sys.path.insert(0, BENCH)
CELL = "lagunas-1chip-ep32-w512"


def _config():
    with open(os.path.join(BENCH, "configs", "laguna_s_lm_ep32.json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


def test_rehearsal_last_line_reference_agreement_and_metrics(
        capfd, monkeypatch):
    last, lines = rehearse(capfd, monkeypatch, CELL, trace=1)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["device"]["platform"] == "cpu"   # never a device number
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    compared = _compared(lines)
    # the float32 rehearsal sits far inside every limit
    for name in ("loss_gap_step1", "loss_gap_step3",
                 "first_grad_worst_matrix_leaf",
                 "first_grad_worst_expert_leaf", "param_change_worst_leaf",
                 "update_direction_gap"):
        assert compared[name]["value"] < 0.05 * compared[name]["limit"], name
    assert compared["routing_flips_bf16_share"]["limit"] is None
    info = next(ln for ln in lines if "compile_cache" in ln)
    assert info["compile_cache"]["compiles_in_window"] == 0
    imbalance = last["metrics"]["moe_expert_imbalance.tokens"]
    assert imbalance["unit"] == "ratio" and imbalance["value"] >= 1.0
    # the CPU's trace has no Mosaic kernel: the three new readers find
    # nothing and the line leaves their metrics out, it does not raise
    for name in ("swa_attn_ms_per_step", "swa_attn_roofline",
                 "gqa_attn_roofline", "flash_attn_ms_per_step"):
        assert name not in last["metrics"]
    for name in ("step_device_ms.tokens", "device_idle_pct.tokens",
                 "input_wait_pct.tokens", "setup_step_compile_s"):
        assert name in last["metrics"]


def test_a_window_layer_run_without_its_window_is_not_correct(
        capfd, monkeypatch):
    """The window layers attend to every earlier key (S 128 against a
    window of 32 at the rehearsal's sizes): the loss and the gradients of
    the matrices move."""
    from pytorch_distributed_train_tpu.models import hybrid

    sound = hybrid.dot_product_attention
    monkeypatch.setattr(
        hybrid, "dot_product_attention",
        lambda q, k, v, window=0, **kw: sound(q, k, v, window=0, **kw))
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    assert _compared(lines)["first_grad_worst_matrix_leaf"]["ok"] is False


def test_a_step_that_drops_a_routed_expert_is_not_correct(
        capfd, monkeypatch):
    """ONE held expert's part left out in every expert layer (its weights
    zeroed before every step): the loss hardly moves, that expert's
    gradients vanish."""
    import jax
    import jax.numpy as jnp
    from pytorch_distributed_train_tpu import trainer as trainer_mod

    def without_expert_two(state):
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x.at[2].set(jnp.zeros_like(x[2]))
            if "experts" in jax.tree_util.keystr(path) else x, state.params)
        return state.replace(params=params)

    class Broken(trainer_mod.Trainer):
        def __init__(self, cfg, mesh=None):
            super().__init__(cfg, mesh)
            inner = self.train_step
            self.train_step = lambda state, batch, rng: inner(
                without_expert_two(state), batch, rng)

    monkeypatch.setattr(trainer_mod, "Trainer", Broken)
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    assert _compared(lines)["first_grad_worst_expert_leaf"]["ok"] is False


def test_the_fp8_control_comes_out_not_correct_at_the_rehearsals_size(
        monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import control

    cell = _cell()
    cell.update(cell["rehearsal"])
    for r in control.control(_config(), cell, [21]):
        assert r["correct"] is False, r
        failing = [n["name"] for n in r["numbers"]
                   if n["limit"] is not None and n["value"] > n["limit"]]
        assert any(name.startswith("first_grad_worst") for name in failing)


# --------------------------------------- gqa_flops.py and the new readers

def test_band_pairs_and_the_kernels_cost_by_hand():
    import gqa_flops

    # a window of 3 over 5 queries: 1 + 2 + 3 + 3 + 3
    assert gqa_flops.band_pairs(5, 3) == 12
    assert gqa_flops.band_pairs(5) == 15 == gqa_flops.band_pairs(5, 9)
    # the cell's window layers: 512*513/2 + 7680*512 = 4 063 488 pairs of
    # the square's 67 108 864; the full layers 8192*8193/2 = 33 558 528
    assert gqa_flops.band_pairs(8192, 512) == 4063488
    assert gqa_flops.band_pairs(8192) == 33558528
    c = gqa_flops.gqa_attention_cost(1, 72, 8, 8192, 128, 512)
    assert c["flops"] == 3 * 4 * 72 * 128 * 4063488   # 0.449 TFLOP
    assert c["bytes"] == 4 * 8192 * (72 + 8) * 128 * 2
    full = gqa_flops.gqa_attention_cost(1, 48, 8, 8192, 128)
    assert full["flops"] == 3 * 4 * 48 * 128 * 33558528  # 2.474 TFLOP


def _ctx(ops, steps=2):
    return {"trace": {"steps": steps, "device0": {"ops": ops}},
            "config": _config(), "cell": _cell(),
            "device_kind": "TPU v5 lite", "chips": 1}


def _reader(name):
    return load_run(os.path.join(BENCH, "layer_metrics", name + ".py"),
                    "reader_" + name.replace(".", "_"))


def test_the_new_readers_on_a_planted_trace():
    """Two traced steps: the window kind's 12 events a step take 30 ms a
    step, the full kind's 8 take 100 ms; other operations are not
    counted."""
    ops = {"%swa.12 custom-call": [8, 0.020], "%swa.13 custom-call": [16, 0.040],
           "%gqa.8 custom-call": [16, 0.200],
           "%ragged-dot-none.3 custom-call": [12, 0.5],
           "%fusion.7 fusion": [2, 1.0]}
    ctx = _ctx(ops)
    assert _reader("swa_attn_ms_per_step").read(ctx) == pytest.approx(30.0)
    assert _reader("flash_attn_ms_per_step").read(ctx) \
        == pytest.approx(130.0)
    # three window layers: 3 x 3*4*72*128*4063488 FLOP at 197 TFLOP/s =
    # 6.843 ms (compute-bound: their bytes take 2.46 ms) of 30 ms
    swa = _reader("swa_attn_roofline").read(ctx)
    assert swa == pytest.approx(
        100 * (3 * 3 * 4 * 72 * 128 * 4063488 / 197e12) / 0.030)
    assert 22.7 < swa < 22.9
    # two full layers: 2 x 2.474 TFLOP = 25.12 ms of 100 ms
    gqa = _reader("gqa_attn_roofline").read(ctx)
    assert gqa == pytest.approx(
        100 * (2 * 3 * 4 * 48 * 128 * 33558528 / 197e12) / 0.100)
    assert 25.0 < gqa < 25.3


def test_the_new_readers_find_nothing_where_nothing_is():
    """A trace without the kernels' events (the CPU's, or a program without
    these layers), no trace, or a configuration that names no such kernel
    and lists no layer types (every other configuration's file): None, no
    raise."""
    names = ("swa_attn_ms_per_step", "swa_attn_roofline", "gqa_attn_roofline")
    empty = _ctx({"%fusion.7 fusion": [2, 1.0]})
    no_trace = {**empty, "trace": None}
    other = _ctx({"%swa.1 custom-call": [4, 0.1], "%gqa.1 custom-call": [4, 0.1]})
    with open(os.path.join(BENCH, "configs", "ling3_flash_lm_ep64.json")) as f:
        other["config"] = json.load(f)
    unlisted = _ctx({"%swa.1 custom-call": [4, 0.1],
                     "%gqa.1 custom-call": [4, 0.1]})
    unlisted["config"] = {k: v for k, v in _config().items()
                          if k != "layer_types"}
    for name in names:
        for ctx in (empty, no_trace, other):
            assert _reader(name).read(ctx) is None, name
    for name in names[1:]:
        assert _reader(name).read(unlisted) is None, name


def test_the_configurations_lists_are_the_presets():
    """`expect` in the runner compares scalars (a JSON list never equals the
    program's tuple): the per-layer lists are held here, the program's
    against the configuration file's and against the published lists."""
    from pytorch_distributed_train_tpu.config import get_preset

    config = _config()
    model = get_preset(config["preset"]).model
    assert list(model.layer_kinds) == config["expect_lists"]["model.layer_kinds"]
    assert list(model.layer_heads) == config["expect_lists"]["model.layer_heads"]
    kind = {"full_attention": "gqa_full", "sliding_attention": "gqa_window"}
    assert [kind[t] for t in config["layer_types"]] == list(model.layer_kinds)
    assert config["num_attention_heads_per_layer"] == list(model.layer_heads)
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert model.first_dense_layers == len(config["mlp_only_layers"]) == 1
    assert set(config["gating_types"]) == {"per_head"}
    rope = config["rope_parameters"]["full_attention"]
    assert (model.rope_theta, model.rope_scaling, model.rope_beta_fast,
            model.rope_beta_slow, model.rope_original_max_len,
            model.rope_attention_factor, model.partial_rotary_factor) == (
        rope["rope_theta"], rope["factor"], rope["beta_fast"],
        rope["beta_slow"], rope["original_max_position_embeddings"],
        rope["attention_factor"], rope["partial_rotary_factor"])
    assert model.window_rope_theta \
        == config["rope_parameters"]["sliding_attention"]["rope_theta"]
