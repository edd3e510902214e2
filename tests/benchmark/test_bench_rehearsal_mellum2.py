"""The 16k window/full cell's tiny CPU rehearsal: the plain reference
(attention a head at a time under an explicit mask with a norm a head, the
full kind's YaRN past its "original" positions, a loop over the held experts
with no shared one, an untied head, AdamW) agrees with the trainer's model
through the whole harness; a window layer run without its window and a held
expert left out read `correct` false; the fp8 control fails; the new reader
on a map made by hand; `gqa_flops.layers_cost` on the configuration; the
manifest's entries; the configuration's file against the catalog's numbers
and the preset. ONE sound run a module."""

import contextlib
import io
import json
import os
import sys

import pytest
import test_bench_kda_inputs as kda
import test_bench_scope_readers as hand
from bench_helpers import BENCH, RESULT_KEYS, load_run, rehearse

from pytorch_distributed_train_tpu.obs import step_program

sys.path.insert(0, BENCH)
CELL = "mellum2-1chip-ep4-s16k"
CONFIG = "mellum2_12b_a2_5b_lm_ep4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRIC = "moe_gather_combine_ms_per_step"
EXPERT_CELLS = ["ling3f-1chip-ep64-s8k", "lagunas-1chip-ep32-w512",
                "solar2-1chip-ep40-tp8", "kanana2-1chip-ep8-s8k",
                "lfm2moe-1chip-ep4-s8k"]
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_experts", "vocab_size"}


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def _reference():
    return load_run().load_module(
        os.path.join(BENCH, "references", CONFIG + ".py"))


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """(last line, the lines before it, everything printed) of ONE traced
    rehearsal run of the cell, in a work directory of its own: under
    ``--dist load`` each worker handed a test of this module makes the
    fixture, and two runs in the cell's shared ``benchmark/.work/<cell>``
    remove and read each other's trace (``tests/benchmark/conftest.py``
    gives every TEST's run its own; a module's fixture is out of its
    reach)."""
    run = load_run()
    run.WORK = str(tmp_path_factory.mktemp("work"))
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setenv("JAX_PLATFORMS", "cpu")
        assert run.main(["--workload", CELL, "--seed", "4000000123",
                         "--seconds", "1.0", "--trace", "1"]) == 0
    text = out.getvalue().splitlines()
    lines = [json.loads(ln) for ln in text if ln.startswith("{")]
    return lines.pop(), lines, text


def test_rehearsal_last_line_and_reference_agreement(sound):
    last, lines, _ = sound
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["device"]["platform"] == "cpu"   # never a device number
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    compared = _compared(lines)
    # the float32 rehearsal sits far inside every limit
    for name in ("loss_gap_step1", "loss_gap_step3",
                 "first_grad_median_matrix_leaf",
                 "first_grad_worst_matrix_leaf",
                 "first_grad_worst_expert_leaf", "param_change_worst_leaf",
                 "update_direction_gap"):
        assert compared[name]["value"] < 0.05 * compared[name]["limit"], name
    for name in ("routing_flips_bf16_share", "held_rows_fullest_layer"):
        assert compared[name]["limit"] is None
    # the fullest layer's pairs on the held experts fit the rehearsal's
    # bound (every pair of its 256 tokens' four choices)
    rows = compared["held_rows_fullest_layer"]
    assert 0 < rows["mean"] <= rows["value"] <= 2 * 128 * 4
    info = next(ln for ln in lines if "compile_cache" in ln)
    assert info["compile_cache"]["compiles_in_window"] == 0


def test_rehearsal_reports_the_new_metric_beside_the_steps_parts(sound):
    last, _, text = sound
    assert last["metrics"][METRIC]["unit"] == "ms"
    values = {k: v["value"] for k, v in last["metrics"].items()}
    # gather and combine are a part of the expert layers
    assert 0.0 < values[METRIC] < values["step_experts_ms.tokens"]
    # the CPU's trace has no Mosaic kernel, and a share of a chip's peaks
    # is no CPU number: those readers find nothing and the line leaves
    # their metrics out
    for name in ("swa_attn_ms_per_step", "swa_attn_roofline",
                 "gqa_attn_roofline", "flash_attn_ms_per_step"):
        assert name not in last["metrics"]
    for name in ("step_device_ms.tokens", "step_recompute_ms.tokens",
                 "step_head_loss_ms.tokens", "step_optimizer_ms.tokens",
                 "moe_expert_imbalance.tokens", "setup_step_compile_s"):
        assert name in last["metrics"], name
    logs = [ln for ln in text if ln.startswith("[train]")]
    fields = dict(f.split("=") for f in logs[0].split()[1:])
    assert float(fields["moe_rows_over_bound"]) == 0.0
    assert float(fields["update_skipped"]) == 0.0


def test_a_window_layer_run_without_its_window_is_not_correct(
        capfd, monkeypatch):
    """The window layers of the PROGRAM attend to every earlier key (S 128
    against a window of 32 at the rehearsal's sizes): the gradients of the
    matrices move."""
    from pytorch_distributed_train_tpu.models import hybrid

    whole = hybrid.dot_product_attention
    monkeypatch.setattr(
        hybrid, "dot_product_attention",
        lambda q, k, v, window=0, **kw: whole(q, k, v, window=0, **kw))
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    assert _compared(lines)["first_grad_worst_matrix_leaf"]["ok"] is False


def test_a_step_that_leaves_out_a_held_expert_is_not_correct(
        capfd, monkeypatch):
    """ONE held expert's part left out in every layer (its weights zeroed
    before every step): the loss hardly moves, that expert's gradients
    vanish."""
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
        assert any(name.startswith("first_grad_") for name in failing)


# (sound runs' largest, the smallest of what the number is held against) at
# the cell's size on the chip: PERF.md section 2's table (my chip runs, PR 48)
READINGS = {
    "first_grad_median_matrix_leaf": (2.23e-4, 1.41e-3),   # the fp8 control
    "first_grad_worst_matrix_leaf": (6.61e-3, 0.319),      # no window
    "param_change_worst_leaf": (3.15e-4, 4.9e-3),          # no window
    "update_direction_gap": (5.8e-5, 1.1),                 # a flipped update
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_a_limit_of_the_cell_lies_between_its_two_readings(name):
    """Twice of room or more on both sides: a limit copied from another
    cell may stand ABOVE its upper reading, as `param_change_worst_leaf`
    did at the hybrid cells' 0.5 % (the no-window fault read 0.49 %)."""
    sound, held_against = READINGS[name]
    limit = _reference().LIMITS[name]
    assert 2 * sound <= limit <= held_against / 2, (sound, limit)


def test_the_reference_brings_its_equations_and_shares_the_rest():
    import reflayers

    mod = _reference()
    shared = ("follow", "check", "probes", "_sweep", "_functions", "_logits",
              "make_batches", "routing_flips")
    for name in shared:
        assert getattr(mod.Reference, name) \
            is getattr(reflayers.LayeredReference, name), name
    for name in ("_make", "_angles", "_mix", "_route", "_moe", "_layer"):
        assert name in vars(mod.Reference), name
    assert mod.Reference(_config(), rehearsal=True).limits is mod.LIMITS


# ------------------------------------- the new reader on a map made by hand

MOE = "layer1/moe/"
# instruction -> (op_name, self seconds over the slice of hand.STEPS steps)
ROWS = {
    "fusion.40": (hand.FWD + MOE + "held_gather/gather", 0.003),
    "fusion.41": (hand.FWD + MOE + "held_combine/scatter-add", 0.005),
    "fusion.42": (hand.BWD + "rematted_computation/" + MOE
                  + "held_gather/gather", 0.003),
    "fusion.43": (hand.BWD + MOE + "held_gather/scatter-add", 0.007),
    "fusion.44": (hand.BWD + MOE + "held_combine/gather", 0.004),
    # neighbours that are NOT the scopes: the layer's rows, a like name
    "fusion.45": (hand.FWD + MOE + "held_rows/reduce_sum", 0.100),
    "fusion.46": (hand.FWD + MOE + "held_gather_like/mul", 0.100),
}
GATHER_MS = 1e3 * (0.003 + 0.003 + 0.007) / hand.STEPS
COMBINE_MS = 1e3 * (0.005 + 0.004) / hand.STEPS


@pytest.mark.parametrize("case", [
    "sums_both_scopes", "one_scope_alone", "no_such_scope", "no_map"])
def test_moe_gather_combine_ms_per_step(monkeypatch, case):
    if case == "sums_both_scopes":
        ctx = kda._with_shaping(monkeypatch, ROWS)
        assert hand.read(METRIC, ctx) == pytest.approx(
            GATHER_MS + COMBINE_MS)
    elif case == "one_scope_alone":
        rows = {k: v for k, v in ROWS.items() if "held_combine/" not in v[0]}
        ctx = kda._with_shaping(monkeypatch, rows)
        assert hand.read(METRIC, ctx) == pytest.approx(GATHER_MS)
    elif case == "no_such_scope":  # the parent's program: nothing, no raise
        assert hand.read(METRIC, kda._with_shaping(monkeypatch, {})) is None
    else:
        monkeypatch.setattr(step_program, "_LATEST", None)
        assert hand.read(METRIC, hand.hand_ctx()) is None


def test_gqa_flops_counts_three_window_layers_and_the_full_one():
    import gqa_flops

    config, cell = _config(), _cell()
    window = gqa_flops.gqa_attention_cost(1, 32, 4, 16384, 128, 1024)
    full = gqa_flops.gqa_attention_cost(1, 32, 4, 16384, 128)
    assert gqa_flops.layers_cost(config, cell, 1, "sliding_attention") \
        == (3 * window["flops"], 3 * window["bytes"])
    assert gqa_flops.layers_cost(config, cell, 1, "full_attention") \
        == (full["flops"], full["bytes"])
    # the band's pairs: 1024 x 1025 / 2 + 15360 x 1024, a sixteenth of the
    # sequence a query; forward 4 x pairs x 128 a query head, three times
    # that with backward
    assert gqa_flops.band_pairs(16384, 1024) == 524800 + 15360 * 1024
    assert window["flops"] == 3 * 4.0 * 32 * 128 * 16253440
    assert full["flops"] == 3 * 4.0 * 32 * 128 * 16384 * 16385 / 2
    # every event of the forward and of the backward's split pair carries
    # its module's name (`%swa.N`, `%gqa.N`: tests/test_tpu_compile.py
    # compiles the calls), so the three patterns are the window/full
    # configuration's own
    with open(os.path.join(BENCH, "configs", "laguna_s_lm_ep32.json")) as f:
        laguna = json.load(f)
    for key in ("flash_kernel_pattern", "swa_kernel_pattern",
                "gqa_kernel_pattern"):
        assert config[key] == laguna[key], key


def test_the_manifest_has_the_cell_on_the_lists_named_and_no_pinned_one():
    manifest = _manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "b1-s16384", 1)
    assert "2048 rows" in cell["why"] and "sixteenth" in cell["why"]
    assert len(manifest["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    lists = {m["name"]: m.get("workloads")
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    assert [m for m in manifest["per_layer"] if m["name"] == METRIC] == [{
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step program",
        "moves": "tokens_per_s_per_chip",
        "workloads": [CELL] + EXPERT_CELLS}]
    for name in ("tokens_per_s_per_chip", "step_ms_p95",
                 "step_forward_ms.tokens", "step_device_ms.tokens",
                 "flash_attn_ms_per_step", "device_idle_pct.tokens",
                 "swa_attn_ms_per_step", "swa_attn_roofline",
                 "gqa_attn_roofline", "moe_expert_imbalance.tokens",
                 "step_experts_ms.tokens", "step_recompute_ms.tokens",
                 "step_unattributed_ms.tokens", "setup_program_map_s"):
        assert lists[name][-1] == CELL, name
    # (`moe_held_rows_ms_per_step` and `moe_grouped_ms_per_step` would find
    # something here too, but `test_bench_moe_held_rows.py` and
    # `test_bench_moe_grouped.py` hold their lists to the cells they had:
    # the next `benchmark` PR's to append, PERF.md section 7)
    for name in ("moe_held_rows_ms_per_step", "moe_grouped_ms_per_step",
                 "moe_router_load_imbalance.tokens", "mla_attn_roofline",
                 "kda_chunk_ms_per_step", "flash_attn_roofline",
                 "conv_mixer_ms_per_step", "step_grad_reduce_ms.tokens"):
        assert CELL not in lists[name], name


def test_the_configurations_file_holds_the_catalogs_numbers_and_the_preset():
    """Every key of the catalog row's `config` under the same key (the two
    rope groups whole), but the five cut keys, which `reduced` lists and
    `published` restates: the two per-layer lists hold the four layers that
    run, the first four of the published 28; the widths as published; the
    preset what the file says."""
    from pytorch_distributed_train_tpu.config import get_preset

    config, manifest = _config(), _manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == config["name"])
    assert set(entry["reduced"]) == REDUCED \
        == set(config["changed"]) - {"note"}
    assert REDUCED <= set(config["published"])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert entry["source"] == config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in ("layer_types", "mlp_layer_types"):
                assert config[key] == value[:4] and len(value) == 28
                assert value == value[:4] * 7
            elif key in REDUCED:
                assert config["published"][key] == value, key
            else:
                assert config[key] == value, key
    for key in ("qk_norm", "router", "aux_loss"):
        assert "alternative" in config["assumed"][key], key
    assert "LEFT OUT, a departure" in config["assumed"]["mtp_head"]
    assert "4 chips" in config["deployment"] \
        and "595,154,176 parameters" in config["deployment"] \
        and "9.52 GB" in config["deployment"]
    model = get_preset(config["preset"]).model
    kind = {"full_attention": "gqa_full", "sliding_attention": "gqa_window"}
    assert list(model.layer_kinds) \
        == config["expect_lists"]["model.layer_kinds"] \
        == [kind[t] for t in config["layer_types"]]
    assert config["mlp_layer_types"] == ["sparse"] * 4
    assert model.first_dense_layers == 0 and not model.layer_heads
    assert config["num_attention_heads_per_layer"] == [model.num_heads] * 4
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.mlp_dim, model.moe_mlp_dim,
            model.expert_top_k, model.num_experts, model.attention_window,
            model.num_layers, model.rms_norm_eps) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["intermediate_size"], config["moe_intermediate_size"],
        config["num_experts_per_tok"], config["router_num_experts"],
        config["sliding_window"], config["num_hidden_layers"],
        config["rms_norm_eps"])
    assert (model.experts_held, model.experts_held_first,
            model.tie_word_embeddings, model.moe_shared_mlp_dim,
            model.moe_score, model.moe_routed_scale, model.moe_bias_rate,
            model.gqa_qk_norm, model.gqa_out_gate) == (
        config["num_experts"], config["held_expert_first_id"],
        config["tie_word_embeddings"], -1, "softmax", 1.0, 0.0, True, "none")
    rope = config["rope_parameters"]["full_attention"]
    assert (model.rope_theta, model.rope_scaling, model.rope_scaling_type,
            model.rope_beta_fast, model.rope_beta_slow,
            model.rope_original_max_len, model.rope_attention_factor,
            model.partial_rotary_factor) == (
        rope["rope_theta"], rope["factor"], rope["rope_type"],
        rope["beta_fast"], rope["beta_slow"],
        rope["original_max_position_embeddings"], rope["attention_factor"],
        1.0)
    plain = config["rope_parameters"]["sliding_attention"]
    assert (model.window_rope_theta, plain["rope_type"]) \
        == (plain["rope_theta"], "default")
    # the cell's sequence is past the original positions: YaRN's divided
    # frequencies decide the scores of half of the positions
    assert _cell()["seq_len"] == model.max_seq_len \
        == 2 * rope["original_max_position_embeddings"]
    # the floors: a quarter of the vocabulary, 16 experts, a whole period
    # of four layers
    assert model.vocab_size * 4 == config["published"]["vocab_size"]
    assert model.num_experts == config["published"]["num_experts"]
    assert model.experts_held >= 8 and model.num_layers >= 4
