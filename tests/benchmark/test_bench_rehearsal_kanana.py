"""The all-latent cell's tiny CPU rehearsal: the plain reference (latent
attention a head at a time with the pairs (2i, 2i+1) rotated in place, a
loop over the held experts beside ONE shared SwiGLU of twice their width,
AdamW, then the selection bias's balancing update from its own counts)
agrees with the trainer's model through the whole harness, the bias leaves
judged apart; the update dropped reads `correct` false by that number; the
fp8 control fails; the new reader; the configuration's file against the
catalog's numbers and the preset. ONE sound run a module."""

import contextlib
import io
import json
import os
import sys

import pytest
from bench_helpers import BENCH, RESULT_KEYS, load_run, rehearse

sys.path.insert(0, BENCH)
CELL = "kanana2-1chip-ep8-s8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(BENCH, "configs", "kanana2_lm_ep8.json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


@pytest.fixture(scope="module")
def sound():
    """(last line, the lines before it, everything printed) of ONE traced
    rehearsal run of the cell."""
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setenv("JAX_PLATFORMS", "cpu")
        assert load_run().main(["--workload", CELL, "--seed", "4000000123",
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
                 "first_grad_worst_matrix_leaf",
                 "first_grad_worst_expert_leaf", "param_change_worst_leaf",
                 "update_direction_gap"):
        assert compared[name]["value"] < 0.05 * compared[name]["limit"], name
    # the bias leaves, judged apart: every judged entry moved the rule's way
    bias = compared["router_bias_wrong_way_share"]
    assert bias["value"] == 0.0 and bias["judged"] >= 10 \
        and bias["of"] == 3 * 2 * 16
    assert "bias" not in compared["param_change_worst_leaf"]["leaf"]
    for name in ("routing_flips_bf16_share", "router_count_shift_bf16"):
        assert compared[name]["limit"] is None
    info = next(ln for ln in lines if "compile_cache" in ln)
    assert info["compile_cache"]["compiles_in_window"] == 0


def test_rehearsal_metrics_and_the_updates_counters(sound):
    last, _, text = sound
    for name in ("moe_expert_imbalance.tokens",
                 "moe_router_load_imbalance.tokens"):
        assert last["metrics"][name]["unit"] == "ratio"
        assert last["metrics"][name]["value"] >= 1.0
    # the CPU's trace has no Mosaic kernel, and a share of a chip's peaks
    # is no CPU number: those readers find nothing, the line leaves their
    # metrics out and does not raise; the scopes are read on both paths
    for name in ("mla_attn_roofline", "flash_attn_ms_per_step"):
        assert name not in last["metrics"]
    for name in ("step_device_ms.tokens", "step_experts_ms.tokens",
                 "step_attention_ms.tokens", "step_recompute_ms.tokens",
                 "step_optimizer_ms.tokens", "setup_step_compile_s"):
        assert name in last["metrics"], name
    # the update's three step metrics, through `_log_train`: in every
    # `[train]` line of the cell's run
    logs = [ln for ln in text if ln.startswith("[train]")]
    assert logs
    fields = dict(f.split("=") for f in logs[0].split()[1:])
    assert float(fields["moe_load_mean"]) == 2 * 128 * 3 / 16
    assert float(fields["moe_load_fullest"]) > float(fields["moe_load_mean"])
    assert 0.01 < float(fields["moe_bias_abs_max"]) < 0.1


def test_the_update_dropped_is_not_correct_by_the_bias_leaves_own_number(
        capfd, monkeypatch):
    """The balancing update left out of the PROGRAM (the optimizer's step
    as it was): every judged entry reads wrong, no other number moves past
    its limit."""
    from pytorch_distributed_train_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "balance_routers",
                        lambda params, moved, load, rate: moved)
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    failing = [n for n, ln in _compared(lines).items() if not ln["ok"]]
    assert failing == ["router_bias_wrong_way_share"]
    assert _compared(lines)["router_bias_wrong_way_share"]["value"] == 1.0


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


def test_the_load_reader_on_the_registrys_gauges_and_where_nothing_is():
    from pytorch_distributed_train_tpu.obs.registry import get_registry

    reader = load_run(os.path.join(BENCH, "layer_metrics",
                                   "moe_router_load_imbalance.tokens.py"),
                      "reader_moe_router_load_imbalance")
    registry = get_registry()
    registry.gauge("train_moe_load_fullest").set(912.0)
    registry.gauge("train_moe_load_mean").set(768.0)
    assert reader.read({}) == pytest.approx(912.0 / 768.0)
    # a program that logged no such metric (every other preset): None
    registry.gauge("train_moe_load_mean").set(0.0)
    assert reader.read({}) is None


def test_the_configurations_file_holds_the_catalogs_numbers_and_the_preset():
    """Every key of the catalog row's `config` under the same key, but the
    three cut keys, which `reduced` lists and `published` restates; the
    widths as published; the preset what the file says."""
    from pytorch_distributed_train_tpu.config import get_preset

    config = _config()
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == config["name"])
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced \
        == set(config["changed"]) - {"note"} == set(config["published"])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert entry["source"] == config["source"] == row["source_url"]
        for key, value in row["config"].items():
            want = config["published"][key] if key in reduced else config[key]
            assert want == value, key
    assert "alternative" in config["assumed"]["mla_form"]
    assert "8 chips" in config["deployment"] \
        and "687.5 M" in config["deployment"]
    model = get_preset(config["preset"]).model
    assert list(model.layer_kinds) \
        == config["expect_lists"]["model.layer_kinds"] \
        == ["mla"] * config["num_hidden_layers"]
    assert (model.hidden_size, model.num_heads, model.head_dim,
            model.rope_head_dim, model.kv_lora_rank, model.mlp_dim,
            model.moe_mlp_dim, model.moe_shared_mlp_dim,
            model.expert_top_k, model.num_experts) == (
        config["hidden_size"], config["num_attention_heads"],
        config["qk_nope_head_dim"], config["qk_rope_head_dim"],
        config["kv_lora_rank"], config["intermediate_size"],
        config["moe_intermediate_size"],
        config["n_shared_experts"] * config["moe_intermediate_size"],
        config["num_experts_per_tok"], config["router_num_experts"])
    assert config["v_head_dim"] == config["qk_nope_head_dim"]
    assert (model.experts_held, model.moe_bias_rate, model.rope_theta,
            model.moe_routed_scale) == (
        config["n_routed_experts"], config["router_bias_update_rate"],
        config["rope_theta"], config["routed_scaling_factor"])
    assert model.vocab_size * 8 == config["published"]["vocab_size"]
    assert model.num_experts == config["published"]["n_routed_experts"]
    # the cell is on the kernel's lists, and alone on the new metric's
    lists = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    assert lists["moe_router_load_imbalance.tokens"] == [CELL]
    for name in ("mla_attn_roofline", "flash_attn_ms_per_step",
                 "moe_expert_imbalance.tokens", "step_experts_ms.tokens",
                 "step_recompute_ms.tokens", "step_unattributed_ms.tokens"):
        assert CELL in lists[name], name
