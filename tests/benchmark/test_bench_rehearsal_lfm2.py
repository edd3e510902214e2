"""The short-convolution cell's tiny CPU rehearsal: the plain reference (the
convolution as the sum of three shifted products, attention a head at a time
with a norm a head, a loop over the held experts with no shared one, the tied
table's two uses, AdamW, then the selection bias's update from its own
counts) agrees with the trainer's model through the whole harness; the `C`
gate dropped from the timed step's conv mixer reads `correct` false; the fp8
control fails; the two new readers on the rehearsal's trace and on GPT-2's;
`gqa_flops.layers_cost` on the configuration; the configuration's file
against the catalog's numbers and the preset. ONE sound run a module."""

import contextlib
import io
import json
import os
import sys

import pytest
from bench_helpers import BENCH, RESULT_KEYS, load_run, rehearse

sys.path.insert(0, BENCH)
CELL = "lfm2moe-1chip-ep4-s8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("conv_mixer_ms_per_step", "short_conv_ms_per_step")
REDUCED = {"num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"}


def _config():
    with open(os.path.join(BENCH, "configs", "lfm2_8b_a1b_lm_ep4.json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
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
                 "first_grad_median_matrix_leaf",
                 "first_grad_worst_matrix_leaf",
                 "first_grad_worst_expert_leaf", "param_change_worst_leaf",
                 "update_direction_gap"):
        assert compared[name]["value"] < 0.05 * compared[name]["limit"], name
    bias = compared["router_bias_wrong_way_share"]
    assert bias["value"] == 0.0 and bias["judged"] >= 10 \
        and bias["of"] == 3 * 2 * 16
    assert "bias" not in compared["param_change_worst_leaf"]["leaf"]
    for name in ("routing_flips_bf16_share", "router_count_shift_bf16",
                 "held_rows_fullest_layer"):
        assert compared[name]["limit"] is None
    # the fullest layer's pairs on the held experts fit the rehearsal's bound
    assert compared["held_rows_fullest_layer"]["value"] <= 2 * 128 * 4
    info = next(ln for ln in lines if "compile_cache" in ln)
    assert info["compile_cache"]["compiles_in_window"] == 0


def test_rehearsal_reports_the_new_metrics_beside_the_steps_parts(sound):
    last, _, text = sound
    for name in NEW_METRICS:
        assert last["metrics"][name]["unit"] == "ms"
        assert last["metrics"][name]["value"] > 0.0
    # the chain is a part of the mixer, the mixer a part of the attention
    # component (the one the other mixers go to)
    values = {k: v["value"] for k, v in last["metrics"].items()}
    assert values["short_conv_ms_per_step"] \
        < values["conv_mixer_ms_per_step"] \
        < values["step_attention_ms.tokens"]
    # the CPU's trace has no Mosaic kernel, and a share of a chip's peaks
    # is no CPU number: those readers find nothing and the line leaves
    # their metrics out
    for name in ("gqa_attn_roofline", "flash_attn_ms_per_step"):
        assert name not in last["metrics"]
    for name in ("step_device_ms.tokens", "step_experts_ms.tokens",
                 "step_recompute_ms.tokens", "step_head_loss_ms.tokens",
                 "step_optimizer_ms.tokens", "moe_expert_imbalance.tokens",
                 "setup_step_compile_s"):
        assert name in last["metrics"], name
    logs = [ln for ln in text if ln.startswith("[train]")]
    fields = dict(f.split("=") for f in logs[0].split()[1:])
    assert float(fields["moe_load_mean"]) == 2 * 128 * 4 / 16
    assert float(fields["moe_rows_over_bound"]) == 0.0


def test_the_c_gate_dropped_from_the_timed_step_is_not_correct(
        capfd, monkeypatch):
    """The conv mixer without its output gate (y = W_out conv(B * u)) in the
    PROGRAM alone: the same tree, another function; the losses and the
    first gradient read it."""
    from pytorch_distributed_train_tpu.models import hybrid

    chain = hybrid.short_conv

    def ungated(bcu, taps, dtype):
        d = taps.shape[1]  # C := 1: in_proj's C columns go unused
        return chain(bcu.at[..., d:2 * d].set(1.0), taps, dtype)

    monkeypatch.setattr(hybrid, "short_conv", ungated)
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    failing = {n for n, ln in _compared(lines).items() if not ln["ok"]}
    assert "first_grad_worst_matrix_leaf" in failing
    assert "router_bias_wrong_way_share" not in failing


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
        # the median matrix leaf is the number that parts the precisions
        assert "first_grad_median_matrix_leaf" in failing


@pytest.fixture(scope="module")
def gpt2_trace(tmp_path_factory):
    """(the reduced trace, the program's map) of ONE traced rehearsal run of
    `gpt2s-1chip-b16`, in a work directory of its own."""
    from pytorch_distributed_train_tpu.obs import step_program

    run, seen = load_run(), {}
    run.WORK = str(tmp_path_factory.mktemp("work"))
    load = run.load_module

    def keeping(path):  # the runner loads trace_reduce by its path
        mod = load(path)
        if path.endswith("trace_reduce.py"):
            reduce_trace = mod.reduce_trace
            mod.reduce_trace = lambda *a, **kw: seen.setdefault(
                "trace", reduce_trace(*a, **kw))
        return mod

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setenv("JAX_PLATFORMS", "cpu")
        mp.setattr(run, "load_module", keeping)
        assert run.main(["--workload", "gpt2s-1chip-b16", "--seed", "3",
                         "--seconds", "1.0", "--trace", "1"]) == 0
    last = json.loads(out.getvalue().splitlines()[-1])
    assert last["correct"] is True
    return seen["trace"], step_program.latest(), last


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_readers_return_nothing_on_a_program_without_the_scope(
        gpt2_trace, monkeypatch, metric):
    """GPT-2's step has no `conv` module and no `short_conv` scope: on its
    trace and its map the readers return None, as against a program with
    no map at all (the parent's, for a metric new in this PR)."""
    from pytorch_distributed_train_tpu.obs import step_program

    trace, built, last = gpt2_trace
    assert metric not in last["metrics"] and trace["steps"]
    reader = load_run(os.path.join(BENCH, "layer_metrics", metric + ".py"),
                      "reader_" + metric)
    monkeypatch.setattr(step_program, "_LATEST", built)
    assert not any("conv" in op.split("/") for op in built.scopes.values())
    assert reader.read({"trace": trace}) is None
    monkeypatch.setattr(step_program, "_LATEST", None)
    assert reader.read({"trace": trace}) is None


def test_gqa_flops_counts_the_one_attention_layer():
    import gqa_flops

    config, cell = _config(), _cell()
    got = gqa_flops.layers_cost(config, cell, 1, "full_attention")
    one = gqa_flops.gqa_attention_cost(2, 32, 8, 8192, 64)
    assert got == (one["flops"], one["bytes"])
    # forward 4 x pairs x 64 a query head, three times that with backward
    assert one["flops"] == 3 * 4.0 * 2 * 32 * 64 * 8192 * 8193 / 2
    assert config["layer_types"].count("full_attention") == 1
    assert config["gqa_kernel_pattern"] == config["flash_kernel_pattern"]


def test_the_configurations_file_holds_the_catalogs_numbers_and_the_preset():
    """Every key of the catalog row's `config` under the same key, but the
    four cut keys, which `reduced` lists and `published` restates, and
    `layer_types`, which holds the five layers that run (layers 1-5 of the
    published list); the widths as published; the preset what the file
    says."""
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
                       if r["name"] == "LFM2-8B-A1B")
        assert entry["source"] == config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key == "layer_types":
                assert config[key] == value[1:6]
                assert config["published"][key].split(": ")[1].split(", ") \
                    == value
                continue
            want = config["published"][key] if key in REDUCED else config[key]
            assert want == value, key
    for key in ("tie_word_embeddings", "router_bias_update",
                "norm_topk_prob", "attention"):
        assert "alternative" in config["assumed"][key], key
    assert "4 chips" in config["deployment"] \
        and "507.8 M" in config["deployment"]
    model = get_preset(config["preset"]).model
    assert list(model.layer_kinds) \
        == config["expect_lists"]["model.layer_kinds"] \
        == [{"conv": "conv", "full_attention": "gqa_full"}[t]
            for t in config["layer_types"]]
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.mlp_dim, model.moe_mlp_dim,
            model.expert_top_k, model.num_experts, model.conv_kernel_size,
            model.num_layers, model.first_dense_layers) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["intermediate_size"], config["moe_intermediate_size"],
        config["num_experts_per_tok"], config["router_num_experts"],
        config["conv_L_cache"], config["num_hidden_layers"],
        config["num_dense_layers"])
    assert (model.experts_held, model.moe_bias_rate, model.rope_theta,
            model.moe_routed_scale, model.rms_norm_eps,
            model.tie_word_embeddings, model.moe_shared_mlp_dim) == (
        config["num_experts"], config["router_bias_update_rate"],
        config["rope_theta"], config["routed_scaling_factor"],
        config["norm_eps"], config["tie_word_embeddings"], -1)
    # the floors: a quarter of the vocabulary, 8 experts, a whole period
    # and four layers after the dense one
    assert model.vocab_size * 4 == config["published"]["vocab_size"]
    assert model.num_experts == config["published"]["num_experts"]
    assert model.experts_held >= 8
    assert model.num_layers - model.first_dense_layers >= 4
    # the cell is on the lists of what its readers find, alone on the new
    lists = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert lists[name] == [CELL]
    for name in ("gqa_attn_roofline", "flash_attn_ms_per_step",
                 "moe_expert_imbalance.tokens", "step_experts_ms.tokens",
                 "step_recompute_ms.tokens", "step_unattributed_ms.tokens"):
        assert CELL in lists[name], name
    # (`moe_router_load_imbalance.tokens` and `moe_held_rows_ms_per_step`
    # would find something here too, but `test_bench_rehearsal_kanana.py`
    # and `test_bench_moe_held_rows.py` hold their lists to the cells they
    # had: the next `benchmark` PR's to append, PERF.md section 7)
    for name in ("mla_attn_roofline", "kda_chunk_ms_per_step",
                 "swa_attn_ms_per_step", "step_grad_reduce_ms.tokens",
                 "moe_router_load_imbalance.tokens",
                 "moe_held_rows_ms_per_step"):
        assert CELL not in lists[name], name
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "2048 rows" in cell["why"]
