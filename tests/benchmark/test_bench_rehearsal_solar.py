"""The head-share cell's tiny CPU rehearsal: the plain reference (KDA token
by token under the unbounded softplus gate with step sizes up to 2, NoPE
attention a head at a time with a gate a channel, a loop over the held
experts, all at the same share of heads and experts) agrees with the
trainer's model through the whole harness; a step size left in (0, 1) and
the other family's bounded decay gate read `correct` false; the fp8 control
fails; `kda_flops.py` by hand and `kda_chunk_roofline` on a planted trace;
the configuration's file against the catalog's numbers and the preset."""

import json
import os
import sys

import pytest
from bench_helpers import BENCH, RESULT_KEYS, load_run, rehearse

sys.path.insert(0, BENCH)
CELL = "solar2-1chip-ep40-tp8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config(name="solar_open2_lm_ep40_tp8"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _cell(name=CELL):
    with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
        return json.load(f)


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


def test_rehearsal_last_line_reference_agreement_metrics_and_counters(
        capfd, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert load_run().main(["--workload", CELL, "--seed", "3", "--seconds",
                            "1.0", "--trace", "1"]) == 0
    out = capfd.readouterr().out.splitlines()
    lines = [json.loads(ln) for ln in out if ln.startswith("{")]
    last = lines.pop()
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
    # the CPU's trace has no Mosaic kernel, and a share of a chip's peaks
    # is no CPU number: those readers find nothing, the line leaves their
    # metrics out and does not raise; the scopes are read on both paths
    for name in ("gqa_attn_roofline", "flash_attn_ms_per_step",
                 "kda_chunk_roofline"):
        assert name not in last["metrics"]
    for name in ("step_device_ms.tokens", "step_experts_ms.tokens",
                 "step_attention_ms.tokens", "step_recompute_ms.tokens",
                 "setup_step_compile_s"):
        assert name in last["metrics"], name
    # the two accepted KDA times list the first hybrid cell alone, and the
    # tests that hold their lists are not this PR's to edit: the cell's
    # chunk time is read through `kda_chunk_roofline` on the chip
    for name in ("kda_chunk_ms_per_step", "kda_inputs_ms_per_step"):
        assert name not in last["metrics"]
    # `kda_log_decay_min` and `kda_beta_max`, from the model's step metrics
    # through `_log_train`: in every `[train]` line of the cell's run
    logs = [ln for ln in out if ln.startswith("[train]")]
    assert logs
    fields = dict(f.split("=") for f in logs[0].split()[1:])
    assert -5.0 < float(fields["kda_log_decay_min"]) < 0.0
    assert 1.0 < float(fields["kda_beta_max"]) <= 2.0


@pytest.mark.parametrize("fault", ["beta_in_0_1", "bounded_gate"])
def test_another_familys_mixer_is_not_correct(capfd, monkeypatch, fault):
    """The mixer's two family variants that change a number everywhere: the
    step size left in (0, 1), and the decay gate the bounded one (both what
    the first hybrid preset's mixer computes). A recurrent state kept in
    bfloat16 is NOT among them: planted on the chip it read `correct` true
    (PERF.md section 2: the compared numbers are gaps of leaf NORMS, which
    zero-mean rounding moves to second order); the state's precision is held
    by tests/test_kda.py and tools/kda_chip_check.py."""
    from pytorch_distributed_train_tpu.models import hybrid

    sound = hybrid.KDAMixer
    wrong = dict(beta_scale=1.0) if fault == "beta_in_0_1" \
        else dict(gate="bounded")
    monkeypatch.setattr(hybrid, "KDAMixer",
                        lambda *a, **kw: sound(*a, **{**kw, **wrong}))
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    assert _compared(lines)["first_grad_worst_matrix_leaf"]["ok"] is False


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


# --------------------------------------- kda_flops.py and the new reader

def test_the_chunk_cores_cost_by_hand():
    import kda_flops

    assert kda_flops.CHUNK == 64
    # a token and head, forward, d 128, C 64: tables 128 x 63 + 128 x 65
    # + 128 x 65, the solve's right-hand sides 256 x 65, the state's three
    # products 6 x 128 x 128
    per_token = 8064 + 8320 + 8320 + 16640 + 98304
    c = kda_flops.kda_core_cost(1, 8192, 8, 128, 128)
    assert c["flops"] == 3 * 8192 * 8 * per_token
    # q, k, v, o in bfloat16, g and beta in float32, and their gradients
    assert c["bytes"] == 2 * 8192 * 8 * (4 * 128 * 2 + 128 * 4 + 4)
    # the new cell: three KDA layers of the 8 held heads; the first hybrid
    # cell: five of 32 heads at two sequences
    assert kda_flops.kda_layers(_config()) == (3, 8, 128)
    assert kda_flops.kda_layers(_config("ling3_flash_lm_ep64")) \
        == (5, 32, 128)
    flops, nbytes = kda_flops.layers_cost(_config(), _cell(), 1)
    assert flops == 3 * c["flops"] and nbytes == 3 * c["bytes"]
    ling = kda_flops.layers_cost(_config("ling3_flash_lm_ep64"),
                                 _cell("ling3f-1chip-ep64-s8k"), 1)
    assert ling[0] == 5 * 3 * 16384 * 32 * per_token
    # configurations without such a layer: nothing to count
    for name in ("laguna_s_lm_ep32", "gpt2_small", "ouro_2_6b_lm_l8"):
        assert kda_flops.kda_layers(_config(name)) is None
        assert kda_flops.layers_cost(_config(name), _cell(), 1) is None


def test_the_roofline_reader_on_a_planted_table_and_where_nothing_is():
    """The scope's 12 ms a step against the cell's least time: memory
    bound, 3 layers x 2 x 8192 x 8 x 1540 B at 819 GB/s = 0.739 ms."""
    reader = load_run(os.path.join(BENCH, "layer_metrics",
                                   "kda_chunk_roofline.py"),
                      "reader_kda_chunk_roofline")
    table = {"scope": {"kda_chunk": 12.0}}
    ctx = {"trace": {"steps": 2}, "config": _config(), "cell": _cell(),
           "device_kind": "TPU v5 lite", "chips": 1, "scope_table": table}
    least_ms = 1e3 * 3 * 2 * 8192 * 8 * 1540 / 819e9
    assert 0.73 < least_ms < 0.75
    assert reader.read(ctx) == pytest.approx(100 * least_ms / 12.0)
    # no map of the step (a program without one), no such scope's time, a
    # configuration without such layers, the CPU rehearsal: None, no raise
    assert reader.read({**ctx, "scope_table": None}) is None
    assert reader.read({**ctx, "scope_table": {"scope": {"kda_chunk": 0.0}}}) \
        is None
    assert reader.read({**ctx, "config": _config("laguna_s_lm_ep32")}) is None
    assert reader.read({**ctx, "device_kind": "cpu"}) is None


# ------------------------------------- the configuration's file, held

def test_the_configurations_file_holds_the_catalogs_numbers_and_the_preset():
    """Every number of the catalog row's `config` under the same key, but
    the seven cut keys, which `reduced` lists and `published` restates;
    the widths as published; the preset what the file says."""
    from pytorch_distributed_train_tpu.config import get_preset

    config = _config()
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == config["name"])
    reduced = {"num_hidden_layers", "gqa_layers", "n_routed_experts",
               "num_attention_heads", "num_key_value_heads",
               "linear_attn_config", "vocab_size"}
    assert set(entry["reduced"]) == reduced \
        == set(config["changed"]) - {"note"} == set(config["published"])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Solar-Open2-250B")
        assert entry["source"] == config["source"] == row["source_url"]
        for key, value in row["config"].items():
            want = config["published"][key] if key in reduced else config[key]
            assert want == value, key
    lin = config["linear_attn_config"]
    assert (config["hidden_size"], config["head_dim"], lin["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            lin["short_conv_kernel_size"], config["router_num_experts"]) \
        == (4096, 128, 128, 1280, 8, 4, 320)
    for key, text in config["assumed"].items():
        assert "alternative" in text, key
    assert "40 chips" in config["deployment"] \
        and "840 871 320" in config["deployment"]
    model = get_preset(config["preset"]).model
    assert list(model.layer_kinds) == config["expect_lists"]["model.layer_kinds"]
    kind = {"full_attention": "gqa_full", "linear_attention": "kda"}
    assert [kind[t] for t in config["layer_types"]] == list(model.layer_kinds)
    assert config["num_attention_heads_per_layer"] == [model.heads_held] * 4
    assert (model.heads_held, model.experts_held) \
        == (config["num_attention_heads"], config["n_routed_experts"])
    assert (model.num_heads, model.num_kv_heads, model.num_experts) == tuple(
        config["published"][k] for k in (
            "num_attention_heads", "num_key_value_heads", "n_routed_experts"))
    assert model.vocab_size * 8 == config["published"]["vocab_size"]
