"""The looped decoder's cell, its tiny CPU rehearsal: the plain reference
(two nested Python loops over one set of layer weights, the loss over the
exits written out) agrees with the trainer's model through the whole
harness; four planted faults read `correct` false: a pass left out, the
weights untied across passes, the entropy term dropped, one exit left out
of the loss; the fp8 control fails; `loop_flops.py` and the three new
readers on a planted trace; the configuration's file against the catalog's
row and the preset."""

import json
import os
import sys

import bench_helpers
import pytest
from bench_helpers import BENCH, RESULT_KEYS, rehearse

sys.path.insert(0, BENCH)
CELL = "ouro26b-1chip-ut4-s4k"
NEW = ("loop_attn_roofline", "exit_head_ms_per_step", "exit_head_roofline")


@pytest.fixture
def run():
    """The runner (its work directory this test's own: conftest.py), to
    plant a fault in before `rehearse` runs it."""
    return bench_helpers.load_run()


def _config():
    with open(os.path.join(BENCH, "configs", "ouro_2_6b_lm_l8.json")) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


def _run_with(run, monkeypatch, change):
    """The runner, its built config changed by ``change(cfg)`` before the
    trainer sees it: a fault planted in the PROGRAM's settings, the tree
    and the reference untouched."""
    build = run.build_config

    def changed(*args, **kw):
        cfg = build(*args, **kw)
        change(cfg)
        return cfg

    monkeypatch.setattr(run, "build_config", changed)
    return run


def test_rehearsal_last_line_reference_agreement_and_metrics(
        capfd, monkeypatch, run):
    # three seconds: the trace starts five steps into the window, and a
    # loaded box stalls a step for seconds
    last, lines = rehearse(capfd, monkeypatch, CELL, trace=1, run=run,
                           seconds=3.0)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["device"]["platform"] == "cpu"   # never a device number
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    compared = _compared(lines)
    # the float32 rehearsal sits far inside every limit
    for name in ("loss_gap_step1", "loss_gap_step3",
                 "first_grad_worst_matrix_leaf", "exit_gate_grad_gap",
                 "param_change_worst_leaf", "update_direction_gap"):
        assert compared[name]["value"] < 0.05 * compared[name]["limit"], name
    # each exit's means, from the reference, for the record
    for name in ("exit_ce_worst_gap", "exit_share_worst_gap"):
        assert compared[name]["limit"] is None
        assert len(compared[name]["reference"]) == 3       # steps followed
        assert len(compared[name]["reference"][0]) == 3    # the rehearsal's T
    info = next(ln for ln in lines if "compile_cache" in ln)
    assert info["compile_cache"]["compiles_in_window"] == 0
    # the CPU's trace has no Mosaic kernel: the new readers find nothing
    # and the line leaves their metrics out, it does not raise
    for name in NEW + ("flash_attn_ms_per_step",):
        assert name not in last["metrics"]
    for name in ("step_device_ms.tokens", "device_idle_pct.tokens",
                 "input_wait_pct.tokens", "setup_step_compile_s"):
        assert name in last["metrics"]


def test_a_pass_left_out_is_not_correct(capfd, monkeypatch, run):
    """Two passes for the rehearsal's three (at the cell's size: three for
    four): the tree is the same, the exit distribution has an exit fewer, so
    the entropy alone moves the loss."""
    def fewer(cfg):
        cfg.model.loop_steps -= 1

    last, lines = rehearse(capfd, monkeypatch, CELL,
                           run=_run_with(run, monkeypatch, fewer))
    assert last["correct"] is False
    assert _compared(lines)["loss_gap_step1"]["ok"] is False


def test_the_entropy_term_dropped_is_not_correct(capfd, monkeypatch, run):
    def no_entropy(cfg):
        cfg.model.loop_entropy_beta = 0.0

    last, lines = rehearse(capfd, monkeypatch, CELL,
                           run=_run_with(run, monkeypatch, no_entropy))
    assert last["correct"] is False
    compared = _compared(lines)
    assert compared["loss_gap_step1"]["ok"] is False
    assert compared["exit_gate_grad_gap"]["ok"] is False


def test_one_exit_left_out_of_the_loss_is_not_correct(capfd, monkeypatch, run):
    """The second exit's term dropped from the expectation (its weight
    zeroed where the loss reads the distribution)."""
    import jax.numpy as jnp
    from pytorch_distributed_train_tpu import losses

    sound = losses.exit_distribution

    def without_the_second(gates):
        p, logp = sound(gates)
        return p.at[1].set(0.0), jnp.where(
            jnp.arange(p.shape[0])[:, None, None] == 1, 0.0, logp)

    monkeypatch.setattr(losses, "exit_distribution", without_the_second)
    last, lines = rehearse(capfd, monkeypatch, CELL, run=run)
    assert last["correct"] is False
    assert _compared(lines)["loss_gap_step1"]["ok"] is False


def test_weights_untied_across_passes_are_not_correct(capfd, monkeypatch, run):
    """Every pass after the first reads a detached copy of the weights, as
    a program with a stack a pass would: the first step's loss is the tied
    one's (the copies start alike), each leaf's gradient is ONE use's and
    not the sum over the passes."""
    import flax.linen as nn
    import jax
    from pytorch_distributed_train_tpu.models import llama

    calls = {"n": 0, "inside": False}

    def interceptor(next_fun, args, kwargs, context):
        block = context.module
        if (not isinstance(block, llama.LlamaBlock)
                or context.method_name != "__call__" or calls["inside"]
                or block.is_initializing()):
            return next_fun(*args, **kwargs)
        calls["n"] += 1
        if calls["n"] <= block_count["layers"]:  # the first pass: as it is
            return next_fun(*args, **kwargs)
        calls["inside"] = True
        try:
            detached = jax.lax.stop_gradient(block.variables["params"])
            return block.clone(parent=None).apply(
                {"params": detached}, *args, **kwargs)
        finally:
            calls["inside"] = False

    block_count = {"layers": _config()["rehearsal"]["num_hidden_layers"]}
    loop = llama.LlamaForCausalLM._loop

    def untied_loop(self, make_stack, x):
        calls["n"] = 0
        with nn.intercept_methods(interceptor):
            return loop(self, make_stack, x)

    monkeypatch.setattr(llama.LlamaForCausalLM, "_loop", untied_loop)
    last, lines = rehearse(capfd, monkeypatch, CELL, run=run)
    compared = _compared(lines)
    assert compared["loss_gap_step1"]["ok"] is True  # the copies start alike
    assert last["correct"] is False
    assert compared["first_grad_worst_matrix_leaf"]["ok"] is False


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
        assert "first_grad_worst_matrix_leaf" in failing
        # the control brings each exit's means: compared for the record
        gaps = {n["name"]: n for n in r["numbers"]}
        assert gaps["exit_ce_worst_gap"]["value"] > 0.0


# ------------------------------------ loop_flops.py and the three readers

def test_the_loops_counts_by_hand():
    import loop_flops

    config, cell = _config(), _cell()
    assert loop_flops.loop_counts(config) == (4, 8)
    # 32 applications x 3 x 4 x 16 heads x 128 x 4096 * 4097 / 2 pairs
    pairs = 4096 * 4097 // 2
    flop, nbytes = loop_flops.loop_attention_cost(config, cell, 1)
    assert flop == 32 * 3 * 4 * 16 * 128 * pairs      # 6.60 TFLOP
    assert nbytes == 32 * 8 * 4096 * 16 * 128 * 2
    # four exits x two products of 2 N V C
    flop, nbytes = loop_flops.exit_head_cost(config, cell, 1)
    assert flop == 4 * 2 * 2 * 4096 * 49152 * 2048     # 6.60 TFLOP
    assert nbytes == 4 * (4096 * 49152 * 10
                          + 2 * (2 * 4096 * 2048 + 2 * 49152 * 2048)
                          + 2 * 49152 * 2048)
    with open(os.path.join(BENCH, "configs", "gpt2_small.json")) as f:
        other = json.load(f)
    assert loop_flops.loop_counts(other) is None
    assert loop_flops.loop_attention_cost(other, cell, 1) is None
    assert loop_flops.exit_head_cost(other, cell, 1) is None


def _ctx(ops, steps=2):
    return {"trace": {"steps": steps, "device0": {"ops": ops}},
            "config": _config(), "cell": _cell(),
            "device_kind": "TPU v5 lite", "chips": 1}


def _reader(name):
    return bench_helpers.load_run(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def test_the_new_readers_on_a_planted_trace():
    """Two traced steps: the flash kernel's events take 120 ms a step, the
    head's eight take 50 ms; other operations are not counted, and the
    head's kernels are not the flash pattern's."""
    ops = {"%attn.12 custom-call": [128, 0.100], "%attn.13 custom-call":
           [128, 0.140], "%lm_head_fwd.3 custom-call": [8, 0.040],
           "%lm_head_bwd.7 custom-call": [8, 0.060],
           "%fusion.7 fusion": [2, 1.0], "%custom-call.9 custom-call":
           [4, 0.3]}
    ctx = _ctx(ops)
    assert _reader("flash_attn_ms_per_step").read(ctx) \
        == pytest.approx(120.0)
    assert _reader("exit_head_ms_per_step").read(ctx) == pytest.approx(50.0)
    pairs = 4096 * 4097 / 2
    attn = _reader("loop_attn_roofline").read(ctx)
    assert attn == pytest.approx(
        100 * (32 * 3 * 4 * 16 * 128 * pairs / 197e12) / 0.120)
    assert 27.8 < attn < 28.0
    head = _reader("exit_head_roofline").read(ctx)
    assert head == pytest.approx(
        100 * (4 * 4 * 4096 * 49152 * 2048 / 197e12) / 0.050)
    assert 66.9 < head < 67.1


def test_the_new_readers_find_nothing_where_nothing_is():
    """A trace without the kernels' events (the CPU's, or the parent's
    program, whose head keeps no kernel under that name), no trace, or a
    configuration without a loop: None, no raise."""
    empty = _ctx({"%fusion.7 fusion": [2, 1.0]})
    no_trace = {**empty, "trace": None}
    other = _ctx({"%attn.1 custom-call": [4, 0.1],
                  "%lm_head_fwd.1 custom-call": [4, 0.1]})
    with open(os.path.join(BENCH, "configs", "gpt2_small.json")) as f:
        other["config"] = json.load(f)
    for name in NEW:
        for ctx in (empty, no_trace, other):
            assert _reader(name).read(ctx) is None, name


# ------------------------------------------- the configuration's own file

def test_the_configuration_is_the_catalogs_row_but_for_the_depth():
    """Every number the source's config.json gives is in the file under the
    same key; `changed` (the manifest's `reduced`) names the depth and the
    per-layer list cut with it, no width; every `assumed` item names its
    alternative; the preset is what the file says."""
    from pytorch_distributed_train_tpu.config import get_preset

    config = _config()
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == ["full_attention"] * 8
    assert config["published"]["num_hidden_layers"] == 48
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == sorted(
        k for k in config["changed"] if k != "note") \
        == ["layer_types", "num_hidden_layers"]
    assert entry["source"] == config["source"]
    for key, said in config["assumed"].items():
        if key in ("sandwich_norm", "loop_input", "exit_gate",
                   "exit_entropy_beta", "attention_bias", "rope"):
            assert "alternative" in said, key
    model = get_preset(config["preset"]).model
    assert (model.loop_steps, model.num_layers, model.loop_entropy_beta) \
        == (config["total_ut_steps"], config["num_hidden_layers"],
            config["objective"]["exit_entropy_beta"])
    assert model.hidden_size // model.num_heads == config["head_dim"]
