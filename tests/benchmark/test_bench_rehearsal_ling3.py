"""The hybrid cell's tiny CPU rehearsal: the plain reference (token-by-token
KDA, expanded MLA, a loop over the held experts) agrees with the trainer's
model through the whole harness — loss, gradients by leaf, parameters after
three steps — the last line has the contract's keys and the new per-layer
metric; a broken step reads `correct` false, and so does an update with its
sign flipped, by the window's loss trend; the fp8 control fails."""

import json
import os
import sys

from bench_helpers import BENCH, RESULT_KEYS, rehearse

sys.path.insert(0, BENCH)
CELL = "ling3f-1chip-ep64-s8k"


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


def test_rehearsal_last_line_reference_agreement_and_new_metric(
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
    # the expert layers' counter reaches the result through the registry
    imbalance = last["metrics"]["moe_expert_imbalance.tokens"]
    assert imbalance["unit"] == "ratio" and imbalance["value"] >= 1.0
    # the CPU's trace has no Mosaic kernel: the roofline reader finds
    # nothing and the line leaves the metric out, it does not raise
    assert "mla_attn_roofline" not in last["metrics"]
    assert "flash_attn_ms_per_step" not in last["metrics"]


def test_a_step_that_drops_its_routed_experts_is_not_correct(
        capfd, monkeypatch):
    """The held experts' part left out (their weights zeroed before every
    step): the loss hardly moves, the experts' gradients vanish."""
    import jax
    import jax.numpy as jnp
    from pytorch_distributed_train_tpu import trainer as trainer_mod

    def without_experts(state):
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x)
            if "experts" in jax.tree_util.keystr(path) else x, state.params)
        return state.replace(params=params)

    class Broken(trainer_mod.Trainer):
        def __init__(self, cfg, mesh=None):
            super().__init__(cfg, mesh)
            inner = self.train_step
            self.train_step = lambda state, batch, rng: inner(
                without_experts(state), batch, rng)

    monkeypatch.setattr(trainer_mod, "Trainer", Broken)
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    assert _compared(lines)["first_grad_worst_expert_leaf"]["ok"] is False


def test_an_update_with_its_sign_flipped_is_not_correct_by_the_loss_trend(
        capfd, monkeypatch):
    """Gradient ASCENT: every norm the reference compares is the sound
    step's (a change's norm has no sign) and the first three losses agree
    to the warm-up's tiny rates. The direction of the update sees it at
    once, the window's loss trend once ascent has left the noise (a window
    of 3 s at the rehearsal's own, higher rates: some 40 tiny steps)."""
    import optax
    from pytorch_distributed_train_tpu import trainer as trainer_mod

    sound = trainer_mod.make_optimizer

    def ascending(*args, **kwargs):
        tx, schedule = sound(*args, **kwargs)
        return optax.chain(tx, optax.scale(-1.0)), schedule

    monkeypatch.setattr(trainer_mod, "make_optimizer", ascending)
    last, lines = rehearse(capfd, monkeypatch, CELL, seconds=3.0)
    compared = _compared(lines)
    assert last["correct"] is False and last["failed"] == 0
    assert compared["window_loss_last_tenth_minus_first"]["ok"] is False
    direction = compared["update_direction_gap"]
    assert direction["ok"] is False
    assert direction["program"] > 0 > direction["reference"]
    # the norms see next to nothing of it: the first gradient is the sound
    # one, and the change's worst leaf (a router, whose later gradients
    # follow the parameters the other way) stays under 2 % where a step
    # that returns its state reads 100 %
    assert all(n["ok"] for name, n in compared.items()
               if name.startswith("first_grad"))
    assert compared["param_change_worst_leaf"]["value"] < 0.02


def test_the_fp8_control_comes_out_not_correct_at_the_rehearsals_size(
        monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import control

    with open(os.path.join(BENCH, "configs", "ling3_flash_lm_ep64.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    cell.update(cell["rehearsal"])
    for r in control.control(config, cell, [21]):
        assert r["correct"] is False, r
        failing = [n["name"] for n in r["numbers"]
                   if n["limit"] is not None and n["value"] > n["limit"]]
        assert any(name.startswith("first_grad_worst") for name in failing)
