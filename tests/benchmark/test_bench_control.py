"""`correct` has been shown to fail: the lower-precision control at a size a
test can hold, and runs whose timed step is broken underneath."""

import json
import os
import sys

import pytest
from bench_helpers import BENCH, rehearse

sys.path.insert(0, BENCH)
HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "gpt2s-1chip-b16"


def _files(config, cell_path):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        config = json.load(f)
    with open(cell_path) as f:
        cell = json.load(f)
    cell.update(cell["rehearsal"])
    return config, cell


@pytest.mark.parametrize("config,cell_path", [
    ("gpt2_small", os.path.join(BENCH, "workloads", CELL + ".json")),
    ("resnet50_imagenet", os.path.join(HERE, "resnet50_cell.json")),
], ids=["gpt2_small", "resnet50_imagenet"])
def test_the_fp8_control_comes_out_not_correct(config, cell_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import control

    for r in control.control(*_files(config, cell_path), [21]):
        assert r["correct"] is False, r
        failing = [n["name"] for n in r["numbers"]
                   if n["limit"] is not None and n["value"] > n["limit"]]
        assert any(name.startswith("first_grad_worst") for name in failing)


def _broken(monkeypatch, wrap):
    """Puts ``wrap(inner step) -> step`` under the timed path of the next
    rehearsal: the harness drives everything but its look for a chip."""
    from pytorch_distributed_train_tpu import trainer as trainer_mod

    class Broken(trainer_mod.Trainer):
        def __init__(self, cfg, mesh=None):
            super().__init__(cfg, mesh)
            self.train_step = wrap(self.train_step)

    monkeypatch.setattr(trainer_mod, "Trainer", Broken)


def _compared(lines):
    return {ln["compared"]: ln for ln in lines if "compared" in ln}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capfd, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(inner):
        def step(state, batch, rng):  # the loss is real, the update lost
            _, metrics = inner(jax.tree.map(jnp.copy, state), batch, rng)
            return state, metrics
        return step

    _broken(monkeypatch, wrap)
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    assert _compared(lines)["param_change_worst_leaf"]["ok"] is False
    assert _compared(lines)["first_grad_worst_matrix_leaf"]["ok"] is False


def test_a_step_that_leaves_out_half_the_batch_is_not_correct(
        capfd, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(inner):
        def step(state, batch, rng):  # the second half never reaches the loss
            half = jax.tree.map(
                lambda x: jnp.concatenate([x[:x.shape[0] // 2]] * 2), batch)
            return inner(state, half, rng)
        return step

    _broken(monkeypatch, wrap)
    last, lines = rehearse(capfd, monkeypatch, CELL)
    assert last["correct"] is False
    # the loss at seeded weights is the number held against this fault
    assert _compared(lines)["loss_gap_step1"]["ok"] is False


def test_an_update_the_guard_skipped_counts_as_failed(capfd, monkeypatch):
    def wrap(inner):
        def step(state, batch, rng):
            state, metrics = inner(state, batch, rng)
            return state, {**metrics, "update_skipped": 1.0}
        return step

    _broken(monkeypatch, wrap)
    last, _ = rehearse(capfd, monkeypatch, CELL)
    assert last["failed"] == last["attempted"] > 0
    assert last["correct"] is False
