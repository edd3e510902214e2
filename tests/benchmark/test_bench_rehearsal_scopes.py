"""The runner's tiny CPU rehearsal of the decoder cell, traced, prints the
entries that read the program's map of its compiled step, as files and
manifest entries only (run.py is as it was); against a program that maps
nothing (the parent commit's) it leaves them out and fails nothing. On the
CPU the host's XLA events stand in for a device's and only some of their
names are instructions of the step: the path is exercised, the numbers mean
nothing."""

import math

import pytest
from bench_helpers import RESULT_KEYS, load_run, rehearse

CELL = "gpt2s-1chip-b16"
PHASES = ("step_forward_ms.tokens", "step_backward_ms.tokens",
          "step_head_loss_ms.tokens", "step_optimizer_ms.tokens",
          "step_unattributed_ms.tokens")
COMPONENTS = ("step_attention_ms.tokens", "step_ffn_ms.tokens",
              "step_norm_ms.tokens")
# listed for other cells: remat, the expert layers, the KDA core, four chips
ELSEWHERE = ("step_recompute_ms.tokens", "step_experts_ms.tokens",
             "kda_chunk_ms_per_step", "step_grad_reduce_ms.tokens")


@pytest.mark.parametrize("maps", [True, False])
def test_traced_rehearsal_prints_the_map_entries_or_leaves_them_out(
        capfd, monkeypatch, maps):
    from pytorch_distributed_train_tpu import trainer as trainer_mod
    from pytorch_distributed_train_tpu.obs import step_program

    step_program.clear()
    if not maps:  # as the parent commit: no map, no span
        monkeypatch.setattr(trainer_mod.Trainer, "_map_step_program",
                            lambda self, batch, step: None)
    last, _lines = rehearse(capfd, monkeypatch, CELL, trace=1,
                            run=load_run())
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True
    got = last["metrics"]
    # the metrics that were there are still there
    assert {"step_device_ms.tokens", "setup_step_compile_s"} <= set(got)
    assert not set(ELSEWHERE) & set(got)
    mine = set(PHASES) | set(COMPONENTS) | {"setup_program_map_s"}
    if not maps:
        assert step_program.latest() is None
        assert not mine & set(got)
        return
    assert mine <= set(got)
    assert step_program.latest().module == "jit_train_step"
    for name in PHASES + COMPONENTS:
        assert got[name]["unit"] == "ms"
        assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0
    assert got["setup_program_map_s"]["unit"] == "s"
    assert 0 < got["setup_program_map_s"]["value"] < got[
        "setup_step_compile_s"]["value"]
    # something of the step joined the map by name, in more than one phase
    assert sum(got[name]["value"] > 0 for name in PHASES[:4]) >= 2
    assert sum(got[n]["value"] for n in COMPONENTS) <= sum(
        got[n]["value"] for n in PHASES[:2]) + 1e-9
