"""`kda_inputs_ms_per_step` (benchmark/layer_metrics/): its manifest entry,
and its file on a map and a trace made by hand with known answers. The
shared table of benchmark/scope_readers.py holds the scopes it was written
with, so the file makes its own sum by the same join; against a program
whose map names no such scope (the parent commit's) it gives None, and the
result line leaves the metric out."""

import json
import os

import pytest
import test_bench_scope_readers as hand

from pytorch_distributed_train_tpu.obs import step_program

METRIC = "kda_inputs_ms_per_step"
CELL = "ling3f-1chip-ep64-s8k"
KDA = "layer0/kda/kda_inputs/"
# instruction -> (op_name, self seconds over the slice of hand.STEPS steps)
SHAPING = {
    "kda_inputs_fwd.20": (hand.FWD + KDA + "kda_inputs_fwd/pallas_call",
                          0.008),
    "kda_inputs_fwd.21": (hand.BWD + "rematted_computation/" + KDA
                          + "kda_inputs_fwd/pallas_call", 0.008),
    "kda_inputs_bwd.22": (hand.BWD + KDA + "kda_inputs_bwd/pallas_call",
                          0.012),
    "fusion.23": (hand.BWD + KDA + "reduce_sum", 0.002),  # the partials' sum
    # XLA's chain where the kernels do not run: the same scope
    "fusion.24": (hand.FWD + "layer1/kda/kda_inputs/checkpoint/mul", 0.010),
    # a neighbour that is NOT the shaping
    "fusion.25": (hand.FWD + "layer0/kda/kda_inputs_like/mul", 0.100),
}
WANT_MS = 1e3 * (0.008 + 0.008 + 0.012 + 0.002 + 0.010) / hand.STEPS


def _with_shaping(monkeypatch, rows):
    built = hand.hand_map()
    built.scopes.update({k: v[0] for k, v in rows.items()})
    monkeypatch.setattr(step_program, "_LATEST", built)
    ctx = hand.hand_ctx()
    ctx["trace"]["device0"]["ops"].update(
        {f"%{name} {name.split('.')[0]}": [hand.STEPS, seconds]
         for name, (_, seconds) in rows.items()})
    return ctx


def test_the_file_sums_the_scopes_rows_of_a_hand_made_map(monkeypatch):
    ctx = _with_shaping(monkeypatch, SHAPING)
    assert hand.read(METRIC, ctx) == pytest.approx(WANT_MS)
    # beside the table's own entries, which read what they read
    assert hand.read("kda_chunk_ms_per_step", ctx) == pytest.approx(
        hand.WANT_MS["kda_chunk_ms_per_step"])
    # an operation the trace holds and the map does not is nobody's
    ctx["trace"]["device0"]["ops"]["%kda_inputs_fwd.99 kda_inputs_fwd"] = [
        hand.STEPS, 1.0]
    assert hand.read(METRIC, ctx) == pytest.approx(WANT_MS)


def test_a_program_without_the_scope_or_without_a_map_gives_none(
        monkeypatch):
    assert hand.read(METRIC, _with_shaping(monkeypatch, {})) is None
    monkeypatch.setattr(step_program, "_LATEST", None)
    assert hand.read(METRIC, hand.hand_ctx()) is None


def test_a_map_of_another_program_raises(monkeypatch):
    monkeypatch.setattr(step_program, "_LATEST",
                        hand.hand_map("jit_eval_step"))
    with pytest.raises(RuntimeError, match="another program"):
        hand.read(METRIC, hand.hand_ctx())


def test_the_manifests_entry_is_the_hybrid_cells_alone():
    with open(os.path.join(hand.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    assert [m for m in manifest["per_layer"] if m["name"] == METRIC] == [{
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step program",
        "moves": "tokens_per_s_per_chip", "workloads": [CELL]}]
    assert os.path.exists(os.path.join(
        hand.ROOT, "benchmark", "layer_metrics", METRIC + ".py"))
