"""`moe_grouped_ms_per_step` (benchmark/layer_metrics/): its manifest entry,
and its file on a map and a trace made by hand with known answers: the
scope's two kernels in the forward, in the forward the backward runs again
and in the backward, the small operations that build the kernels' table, a
neighbour of the bank that is not the scope (the products' gather, a like
name), a program whose map names no such scope (the parent commit's, whose
products were `ragged-dot` under no scope of their own: None, and the result
line leaves the metric out), a program that keeps no map, a map of another
program; and the short-convolution cell's whole rehearsal with the kernels
of `ops/grouped_matmul.py` in the interpreter where the CPU takes
`ragged_dot`: the timed step agrees with the plain reference through the
harness, and the metric reads the scope."""

import json
import os

import pytest
import test_bench_kda_inputs as kda
import test_bench_scope_readers as hand
from bench_helpers import rehearse

from pytorch_distributed_train_tpu.obs import step_program
from pytorch_distributed_train_tpu.ops import grouped_matmul, moe

METRIC = "moe_grouped_ms_per_step"
CELLS = ["ling3f-1chip-ep64-s8k", "lagunas-1chip-ep32-w512",
         "kanana2-1chip-ep8-s8k", "lfm2moe-1chip-ep4-s8k"]
BANK = "layer1/moe/experts/"
SCOPE = BANK + "grouped_product/"
# instruction -> (op_name, self seconds over the slice of hand.STEPS steps)
ROWS = {
    "grouped_matmul_rows.40": (hand.FWD + SCOPE + "grouped_matmul_rows",
                               0.012),
    "fusion.41": (hand.FWD + SCOPE + "cumsum", 0.001),  # the grid's table
    "grouped_matmul_rows.42": (hand.BWD + "rematted_computation/" + SCOPE
                               + "grouped_matmul_rows", 0.012),
    "grouped_matmul_rows.43": (hand.BWD + SCOPE + "grouped_matmul_rows",
                               0.010),
    "grouped_matmul_weights.44": (hand.BWD + SCOPE
                                  + "grouped_matmul_weights", 0.020),
    # neighbours that are NOT the scope: the bank's own elementwise pass,
    # the layer's gather, a like name
    "fusion.45": (hand.FWD + BANK + "mul", 0.100),
    "fusion.46": (hand.FWD + "layer1/moe/gather", 0.100),
    "fusion.47": (hand.FWD + BANK + "grouped_product_like/mul", 0.100),
}
WANT_MS = 1e3 * (0.012 + 0.001 + 0.012 + 0.010 + 0.020) / hand.STEPS


@pytest.mark.parametrize("case", [
    "sums_the_scope", "not_in_the_map", "no_such_scope", "no_map",
    "another_program", "manifest"])
def test_moe_grouped_ms_per_step(monkeypatch, case):
    if case == "sums_the_scope":
        ctx = kda._with_shaping(monkeypatch, ROWS)
        assert hand.read(METRIC, ctx) == pytest.approx(WANT_MS)
        # the experts' component holds the scope's time and its neighbours'
        assert hand.read("step_experts_ms.tokens", ctx) == pytest.approx(
            hand.WANT_MS["step_experts_ms.tokens"]
            + WANT_MS + 1e3 * 3 * 0.100 / hand.STEPS)
    elif case == "not_in_the_map":
        ctx = kda._with_shaping(monkeypatch, ROWS)
        ctx["trace"]["device0"]["ops"][
            "%grouped_matmul_rows.99 grouped_matmul_rows"] = [
            hand.STEPS, 1.0]  # in the trace, not in the map: nobody's
        assert hand.read(METRIC, ctx) == pytest.approx(WANT_MS)
    elif case == "no_such_scope":
        parents = {"ragged-dot-none.7": (
            hand.FWD + BANK + "ragged_dot_general", 0.030)}
        assert hand.read(METRIC,
                         kda._with_shaping(monkeypatch, parents)) is None
    elif case == "no_map":
        monkeypatch.setattr(step_program, "_LATEST", None)
        assert hand.read(METRIC, hand.hand_ctx()) is None
    elif case == "another_program":
        monkeypatch.setattr(step_program, "_LATEST",
                            hand.hand_map("jit_eval_step"))
        with pytest.raises(RuntimeError, match="another program"):
            hand.read(METRIC, hand.hand_ctx())
    else:
        with open(os.path.join(hand.ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as f:
            manifest = json.load(f)
        assert [m for m in manifest["per_layer"] if m["name"] == METRIC] == [{
            "name": METRIC, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "expert layer",
            "moves": "tokens_per_s_per_chip", "workloads": CELLS}]
        assert os.path.exists(os.path.join(
            hand.ROOT, "benchmark", "layer_metrics", METRIC + ".py"))


def test_the_rehearsal_is_correct_with_the_kernels_in_the_interpreter(
        capfd, monkeypatch):
    monkeypatch.setattr(grouped_matmul, "unsupported", lambda K, N: None)
    moe._moe_logged.clear()
    last, _ = rehearse(capfd, monkeypatch, CELLS[-1], trace=1,
                       seed=4600000031)
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"][METRIC]["value"] > 0
