"""`moe_held_rows_ms_per_step` (benchmark/layer_metrics/): its manifest
entry, and its file on a map and a trace made by hand with known answers:
the scope's instructions in the forward, in the forward the backward runs
again and in the backward (the weight gather's transpose), a neighbour that is not the scope, a program whose map names no such
scope (the parent commit's: None, and the result line leaves the metric
out), a program that keeps no map, a map of another program."""

import json
import os

import pytest
import test_bench_kda_inputs as kda
import test_bench_scope_readers as hand

from pytorch_distributed_train_tpu.obs import step_program

METRIC = "moe_held_rows_ms_per_step"
CELLS = ["ling3f-1chip-ep64-s8k", "lagunas-1chip-ep32-w512",
         "solar2-1chip-ep40-tp8", "kanana2-1chip-ep8-s8k"]
MOE = "layer1/moe/held_rows/"
# instruction -> (op_name, self seconds over the slice of hand.STEPS steps)
ROWS = {
    "fusion.30": (hand.FWD + MOE + "reduce_sum", 0.004),
    "convert_reduce_fusion.31": (hand.FWD + MOE + "dot_general", 0.002),
    "fusion.32": (hand.BWD + "rematted_computation/" + MOE + "gather", 0.006),
    "fusion.35": (hand.BWD + MOE + "scatter-add", 0.008),
    # neighbours that are NOT the scope: the layer's router, a like name
    "fusion.33": (hand.FWD + "layer1/moe/router/dot_general", 0.100),
    "fusion.34": (hand.FWD + "layer1/moe/held_rows_like/mul", 0.100),
}
WANT_MS = 1e3 * (0.004 + 0.002 + 0.006 + 0.008) / hand.STEPS


@pytest.mark.parametrize("case", [
    "sums_the_scope", "not_in_the_map", "no_such_scope", "no_map",
    "another_program", "manifest"])
def test_moe_held_rows_ms_per_step(monkeypatch, case):
    if case == "sums_the_scope":
        ctx = kda._with_shaping(monkeypatch, ROWS)
        assert hand.read(METRIC, ctx) == pytest.approx(WANT_MS)
        # the experts' component holds the scope's time as it held the search
        assert hand.read("step_experts_ms.tokens", ctx) == pytest.approx(
            hand.WANT_MS["step_experts_ms.tokens"]
            + WANT_MS + 1e3 * (0.100 + 0.100) / hand.STEPS)
    elif case == "not_in_the_map":
        ctx = kda._with_shaping(monkeypatch, ROWS)
        ctx["trace"]["device0"]["ops"]["%fusion.99 fusion"] = [
            hand.STEPS, 1.0]  # in the trace, not in the map: nobody's
        assert hand.read(METRIC, ctx) == pytest.approx(WANT_MS)
    elif case == "no_such_scope":
        assert hand.read(METRIC, kda._with_shaping(monkeypatch, {})) is None
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
