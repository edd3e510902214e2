"""The readers of the program's map of its compiled step
(benchmark/scope_readers.py) on a map and a trace made by hand, with known
answers: each of the thirteen entries' files, the seven phases as a partition
of the device's self time, an operation the map does not hold, a reducing
collective of the backward pass, a program that keeps no map, one without
the module (as before these readers), and a map of another program."""

import json
import os
import sys
import threading
import types

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import scope_readers as readers  # noqa: E402
from bench_helpers import load_run  # noqa: E402

from pytorch_distributed_train_tpu.obs import spans as spans_lib  # noqa: E402
from pytorch_distributed_train_tpu.obs import step_program  # noqa: E402

J = "jit(train_step)/"
FWD = J + "jvp(forward)/HybridLM/"
BWD = J + "transpose(jvp(forward))/HybridLM/jvp(forward)/HybridLM/checkpoint/"
STEPS = 4
# instruction -> (op_name, self seconds over the slice of STEPS steps)
PROGRAM = {
    "fusion.1": (FWD + "layer0/kda/q_proj/dot_general", 0.040),
    "kda_fwd.2": (FWD + "layer0/kda/kda_chunk/kda_fwd/pallas_call", 0.020),
    "fusion.3": (FWD + "layer0/moe/router/dot_general", 0.012),
    "fusion.4": (FWD + "layer0/mlp/up_proj/dot_general", 0.008),
    "fusion.5": (FWD + "layer0/input_norm/mul", 0.004),
    "fusion.6": (FWD + "tok_embed/jit(_take)/gather", 0.002),
    "fusion.7": (FWD + "lm_head/lm_head/dot_general", 0.030),
    "fusion.8": (J + "jvp(loss)/jit(_take)/reduce_sum", 0.010),
    "kda_bwd.9": (BWD + "layer0/kda/kda_chunk/kda_bwd/pallas_call", 0.060),
    "fusion.10": (BWD + "layer0/mlp/down_proj/dot_general", 0.016),
    "kda_fwd.11": (BWD + "rematted_computation/layer0/kda/kda_chunk/kda_fwd/"
                   "pallas_call", 0.020),
    "fusion.12": (BWD + "rematted_computation/layer0/moe/experts/mul", 0.024),
    "all-reduce.13": (BWD + "layer0/mlp/down_proj/dot_general", 0.006),
    "fusion.14": (J + "optimizer/add", 0.028),
    "fusion.15": (J + "mul", 0.002),
}
UNKNOWN_S = 0.004  # %copy.99: in the trace, not in the map
WANT_MS = {  # 1e3 * seconds / STEPS, by hand
    "step_forward_ms.tokens": 21.5,       # .040 .020 .012 .008 .004 .002
    "step_backward_ms.tokens": 19.0,      # .060 .016
    "step_recompute_ms.tokens": 11.0,     # .020 .024
    "step_head_loss_ms.tokens": 10.0,     # .030 .010
    "step_optimizer_ms.tokens": 7.0,
    "step_grad_reduce_ms.tokens": 1.5,    # the backward all-reduce
    "step_unattributed_ms.tokens": 1.5,   # `other` .002 + unknown .004
    "step_attention_ms.tokens": 35.0,     # .040 .020 .060 .020
    "step_ffn_ms.tokens": 6.0,            # .008 .016
    "step_experts_ms.tokens": 9.0,        # .012 .024
    "step_norm_ms.tokens": 1.0,
    "kda_chunk_ms_per_step": 25.0,        # .020 .060 .020
}
PHASE_METRICS = [name for name in WANT_MS
                 if name.startswith("step_") and name.split("_")[1] in (
                     "forward", "backward", "recompute", "head", "optimizer",
                     "grad", "unattributed")]


def hand_map(module="jit_train_step"):
    return step_program.ProgramMap(
        module=module, scopes={k: v[0] for k, v in PROGRAM.items()},
        mixed=frozenset({"fusion.14"}), borrowed=frozenset({"fusion.12"}),
        reduces=frozenset({"all-reduce.13"}))


def hand_ctx(step_program_name="jit_train_step(123456789)"):
    ops = {f"%{name} {name.split('.')[0]}": [STEPS, seconds]
           for name, (_, seconds) in PROGRAM.items()}
    ops["%copy.99 copy"] = [STEPS, UNKNOWN_S]
    total = sum(row[1] for row in ops.values())
    return {"trace": {"steps": STEPS, "step_program": step_program_name,
                      "window_s": total, "busy_s": total,
                      "device0": {"busy_s": total, "ops": ops}},
            "counters": {"steps": 20, "window_s": 1.0, "input_wait_s": 0.0},
            "config": {}, "cell": {}, "device_kind": "TPU v5 lite",
            "chips": 1}


@pytest.fixture
def mapped(monkeypatch):
    monkeypatch.setattr(step_program, "_LATEST", hand_map())


def read(metric, ctx):
    reader = load_run().load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric + ".py"))
    return reader.read(ctx)


@pytest.mark.parametrize("metric", sorted(WANT_MS))
def test_each_entrys_file_reads_its_part_of_the_table(mapped, metric):
    assert read(metric, hand_ctx()) == pytest.approx(WANT_MS[metric])


def test_the_phases_partition_the_devices_self_time(mapped, capfd):
    ctx = hand_ctx()
    total_ms = 1e3 * ctx["trace"]["device0"]["busy_s"] / STEPS
    assert sum(read(m, ctx) for m in PHASE_METRICS) == pytest.approx(total_ms)
    assert len(PHASE_METRICS) == 7
    table = readers.table(ctx)
    assert table["total_ms"] == pytest.approx(total_ms)
    # the components cover the three model phases and nothing else
    assert sum(table["component"].values()) == pytest.approx(
        sum(table["phase"][p] for p in readers.MODEL_PHASES))
    assert table["component"]["embed"] == pytest.approx(0.5)
    assert table["component"]["other"] == 0.0
    # one line on stderr, however many readers ask: the share joined, and
    # what sits in mixed fusions and under borrowed scopes
    err = capfd.readouterr().err
    assert err.count("[scope_readers]") == 1
    assert "7.000 ms in mixed fusions" in err
    assert "6.000 ms under a borrowed scope" in err
    joined = 100.0 * (1.0 - UNKNOWN_S / ctx["trace"]["device0"]["busy_s"])
    assert f"{joined:.2f} % of it joined" in err


def test_an_operation_the_map_does_not_hold_is_unattributed(mapped):
    ctx = hand_ctx()
    ctx["trace"]["device0"]["ops"]["%fusion.77 fusion"] = [STEPS, 0.1]
    assert read("step_unattributed_ms.tokens", ctx) == pytest.approx(
        WANT_MS["step_unattributed_ms.tokens"] + 25.0)
    assert read("step_forward_ms.tokens", hand_ctx()) == pytest.approx(21.5)


def test_no_map_no_module_and_no_trace_give_none(monkeypatch):
    monkeypatch.setattr(step_program, "_LATEST", None)
    for metric in WANT_MS:
        assert read(metric, hand_ctx()) is None
    # a program older than these readers: no such module
    monkeypatch.setattr(step_program, "_LATEST", hand_map())
    import pytorch_distributed_train_tpu.obs as obs_package

    monkeypatch.delattr(obs_package, "step_program")
    monkeypatch.setitem(sys.modules,
                        "pytorch_distributed_train_tpu.obs.step_program", None)
    assert readers.program() is None
    assert read("step_forward_ms.tokens", hand_ctx()) is None
    monkeypatch.undo()
    monkeypatch.setattr(step_program, "_LATEST", hand_map())
    ctx = hand_ctx()
    ctx["trace"]["steps"] = 0
    assert read("step_forward_ms.tokens", ctx) is None


@pytest.mark.parametrize("module,traced,same", [
    ("jit_train_step", "jit_train_step(9814351193575808478)", True),
    ("jit_train_step", "PjitFunction(train_step)", True),  # the rehearsal's
    ("jit_eval_step", "jit_train_step(9814351193575808478)", False),
    ("", "jit_train_step(1)", False),
])
def test_a_map_of_another_program_raises(monkeypatch, module, traced, same):
    monkeypatch.setattr(step_program, "_LATEST", hand_map(module))
    if same:
        assert read("step_forward_ms.tokens", hand_ctx(traced)) is not None
    else:
        with pytest.raises(RuntimeError, match="another program"):
            read("step_forward_ms.tokens", hand_ctx(traced))


def test_setup_program_map_s_reads_the_runs_span(monkeypatch):
    main = threading.main_thread().name

    def span(seq, name, dur_s, parent=None, **args):
        return types.SimpleNamespace(name=name, dur_s=dur_s, thread=main,
                                     seq=seq, parent_seq=parent, args=args)

    ring = [span(0, "train.init", 9.0), span(1, "train.iteration", 31.0),
            span(2, "train.compile", 30.0, 1),
            span(3, "train.program_map", 0.625, 1, instructions=20104)]
    rec = types.SimpleNamespace(events=lambda: ring, n=len(ring),
                                capacity=4096)
    monkeypatch.setattr(spans_lib, "get_recorder", lambda: rec)
    assert read("setup_program_map_s", hand_ctx()) == 0.625
    ring.pop()  # a program that maps nothing
    assert read("setup_program_map_s", hand_ctx()) is None


def test_the_manifests_entries_are_the_thirteen_files():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"] in WANT_MS or m["name"] == "setup_program_map_s"}
    assert len(mine) == 13
    cells = [w["name"] for w in manifest["workloads"]]
    for name, entry in mine.items():
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert set(entry["workloads"]) <= set(cells)
        if name == "setup_program_map_s":
            assert (entry["source"], entry["layer"], entry["moves"],
                    entry["unit"]) == ("program_span", "trainer loop",
                                       "setup_s", "s")
        else:
            assert (entry["source"], entry["layer"], entry["moves"],
                    entry["unit"]) == ("device_trace", "step program",
                                       "tokens_per_s_per_chip", "ms")
    assert mine["step_forward_ms.tokens"]["workloads"] == cells
    assert mine["step_grad_reduce_ms.tokens"]["workloads"] == [
        "gpt2s-dp4-b64"]
    assert mine["kda_chunk_ms_per_step"]["workloads"] == [
        "ling3f-1chip-ep64-s8k"]
