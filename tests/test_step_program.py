"""The program's map of its compiled step (obs/step_program.py) and the
trainer's ``train.program_map`` span: the parser on a hand-written module,
the two vocabularies on paths copied from the four cells' compiled steps,
what a tiny ``fit`` leaves behind (a map, a span beside ``train.compile``, no
compile of its own), what a replaced ``train_step`` leaves, and the four
configurations' steps at their rehearsal sizes with every scoped
instruction in a phase."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_train_tpu.obs import spans as spans_lib
from pytorch_distributed_train_tpu.obs import step_program
from pytorch_distributed_train_tpu.obs.step_program import (
    COMPONENTS,
    PHASES,
    classify,
    scope_map,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
J = "jit(train_step)/"
FWD = J + "jvp(forward)/"
BWD = J + "transpose(jvp(forward))/"
LOOP = "LlamaForCausalLM/LlamaForCausalLM._loop/while/body/closed_call/" \
    "LlamaForCausalLM.one_pass/loop_pass/"
REMAT = "HybridLM/jvp(forward)/HybridLM/checkpoint/rematted_computation/"

# An optimized module in the compiler's own spelling: a fused computation of
# two phases, one of one, a reducer, a while loop's body, the entry with
# fusions, a kernel, a collective, a rewritten ragged dot whose op_name names
# no scope, an instruction without metadata and the untimed kinds.
MODULE = f"""\
HloModule jit_train_step, is_scheduled=true, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

%fused_computation.1 (param_0.1: f32[8], param_1.2: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %param_1.2 = f32[8]{{0}} parameter(1)
  %mul.3 = f32[8]{{0}} multiply(%param_0.1, %param_1.2), metadata={{op_name="{BWD}GPT2LMHead/h0/c_fc/mul" stack_frame_id=7}}
  ROOT %add.9 = f32[8]{{0}} add(%mul.3, %param_1.2), metadata={{op_name="{J}optimizer/add" stack_frame_id=9}}
}}

%fused_computation.2 (param_0.3: f32[8]) -> f32[8] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  %constant.4 = f32[] constant(2)
  ROOT %tanh.5 = f32[8]{{0}} tanh(%param_0.3), metadata={{op_name="{FWD}GPT2LMHead/h0/tanh"}}
}}

%region_0.7 (reduce_sum.1: f32[], reduce_sum.2: f32[]) -> f32[] {{
  %reduce_sum.1 = f32[] parameter(0)
  %reduce_sum.2 = f32[] parameter(1)
  ROOT %reduce_sum.3 = f32[] add(%reduce_sum.1, %reduce_sum.2), metadata={{op_name="reduce_sum"}}
}}

%body.11 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %arg.1 = (s32[], f32[8]{{0}}) parameter(0)
  %get-tuple-element.12 = f32[8]{{0}} get-tuple-element(%arg.1), index=1
  %attn.13 = f32[8]{{0}} custom-call(%get-tuple-element.12), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}{LOOP}layer0/attn/pallas_call"}}
  ROOT %tuple.14 = (s32[], f32[8]{{0}}) tuple(%get-tuple-element.12, %attn.13)
}}

ENTRY %main.20 (Arg_0.1: f32[8], /*index=1*/Arg_1.2: f32[8]) -> f32[8] {{
  %Arg_0.1 = f32[8]{{0}} parameter(0), metadata={{op_name="state.params['w']"}}
  %Arg_1.2 = f32[8]{{0}} parameter(1)
  %fusion.2 = f32[8]{{0:T(8,128)(2,1)}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{FWD}GPT2LMHead/h0/tanh" stack_frame_id=3}}
  %while.8 = (s32[], f32[8]{{0}}) while(%tuple.0), condition=%cond.10, body=%body.11, metadata={{op_name="{FWD}LlamaForCausalLM/LlamaForCausalLM._loop/while"}}
  %copy.5 = f32[8]{{0}} copy(%fusion.2)
  %copy.15 = f32[8]{{0}} copy(%Arg_1.2)
  %bitcast.6 = f32[8]{{0}} bitcast(%copy.5)
  %ragged-dot-none.1 = f32[8]{{0}} custom-call(%get-tuple-element.99, /*index=1*/%bitcast.6, %fusion.4), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %fusion.4 = f32[8]{{0}} fusion(%Arg_1.2), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{BWD}HybridLM/jvp(forward)/HybridLM/checkpoint/layer2/moe/experts/mul"}}
  %all-reduce.7 = f32[8]{{0}} all-reduce(%fusion.4), channel_id=1, to_apply=%region_0.7, metadata={{op_name="{BWD}GPT2LMHead/h6/c_proj/dot_general" stack_frame_id=164}}
  ROOT %fusion.1 = f32[8]{{0}} fusion(%all-reduce.7, %Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{J}optimizer/add"}}
}}
"""


def test_scope_map_reads_every_computation_of_a_module():
    built = scope_map(MODULE)
    assert built.module == "jit_train_step"
    assert built.text_bytes == len(MODULE)
    # entry, fused computations, the reducer and the loop's body; names
    # without the %, untimed kinds (parameter, constant, tuple, bitcast,
    # get-tuple-element) left out even where they carry metadata
    assert set(built.scopes) == {
        "mul.3", "add.9", "tanh.5", "reduce_sum.3", "attn.13", "fusion.2",
        "while.8", "copy.5", "ragged-dot-none.1", "fusion.4",
        "all-reduce.7", "fusion.1"}
    assert built.scopes["attn.13"].endswith("layer0/attn/pallas_call")
    assert built.scopes["fusion.1"] == J + "optimizer/add"
    # copy.15 has no metadata, reads an argument and nothing reads it:
    # counted, not mapped
    assert built.instructions == len(built.scopes) + 1
    assert built.fusions == 3
    assert built.place("%copy.15 copy") is None


def test_a_fusion_over_two_phases_is_mixed_and_named_by_its_own_metadata():
    built = scope_map(MODULE)
    assert built.mixed == {"fusion.1"}  # backward and optimizer inside
    assert built.place("%fusion.1 fusion") == ("optimizer", "other", False)
    assert built.describe("%fusion.1 fusion") == "optimizer+mixed"
    assert built.describe("fusion.2") == "forward/other"
    assert built.describe("%fusion.77 fusion") == ""


def test_an_instruction_that_names_no_scope_borrows_a_neighbours():
    """The compiler's `ragged-dot-none` reads a forward fusion (through a
    copy and a bitcast that name nothing) and a backward one: it runs in
    the backward pass, in the expert layer. The copy, which has no metadata
    at all, is data on its way to that product: it waits where its reader
    runs, not where its operand was made."""
    built = scope_map(MODULE)
    assert built.borrowed == {"ragged-dot-none.1", "copy.5"}
    assert built.scopes["ragged-dot-none.1"] == built.scopes["fusion.4"]
    assert built.place("%ragged-dot-none.1 custom-call") == (
        "backward", "experts", False)
    assert classify(built.scopes["fusion.2"])[0] == "forward"
    assert built.place("%copy.5 copy") == ("backward", "experts", False)
    # the reducer's bare `reduce_sum` reads parameters: nothing to borrow
    assert built.scopes["reduce_sum.3"] == "reduce_sum"
    assert built.place("reduce_sum.3") == ("other", "other", False)


def test_a_backward_all_reduce_is_the_gradients_reduction():
    built = scope_map(MODULE)
    assert built.reduces == {"all-reduce.7"}
    assert classify(built.scopes["all-reduce.7"])[0] == "backward"
    assert built.place("%all-reduce.7 all-reduce") == (
        "grad_reduce", "other", False)
    assert built.describe("%all-reduce.7 all-reduce") == "grad_reduce"


# (path as the compiled step spells it, phase, component): copied from the
# four cells' steps compiled for a v5e (gpt2_small, the hybrid and
# window/full decoders, the looped decoder) and from the CPU's
PATHS = [
    (FWD + "GPT2LMHead/h0/attn/q_proj/dot_general", "forward", "attention"),
    (FWD + "GPT2LMHead/h3/attn/c_proj/add", "forward", "attention"),
    (FWD + "GPT2LMHead/h11/c_fc/dot_general", "forward", "ffn"),
    (BWD + "GPT2LMHead/h1/c_proj/transpose", "backward", "ffn"),
    (BWD + "GPT2LMHead/h1/ln_2/reduce_sum", "backward", "norm"),
    (FWD + "GPT2LMHead/ln_f/mul", "forward", "norm"),
    (FWD + "GPT2LMHead/wte/jit(_take)/gather", "forward", "embed"),
    (FWD + "GPT2LMHead/h7/tanh", "forward", "other"),
    (J + "jvp(loss)/lm_head/lm_head_fwd/pallas_call", "head_loss", "other"),
    (J + "transpose(jvp(loss))/lm_head/lm_head_bwd/pallas_call",
     "head_loss", "other"),
    (J + "jvp(loss)/jit(_take)/jit(_where)/select_n", "head_loss", "other"),
    (FWD + "HybridLM/lm_head/lm_head/dot_general", "head_loss", "other"),
    (J + "optimizer/jit(clip)/min", "optimizer", "other"),
    (J + "optimizer/jit(_where)/select_n", "optimizer", "other"),
    (J + "grad_reduce/psum", "grad_reduce", "other"),
    (FWD + "HybridLM/layer0/kda/kda_chunk/kda_fwd/pallas_call",
     "forward", "attention"),
    (FWD + "HybridLM/layer5/mla/kv_norm/rsqrt", "forward", "attention"),
    (FWD + "HybridLM/layer1/moe/router/dot_general", "forward", "experts"),
    (FWD + "HybridLM/layer0/mlp/jit(silu)/logistic", "forward", "ffn"),
    (BWD + "HybridLM/jvp(forward)/HybridLM/checkpoint/layer2/swa/o_proj/"
     "dot_general", "backward", "attention"),
    (BWD + REMAT + "layer3/kda/kda_chunk/kda_fwd/pallas_call",
     "recompute", "attention"),
    (BWD + REMAT + "layer1/moe/shared/up_proj/dot_general",
     "recompute", "experts"),
    (BWD + "HybridLM/jvp(forward)/HybridLM/checkpoint/layer4/kda/checkpoint/"
     "rematted_computation/mul", "recompute", "attention"),
    (BWD + REMAT + "layer4/post_attn_norm/mul", "recompute", "norm"),
    (BWD + "HybridLM/tok_embed/jit(_take)/scatter-add", "backward", "embed"),
    # the short-convolution decoder: `conv` is a mixer like the others, its
    # chain's scope beneath it; the tied head is the head's phase whichever
    # table it reads
    (FWD + "HybridLM/layer2/conv/in_proj/dot_general", "forward",
     "attention"),
    (BWD + REMAT + "layer4/conv/short_conv/mul", "recompute", "attention"),
    (BWD + "HybridLM/jvp(forward)/HybridLM/checkpoint/layer0/conv/short_conv/"
     "pad", "backward", "attention"),
    (FWD + "HybridLM/layer1/gqa/q_norm/rsqrt", "forward", "attention"),
    (FWD + "HybridLM/lm_head/dot_general", "head_loss", "other"),
    (BWD + "HybridLM/lm_head/transpose", "head_loss", "other"),
    (BWD + "HybridLM/layer1/gqa/k_norm/mul", "backward", "attention"),
    # the expert layer's gather and combine under scopes of their own, as
    # `held_rows` and `grouped_product` are: the experts' component in every
    # phase (the gather's transpose is a scatter-add, the combine's a gather)
    (FWD + "HybridLM/layer0/moe/held_gather/gather", "forward", "experts"),
    (FWD + "HybridLM/layer0/moe/held_combine/scatter-add", "forward",
     "experts"),
    (BWD + REMAT + "layer2/moe/held_gather/gather", "recompute", "experts"),
    (BWD + "HybridLM/jvp(forward)/HybridLM/checkpoint/layer2/moe/"
     "held_gather/scatter-add", "backward", "experts"),
    (BWD + "HybridLM/jvp(forward)/HybridLM/checkpoint/layer3/moe/"
     "held_combine/gather", "backward", "experts"),
    (FWD + LOOP + "layer7/attn_out_norm/rsqrt", "forward", "norm"),
    (BWD + LOOP + "LlamaForCausalLM.one_pass/loop_pass/checkpoint/"
     "rematted_computation/layer2/mlp/gate_proj/dot_general",
     "recompute", "ffn"),
    (BWD + "LlamaForCausalLM/LlamaForCausalLM._loop/exit_gate/dot_general",
     "backward", "other"),
    (J + "jvp(loss)/broadcast_in_dim;" + J + "jvp(loss)/exit_head/reshape;"
     + J + "jvp(loss)/exit_head/lm_head/dot_general", "head_loss", "other"),
    (J + "transpose(jvp(loss))/jit(cumsum)/exit_distribution/mul",
     "head_loss", "other"),
    (J + "mul", "other", "other"),
    ("state.params['h0']['attn']['c_proj']['kernel']", "other", "other"),
    ("reduce_sum", "other", "other"),
]


@pytest.mark.parametrize("path,phase,component", PATHS)
def test_classify_by_the_paths_segments(path, phase, component):
    got_phase, got_component, recompute = classify(path)
    assert (got_phase, got_component) == (phase, component)
    assert got_phase in PHASES and got_component in COMPONENTS
    assert recompute == (phase == "recompute")


# ------------------------------------------------------- the trainer's span
def _tiny(tmp_path, steps=3):
    from pytorch_distributed_train_tpu.config import get_preset

    cfg = get_preset("gpt2_small")
    cfg.apply_overrides([
        "model.hidden_size=32", "model.num_layers=1", "model.num_heads=2",
        "model.mlp_dim=64", "model.vocab_size=128", "model.max_seq_len=32",
        "model.dropout_rate=0.0", "data.seq_len=32",
        "data.dataset=synthetic_lm", "data.batch_size=8",
        "data.synthetic_size=64", f"total_steps={steps}",
        "obs.log_every_steps=2", "eval_every_steps=1000000",
        "checkpoint.save_every_steps=0", "checkpoint.async_save=false",
        f"checkpoint.dir={tmp_path}"])
    return cfg


def _fit(cfg, replace=None):
    """The main thread's spans of one ``fit``, in open order."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    step_program.clear()
    trainer = Trainer(cfg)
    if replace is not None:
        trainer.train_step = replace(trainer.train_step)
    trainer.fit()
    trainer.close()
    main = threading.main_thread().name
    mine = [s for s in spans_lib.get_recorder().events() if s.thread == main]
    start = max(s.seq for s in mine if s.name == "train.init")
    return sorted((s for s in mine if s.seq >= start), key=lambda s: s.seq)


def _named(run, name):
    return [s for s in run if s.name == name]


def test_fit_leaves_a_map_and_a_span_beside_the_compile(tmp_path,
                                                        monkeypatch):
    """After the first step ``fit`` lowers the jitted step again, which
    JAX serves from its caches: ``train.program_map`` is a sibling of
    ``train.compile`` inside the first turn, holds no ``jax.compile`` span,
    and the run compiles its step as often as one that maps nothing: once."""
    from pytorch_distributed_train_tpu.trainer import Trainer

    run = _fit(_tiny(tmp_path / "a"))
    built = step_program.latest()
    assert built is not None and built.module == "jit_train_step"
    assert built.step == 0 and built.scopes and built.build_s > 0
    (mapped,) = _named(run, "train.program_map")
    (compiled,) = _named(run, "train.compile")
    assert mapped.parent_seq == compiled.parent_seq
    assert mapped.seq > compiled.seq
    turn = next(s for s in run if s.seq == mapped.parent_seq)
    assert turn.name == "train.iteration" and turn.args["step"] == 0
    assert not [s for s in run if s.parent_seq == mapped.seq]
    assert mapped.args["instructions"] == built.instructions > 100
    assert mapped.args["fusions"] == built.fusions
    assert mapped.args["mixed_fusions"] == len(built.mixed)
    assert mapped.args["text_mb"] == round(built.text_bytes / 1e6, 3)
    assert "program_map" not in mapped.args
    # the instructions it names lie in the step's phases
    phases = {built.place(name)[0] for name in built.scopes}
    assert {"forward", "backward", "head_loss", "optimizer"} <= phases

    monkeypatch.setattr(Trainer, "_map_step_program",
                        lambda self, batch, step: None)
    plain = _fit(_tiny(tmp_path / "b"))
    assert not _named(plain, "train.program_map")
    assert step_program.latest() is None
    # (a second fit in one process finds the small host-side programs in
    # JAX's caches, so only the step's own compiles compare)
    step_compiles = [[s for s in _named(spans, "jax.compile")
                      if s.args["fun"] == "jit(train_step)"]
                     for spans in (run, plain)]
    assert [len(found) for found in step_compiles] == [1, 1]


def test_a_wrapper_round_the_jit_still_leaves_a_map(tmp_path):
    """The benchmark's case: ``train_step`` replaced by a callable with no
    ``.lower`` that calls the jit."""
    run = _fit(_tiny(tmp_path),
               lambda inner: lambda state, batch, rng: inner(state, batch,
                                                             rng))
    (mapped,) = _named(run, "train.program_map")
    assert "program_map" not in mapped.args
    assert step_program.latest().module == "jit_train_step"
    assert not [s for s in run if s.parent_seq == mapped.seq]


def test_a_step_that_is_not_the_jits_leaves_no_map_and_says_why(tmp_path):
    """``train_step`` replaced by a plain function that never calls the
    jit: lowering the jit then compiles it, so what it describes is not
    what ran. No map, a reason on the span, no exception."""
    def plain(inner):
        def step(state, batch, rng):
            return state.replace(step=state.step + 1), {
                "loss": jnp.float32(1.0), "grad_norm": jnp.float32(0.0)}
        return step

    run = _fit(_tiny(tmp_path, steps=2), plain)
    (mapped,) = _named(run, "train.program_map")
    assert mapped.args["program_map"].startswith("none: ")
    assert "compiled" in mapped.args["program_map"]
    assert "instructions" not in mapped.args
    assert step_program.latest() is None


# ------------------------------------------ the operator's reader of the map
def test_a_capture_summary_says_what_each_top_operation_is(tmp_path,
                                                           monkeypatch):
    """The managed profiler's ``top_ops.txt`` (obs/profiler.py) prints each
    operation's phase and component from the newest map, where there is
    one; without a map it prints what it printed."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from pytorch_distributed_train_tpu.config import ObsConfig
    from pytorch_distributed_train_tpu.obs import profiler as profiler_lib

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name="/device:TPU:0")
    names = ["%fusion.1 = f32[8]{0} fusion(%all-reduce.7, %Arg_0.1), kind=kLoop",
             "%ragged-dot-none.1 = f32[8]{0} custom-call(%bitcast.6)",
             "%all-reduce.7 = f32[8]{0} all-reduce(%fusion.4)",
             "%copy.15 = f32[8]{0} copy(%Arg_1.2)"]
    line = plane.lines.add(name="XLA Ops")
    for i, name in enumerate(names, start=1):
        meta = plane.event_metadata[i]
        meta.id, meta.name = i, name
        ev = line.events.add()
        ev.metadata_id = i
        ev.duration_ps = int((5 - i) * 1e9)
    logdir = tmp_path / "capture"
    (logdir / "plugins" / "profile" / "run").mkdir(parents=True)
    with open(logdir / "plugins" / "profile" / "run" / "host.xplane.pb",
              "wb") as f:
        f.write(xs.SerializeToString())
    profiler = profiler_lib.ManagedProfiler(
        ObsConfig(profile_dir=str(tmp_path / "profiles")),
        run_dir=str(tmp_path), backend=object())

    monkeypatch.setattr(step_program, "_LATEST", scope_map(MODULE))
    text = profiler._summarize(str(logdir))
    assert text == (logdir / "top_ops.txt").read_text().rstrip("\n")
    rows = {ln.split("n=")[1].split()[1]: ln for ln in text.splitlines()
            if " n=" in ln}
    assert rows["%fusion.1"].endswith("[optimizer+mixed]")
    assert rows["%ragged-dot-none.1"].endswith("[backward/experts]")
    assert rows["%all-reduce.7"].endswith("[grad_reduce]")
    assert "[" not in rows["%copy.15"].split("copy(")[1]  # the map has none

    monkeypatch.setattr(step_program, "_LATEST", None)
    plain = profiler._summarize(str(logdir))
    assert "[optimizer" not in plain and "%fusion.1" in plain


# ------------------------------------- the four configurations' own steps
CONFIGS = ("gpt2_small", "ling3_flash_lm_ep64", "laguna_s_lm_ep32",
           "ouro_2_6b_lm_l8", "lfm2_8b_a1b_lm_ep4",
           "mellum2_12b_a2_5b_lm_ep4")
EXPERT_CONFIGS = ("ling3_flash_lm_ep64", "laguna_s_lm_ep32",
                  "lfm2_8b_a1b_lm_ep4", "mellum2_12b_a2_5b_lm_ep4")
ALL_SPARSE = ("mellum2_12b_a2_5b_lm_ep4",)  # no layer with a dense FFN
# What may stay outside every phase, as the four steps compile here: arguments
# named by their place in the state, reducers' bodies (a bare primitive, under
# `checkpoint/` inside a remat'd block, under the scanned pass's own name in
# the looped decoder) and the step's bookkeeping at its top level (one
# primitive under `jit(train_step)/`: the rng's fold, the guard's compare).
def _may_stay_outside(op_name: str) -> bool:
    if any(part in op_name for part in ("jvp(", "optimizer", "lm_head",
                                        "exit_head", "grad_reduce")):
        return False  # the model's, the head's or the optimizer's: a phase
    return ("/" not in op_name or op_name.startswith(("state.", "checkpoint/"))
            or "LlamaForCausalLM.one_pass/" in op_name
            or (op_name.startswith(J) and op_name.count("/") == 1))


@pytest.fixture(scope="module")
def rehearsal_maps():
    return _rehearsal_maps()


def _rehearsal_maps():
    """{configuration: map of its training step at the rehearsal's sizes},
    one compile each."""
    from pytorch_distributed_train_tpu import losses as losses_lib
    from pytorch_distributed_train_tpu import steps as steps_lib
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.models.registry import build_model
    from pytorch_distributed_train_tpu.optim import make_optimizer
    from pytorch_distributed_train_tpu.train_state import TrainState

    maps = {}
    for name in CONFIGS:
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json"),
                  encoding="utf-8") as f:
            bench = json.load(f)
        cfg = get_preset(bench["preset"])
        cfg.apply_overrides(list(bench["overrides"])
                            + list(bench["rehearsal_overrides"])
                            + ["data.batch_size=2", "data.seq_len=128"])
        model = build_model(cfg.model, cfg.precision)
        tx, _ = make_optimizer(cfg.optim, 10, 0)
        dummy = steps_lib.dummy_inputs(cfg.loss, cfg.model, cfg.data)

        def init(rng, model=model, tx=tx, dummy=dummy):
            params = model.init({"params": rng}, *dummy,
                                train=False)["params"]
            return TrainState.create(params=params, tx=tx, batch_stats={},
                                     dynamic_scale=None, ema=False,
                                     swa=False)

        step = steps_lib.make_train_step(
            model, losses_lib.get_loss_fn(
                cfg.loss, label_smoothing=cfg.label_smoothing), tx)
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            jax.eval_shape(init, jax.random.PRNGKey(0)),
            {"input_ids": jax.ShapeDtypeStruct((2, 128), jnp.int32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        maps[name] = scope_map(compiled.as_text())
    return maps


@pytest.mark.parametrize("config", CONFIGS)
def test_every_scoped_instruction_of_a_cells_step_lies_in_a_phase(
        rehearsal_maps, config):
    built = rehearsal_maps[config]
    assert built.module == "jit_train_step" and len(built.scopes) > 1000
    counts = dict.fromkeys(PHASES, 0)
    components = dict.fromkeys(COMPONENTS, 0)
    for name, op_name in built.scopes.items():
        phase, component, _ = built.place(name)
        counts[phase] += 1
        if phase in step_program.MODEL_PHASES:
            components[component] += 1
        if phase == "other":
            assert _may_stay_outside(op_name), op_name
    remat = config != "gpt2_small"
    for phase in ("forward", "backward", "head_loss", "optimizer"):
        assert counts[phase] > 50, (phase, counts)
    assert (counts["recompute"] > 50) == remat, counts
    assert counts["other"] < 0.1 * len(built.scopes), counts
    for component in ("attention", "norm", "embed"):
        assert components[component] > 0, components
    assert (components["ffn"] > 0) == (config not in ALL_SPARSE), components
    assert (components["experts"] > 0) == (config in EXPERT_CONFIGS), \
        components
    # under the model's phases little is left without a component
    assert components["other"] < 0.25 * sum(components.values()), components


@pytest.mark.parametrize("scope", ["held_gather", "held_combine"])
def test_an_expert_layers_gather_and_combine_have_scopes_in_every_phase(
        rehearsal_maps, scope):
    """The 16k window/full preset's map: the rows read out of the tokens
    (`held_gather`) and the weighted results added back (`held_combine`)
    sit under scopes of their own beneath every layer's `moe`, in the
    forward, the backward and (the gather) the forward run again, all of it
    the experts' component; `held_rows` and `grouped_product` beside them, so the map
    parts the expert layer's four costs."""
    built = rehearsal_maps["mellum2_12b_a2_5b_lm_ep4"]
    under = {name: op for name, op in built.scopes.items()
             if scope in step_program._SEGMENTS.split(op)}
    assert len(under) >= 6  # three layers, two phases or three
    phases = set()
    for name, op in under.items():
        assert "moe" in step_program._SEGMENTS.split(op), op
        phase, component, _ = built.place(name)
        assert component == "experts", op
        phases.add(phase)
    # (the combine is linear in its rows: the backward needs no rerun of it)
    assert phases == set(step_program.MODEL_PHASES) - (
        {"recompute"} if scope == "held_combine" else set())
    for other in ("held_rows", "grouped_product"):
        assert any(other in step_program._SEGMENTS.split(op)
                   for op in built.scopes.values()), other


def test_nothing_of_a_conv_module_is_left_without_a_component(
        rehearsal_maps):
    """The short-convolution preset's map: every scoped instruction under a
    `conv` module (both projections, the `short_conv` chain, in forward,
    rerun forward and backward) is a mixer's, the component the other
    mixers go to; the tied head's product sits in the head's phase; the
    phases and components still partition the step."""
    built = rehearsal_maps["lfm2_8b_a1b_lm_ep4"]
    under = {name: op for name, op in built.scopes.items()
             if "conv" in step_program._SEGMENTS.split(op)}
    assert len(under) > 30
    chain = [op for op in under.values()
             if "short_conv" in step_program._SEGMENTS.split(op)]
    assert chain and len(chain) < len(under)
    phases = set()
    for name in under:
        phase, component, _ = built.place(name)
        assert phase in step_program.MODEL_PHASES, under[name]
        assert component == "attention", under[name]
        phases.add(phase)
    assert phases == set(step_program.MODEL_PHASES)
    heads = [op for op in built.scopes.values()
             if op.endswith("HybridLM/lm_head/dot_general")]
    assert heads and all(classify(op)[0] == "head_loss" for op in heads)
