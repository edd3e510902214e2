"""The trainer's loop and set-up as spans (obs/spans.py, trainer.py): one
``train.iteration`` a step with the wait for the batch and the dispatch
nested in it, a ``train.log``/``train.log.sync`` pair on cadenced steps,
``train.init`` with its parts before them, a ``jax.compile`` span for every
compile JAX reports; each span keeps its parent, so an iteration is its
children plus a self time that is never negative. And the pure function a
trace reduction will name device gaps with."""

import threading

import pytest

from pytorch_distributed_train_tpu.obs import spans as spans_lib
from pytorch_distributed_train_tpu.obs.registry import get_registry
from pytorch_distributed_train_tpu.obs.spans import Span, SpanRecorder

STEPS, LOG_EVERY, EPOCH = 9, 4, 6   # 48 sequences / batch 8: one epoch end


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """The main thread's spans of one short ``fit`` on the tiny decoder,
    in open order."""
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("gpt2_small")
    cfg.apply_overrides([
        "model.hidden_size=32", "model.num_layers=1", "model.num_heads=2",
        "model.mlp_dim=64", "model.vocab_size=128", "model.max_seq_len=32",
        "model.dropout_rate=0.0", "data.seq_len=32",
        "data.dataset=synthetic_lm", "data.batch_size=8",
        f"data.synthetic_size={8 * EPOCH}", f"total_steps={STEPS}",
        f"obs.log_every_steps={LOG_EVERY}", "eval_every_steps=1000000",
        "checkpoint.save_every_steps=0", "checkpoint.async_save=false",
        f"checkpoint.dir={tmp_path_factory.mktemp('ckpt')}"])
    before = spans_lib.get_recorder().n
    trainer = Trainer(cfg)
    trainer.fit()
    trainer.close()
    rec = spans_lib.get_recorder()
    assert rec.n - before < rec.capacity  # nothing of this run fell out
    main = threading.main_thread().name
    mine = [s for s in rec.events() if s.thread == main]
    start = max(s.seq for s in mine if s.name == "train.init")
    return sorted((s for s in mine if s.seq >= start), key=lambda s: s.seq)


def children(run, parent):
    return [s for s in run if s.parent_seq == parent.seq]


def named(run, name):
    return [s for s in run if s.name == name]


def test_one_iteration_a_step_with_wait_and_dispatch_inside(run):
    turns = [t for t in named(run, "train.iteration")
             if "epoch_end" not in t.args]
    assert [t.args["step"] for t in turns] == list(range(STEPS))
    for t in turns:
        assert t.parent_seq is None and t.depth == 0
        kids = [c.name for c in children(run, t)]
        assert kids[0] == "train.input_wait"
        assert kids[1] == ("train.compile" if t.args["step"] == 0
                           else "train.step")
    # the names the benchmark and the docs already use are still there
    assert len(named(run, "train.compile")) == 1
    assert len(named(run, "train.step")) == STEPS - 1


def test_the_compile_span_says_how_often_a_leaf_is_reduced(run):
    """steps.grad_reduce_plan's verdict rides the one train.compile span:
    the suite's virtual CPU devices all lie on the data axis with the
    state replicated, so the tied table is reduced once (per_leaf)."""
    import jax

    (compile_span,) = named(run, "train.compile")
    assert compile_span.args["grad_reduce"] == "per_leaf"
    assert compile_span.args["batch_devices"] == len(jax.devices())
    assert all("grad_reduce" not in s.args for s in named(run, "train.step"))


def test_the_compile_span_says_what_took_the_head_and_its_loss(run):
    """What the trace resolved the LM head and its loss to (ops/lm_head.py)
    rides the same span: on the CPU the step keeps the logits path, and
    the attribute carries the reason its `[lm_head]` line gave."""
    (compile_span,) = named(run, "train.compile")
    assert compile_span.args["head_loss"] == "xla: the backend is not a TPU"
    assert all("head_loss" not in s.args for s in named(run, "train.step"))


def test_the_compile_span_says_what_remat_keeps(run):
    """`remat_keeps` rides the same span: the name under which a remat'd
    block kept the flash kernel's output and log-sum-exp (models/remat.py),
    `none` here, where no block is remat'd and no kernel runs
    (tests/test_remat_policy.py holds the other value)."""
    (compile_span,) = named(run, "train.compile")
    assert compile_span.args["remat_keeps"] == "none"
    assert all("remat_keeps" not in s.args for s in named(run, "train.step"))


def test_cadenced_steps_carry_a_log_with_its_sync(run):
    logs = named(run, "train.log")
    assert [s.args["step"] for s in logs] == [4, 8, 9]  # cadence, horizon
    for log in logs:
        turn = next(s for s in run if s.seq == log.parent_seq)
        # the log of step n closes the turn that dispatched step n-1
        assert turn.name == "train.iteration"
        assert turn.args["step"] == log.args["step"] - 1
        sync = [c for c in children(run, log) if c.name == "train.log.sync"]
        assert len(sync) == 1 and sync[0].dur_s <= log.dur_s


def test_init_comes_first_with_its_parts_and_compiles_are_spans(run):
    assert run[0].name == "train.init"
    parts = [c.name for c in children(run, run[0])
             if c.name.startswith("train.init.")]
    assert parts == ["train.init." + p for p in (
        "mesh", "data", "eval_data", "state", "steps", "checkpoint",
        "planes")]
    first_turn = named(run, "train.iteration")[0]
    assert run[0].seq < first_turn.seq
    assert run[0].t0_ns + run[0].dur_ns <= first_turn.t0_ns + 1_000_000
    compiles = named(run, "jax.compile")
    assert compiles and all(c.dur_s > 0 and c.args["fun"] for c in compiles)
    # the step's own compile is reported inside train.compile, with its step
    (step_compile,) = named(run, "train.compile")
    inside = [c for c in children(run, step_compile)
              if c.name == "jax.compile"]
    assert any("train_step" in c.args["fun"] and c.args["step"] == 0
               for c in inside)


def test_an_iteration_is_its_children_plus_a_self_time_never_negative(run):
    for parent in run:
        kids = children(run, parent)
        if not kids:
            continue
        own = parent.dur_s - sum(k.dur_s for k in kids)
        assert own >= 0.0, (parent.name, parent.args, own)
        # children lie inside their parent on the epoch clock too (1 ms of
        # slack: starts are time.time_ns, lengths perf_counter_ns)
        for k in kids:
            assert k.t0_ns >= parent.t0_ns - 1_000_000
            assert (k.t0_ns + k.dur_ns
                    <= parent.t0_ns + parent.dur_ns + 1_000_000)


def test_the_turn_that_finds_the_epoch_exhausted_is_tagged(run):
    (end,) = [t for t in named(run, "train.iteration")
              if t.args.get("epoch_end")]
    assert end.args["step"] == EPOCH
    assert [c.name for c in children(run, end)] == ["train.input_wait"]


# ------------------------------------------------------- the recorder itself
def test_a_span_hands_its_clock_reads_to_the_caller():
    import time

    rec = SpanRecorder(capacity=8, feed_registry=False)
    before, wall = time.perf_counter(), time.time_ns()
    with rec.span("outer") as outer:
        with rec.span("inner") as inner:
            assert inner.dur_s is None
    after = time.perf_counter()
    assert before <= outer.start_s <= inner.start_s <= after
    got_inner, got_outer = rec.events()
    assert got_inner.dur_s == inner.dur_s <= outer.dur_s
    assert (got_inner.seq, got_inner.parent_seq) == (1, 0)
    assert (got_outer.seq, got_outer.parent_seq) == (0, None)
    # starts are epoch nanoseconds; t0 is the same instant in seconds
    assert 0 <= got_outer.t0_ns - wall < 1_000_000_000
    assert got_outer.t0 == pytest.approx(got_outer.t0_ns * 1e-9)
    assert got_outer.dur_ns == pytest.approx(got_outer.dur_s * 1e9)


def test_record_nests_under_the_span_open_on_the_calling_thread():
    rec = SpanRecorder(capacity=8, feed_registry=False)
    with rec.span("train.compile"):
        rec.record("jax.compile", 100.0, 0.5, fun="jit(f)")
    rec.record("serve.decode", 100.0, 0.1, thread="scheduler")
    compile_, parent, other = rec.events()
    assert compile_.parent_seq == parent.seq and compile_.depth == 1
    assert compile_.t0_ns == 100_000_000_000
    assert other.parent_seq is None and other.thread == "scheduler"


def test_an_init_that_raises_flags_the_open_part_and_leaves_nothing_open(
        tmp_path):
    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.trainer import Trainer

    cfg = get_preset("gpt2_small")
    cfg.apply_overrides([
        "model.hidden_size=32", "model.num_layers=1", "model.num_heads=2",
        "model.mlp_dim=64", "model.vocab_size=128", "model.max_seq_len=32",
        "data.seq_len=32", "data.batch_size=8", "data.synthetic_size=16",
        "data.dataset=no_such_dataset", f"checkpoint.dir={tmp_path}"])
    rec = spans_lib.get_recorder()
    n = rec.n
    with pytest.raises(Exception):
        Trainer(cfg)
    new = {s.name: s for s in rec.events()[-(rec.n - n):]}
    assert "error" not in new["train.init.mesh"].args
    assert new["train.init.data"].args["error"]
    assert new["train.init"].args["error"]
    assert new["train.init.data"].parent_seq == new["train.init"].seq
    assert "train.init.state" not in new
    assert rec.active() == []


def test_the_compile_listener_is_installed_once_and_tags_the_step():
    import jax
    import jax.numpy as jnp

    spans_lib.install_compile_listener()
    spans_lib.install_compile_listener()
    rec = spans_lib.get_recorder()
    x = jnp.arange(7.0)  # its own small program, compiled before the count
    spans_lib.set_correlation_tags(step=41)
    try:
        n = rec.n
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        new = [s for s in rec.events()[-(rec.n - n):]
               if s.name == "jax.compile"]
    finally:
        spans_lib.set_correlation_tags(step=None)
    assert len(new) == 1 and new[0].args["step"] == 41
    assert "lambda" in new[0].args["fun"]


def test_span_histograms_follow_a_registry_reset():
    rec = SpanRecorder(capacity=8)

    def count():
        return get_registry().histogram(
            "span_seconds", labels={"name": "test.reset_probe"}).count

    with rec.span("test.reset_probe"):
        pass
    with rec.span("test.reset_probe"):
        pass
    assert count() == 2
    get_registry().reset()
    with rec.span("test.reset_probe"):
        pass
    assert count() == 1


# ------------------------------------------------ naming a device idle gap
def _sp(name, start, end, *, depth=0, thread=None):
    return Span(name, start, end - start,
                thread or threading.main_thread().name, depth, {})


RING = [
    _sp("train.iteration", 100.0, 150.0),
    _sp("train.input_wait", 100.0, 110.0, depth=1),
    _sp("train.step", 110.0, 120.0, depth=1),
    _sp("train.log", 120.0, 150.0, depth=1),
    _sp("train.log.sync", 120.0, 140.0, depth=2),
    _sp("train.iteration", 160.0, 180.0),
    _sp("data.produce", 150.0, 160.0, thread="producer"),
]


@pytest.mark.parametrize("interval,name", [
    ((125.0, 135.0), "train.log.sync"),      # covered by the innermost span
    ((102.0, 108.0), "train.input_wait"),
    ((135.0, 145.0), "train.log"),           # half in the sync: the log
    ((105.0, 115.0), "train.iteration"),     # straddles two children evenly
    ((145.0, 165.0), "between_spans"),       # straddles two turns, 1/4 each
    ((152.0, 158.0), "between_spans"),       # in no span of the main thread
    ((165.0, 170.0), "train.iteration"),     # the turn's own time
    ((108.0, 118.0), "train.step"),          # 1/5 in the wait, 4/5 here
], ids=["covered", "covered-first-child", "exactly-half-is-not-enough",
        "straddles-siblings", "straddles-turns", "no-span",
        "no-child-there", "mostly-one-child"])
def test_cover_names_gives_the_innermost_span_with_the_larger_part(
        interval, name):
    assert spans_lib.cover_names([interval], RING) == [name]


def test_cover_names_reads_the_thread_it_is_given():
    assert spans_lib.cover_names(
        [(152.0, 158.0), (125.0, 135.0)], RING, thread="producer") == [
            "data.produce", "between_spans"]
