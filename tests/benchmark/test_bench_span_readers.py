"""The readers of the program's spans (benchmark/span_readers.py) on rings
made by hand, with known answers: each of the five metrics, two runs in one
ring, a run whose last iteration raised, a queue that held every dispatch,
a loop that runs a bounded number of steps ahead of the device (as on the
chip), a dispatch of an eighth of a step, a turn that only found its epoch
exhausted, a traced window that stop_trace stretched, a ring that wrapped
past the run's start, and a program that
keeps no parents (as before these readers)."""

import os
import sys
import threading
import types

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmark"))
import span_readers as readers  # noqa: E402

MAIN = threading.main_thread().name


class Ring:
    """Builds spans the way the recorder numbers them: ``seq`` in open
    order, ``parent_seq`` the span open round it."""

    def __init__(self):
        self.spans, self.seq = [], 0

    def add(self, name, dur_s, parent=None, *, thread=MAIN, error=False,
            **args):
        sp = types.SimpleNamespace(
            name=name, dur_s=dur_s, thread=thread, seq=self.seq,
            parent_seq=None if parent is None else parent.seq,
            args={**args, "error": True} if error else args)
        self.seq += 1
        self.spans.append(sp)
        return sp

    def run(self, *, init_s, compile_s, own_ms, steps, blocked_ms,
            dispatch_ms, log_at=(), log_host_ms=0.0, sync_ms=0.0,
            last_raises=False, free_after_log=None):
        """One ``Trainer`` life: init, a compiling turn, then ``steps``
        turns whose own bookkeeping is ``own_ms[i % len(own_ms)]``. Every
        second call is held ``blocked_ms`` by the device queue; with
        ``free_after_log`` it is the calls past that many since the run's
        start or its last log (a queue of that depth, drained by the log)."""
        self.add("train.init", init_s)
        turn = self.add("train.iteration", compile_s + 0.002)
        self.add("train.input_wait", 0.001, turn)
        self.add("train.compile", compile_s, turn)
        since_drain = 0
        for i in range(steps):
            blocked = (i % 2 if free_after_log is None
                       else since_drain >= free_after_log)
            since_drain = 0 if i in log_at else since_drain + 1
            held = blocked_ms if blocked else dispatch_ms[i % len(dispatch_ms)]
            kids = 0.0005 + held * 1e-3
            log = i in log_at
            if log:
                kids += (log_host_ms + sync_ms) * 1e-3
            raises = last_raises and i == steps - 1
            turn = self.add("train.iteration",
                            kids + own_ms[i % len(own_ms)] * 1e-3,
                            error=raises)
            self.add("train.input_wait", 0.0005, turn)
            self.add("data.produce", 0.3, thread="producer")
            self.add("train.step", held * 1e-3, turn, error=raises)
            if log:
                entry = self.add("train.log",
                                 (log_host_ms + sync_ms) * 1e-3, turn)
                self.add("train.log.sync", sync_ms * 1e-3, entry)


def read_all(monkeypatch, ring, steps, step_s=0.1):
    monkeypatch.setattr(readers, "ring", lambda: ring.spans)
    ctx = {"counters": {"steps": steps, "window_s": steps * step_s}}
    return {name: getattr(readers, name)(ctx) for name in (
        "trainer_host_ms_per_step", "step_dispatch_ms",
        "setup_trainer_init_s", "setup_step_compile_s", "setup_first_log_s")}


@pytest.fixture
def two_runs():
    """An older run, then the one to be read: 12 turns, every second one
    held 100 ms by the device queue, logs on turns 3 and 9."""
    ring = Ring()
    ring.run(init_s=99.0, compile_s=77.0, own_ms=[9.0], steps=5,
             blocked_ms=50.0, dispatch_ms=[5.0], log_at=(1,),
             log_host_ms=500.0, sync_ms=1.0)
    ring.run(init_s=25.0, compile_s=7.5, own_ms=[0.2, 0.4, 0.3], steps=12,
             blocked_ms=100.0, dispatch_ms=[0.5, 0.7, 0.6], log_at=(3, 9),
             log_host_ms=6.0, sync_ms=40.0)
    return ring


@pytest.mark.parametrize("metric,want", [
    # median own time 0.3 ms + two logs of 6 ms host work over 12 turns
    ("trainer_host_ms_per_step", 0.3 + 2 * 6.0 / 12),
    # turns 0, 2, 4, ... were not held: 0.5, 0.6, 0.7, 0.5, 0.6, 0.7
    ("step_dispatch_ms", 0.6),
    ("setup_trainer_init_s", 25.0),
    ("setup_step_compile_s", 7.5),
    ("setup_first_log_s", 0.006),
])
def test_each_reader_on_the_newest_of_two_runs(monkeypatch, two_runs,
                                               metric, want):
    assert read_all(monkeypatch, two_runs, 12)[metric] == pytest.approx(want)


def test_only_the_windows_iterations_are_read(monkeypatch, two_runs):
    # the last 4 turns (8..11): own 0.3, 0.2, 0.4, 0.3; one log (turn 9)
    got = read_all(monkeypatch, two_runs, 4)
    assert got["trainer_host_ms_per_step"] == pytest.approx(0.3 + 6.0 / 4)
    assert got["step_dispatch_ms"] == pytest.approx(0.65)  # 0.6 and 0.7
    assert got["setup_first_log_s"] == pytest.approx(0.006)  # still turn 3's


def test_a_last_iteration_that_raised_is_left_out(monkeypatch):
    ring = Ring()
    ring.run(init_s=20.0, compile_s=5.0, own_ms=[0.2, 0.2, 0.2, 0.2, 50.0],
             steps=5, blocked_ms=100.0, dispatch_ms=[0.5, 0.5, 0.5, 0.5, 30.0],
             last_raises=True)
    got = read_all(monkeypatch, ring, 3)
    # turns 1, 2, 3: the raised turn 4 (own 50 ms, dispatch 30 ms) is not one
    assert got["trainer_host_ms_per_step"] == pytest.approx(0.2)
    assert got["step_dispatch_ms"] == pytest.approx(0.5)


def test_when_the_queue_holds_every_dispatch_there_is_nothing_to_read(
        monkeypatch):
    ring = Ring()
    ring.run(init_s=20.0, compile_s=5.0, own_ms=[0.2], steps=6,
             blocked_ms=100.0, dispatch_ms=[100.0])
    got = read_all(monkeypatch, ring, 6)
    assert got["step_dispatch_ms"] is None
    assert got["trainer_host_ms_per_step"] == pytest.approx(0.2)
    assert got["setup_first_log_s"] is None  # the run never logged


def test_a_loop_that_runs_a_bounded_depth_ahead_reads_its_free_calls(
        monkeypatch, capsys):
    # as on the chip: after each log's drain 32 calls return in ~4 ms, the
    # other 18 of 50 wait about one 137 ms step each for a slot in the queue
    ring = Ring()
    ring.run(init_s=11.0, compile_s=8.9, own_ms=[0.4], steps=100,
             blocked_ms=120.0, dispatch_ms=[4.1, 4.3], log_at=(49, 99),
             log_host_ms=17.0, sync_ms=4386.0, free_after_log=32)
    got = read_all(monkeypatch, ring, 100, step_s=0.137)
    assert got["step_dispatch_ms"] == pytest.approx(4.2)
    assert got["trainer_host_ms_per_step"] == pytest.approx(
        0.4 + 2 * 17.0 / 100)
    # how many calls were read is said aloud, so a metric that thins out shows
    assert "64 of 100 train.step calls under 68.5 ms" in capsys.readouterr().err


def test_a_dispatch_of_an_eighth_of_a_step_is_still_a_dispatch(monkeypatch):
    # once the step is short the free call is no small part of it: at 0.12
    # of a step it is read, the calls held about a whole step are not
    ring = Ring()
    ring.run(init_s=11.0, compile_s=8.9, own_ms=[0.4], steps=40,
             blocked_ms=60.0, dispatch_ms=[8.0], free_after_log=25)
    got = read_all(monkeypatch, ring, 40, step_s=0.0667)
    assert got["step_dispatch_ms"] == pytest.approx(8.0)


def test_a_window_that_stop_trace_stretched_does_not_move_the_split(
        monkeypatch):
    # as on four chips: stop_trace held the process 27 s inside the window,
    # so window_s / steps is 321 ms and half of it lies ABOVE the calls that
    # waited a whole 147 ms step; the traced slice's own step time is used
    ring = Ring()
    ring.run(init_s=9.3, compile_s=14.8, own_ms=[0.4], steps=93,
             blocked_ms=145.0, dispatch_ms=[8.2, 8.4], log_at=(46,),
             log_host_ms=17.0, sync_ms=4694.0, free_after_log=33)
    monkeypatch.setattr(readers, "ring", lambda: ring.spans)
    ctx = {"counters": {"steps": 93, "window_s": 29.87},
           "trace": {"steps": 11, "window_s": 1.6147},
           "device_kind": "TPU v5 lite"}
    assert readers.step_dispatch_ms(ctx) == pytest.approx(8.3)
    # the CPU rehearsal (no device plane) and a trace with no steps fall back
    # on the window's, which here lets the 27 held calls in and pulls the
    # median up (the chip read 9.28 for 8.34)
    assert readers.step_dispatch_ms(
        {**ctx, "device_kind": "cpu"}) == pytest.approx(8.4)
    ctx["trace"] = {"steps": 0, "window_s": 0.0}
    assert readers.step_dispatch_ms(ctx) == pytest.approx(8.4)


def test_the_turn_that_found_its_epoch_exhausted_is_no_iteration(monkeypatch):
    ring = Ring()
    ring.run(init_s=11.0, compile_s=8.9, own_ms=[0.2], steps=4,
             blocked_ms=100.0, dispatch_ms=[0.5])
    end = ring.add("train.iteration", 0.9, epoch_end=True)
    ring.add("train.input_wait", 0.8, end)
    got = read_all(monkeypatch, ring, 2)
    # the last two turns that took a step: own 0.2 ms each, not 100 ms
    assert got["trainer_host_ms_per_step"] == pytest.approx(0.2)


def test_a_ring_that_wrapped_past_the_runs_init_raises(monkeypatch):
    from pytorch_distributed_train_tpu.obs import spans

    ring = Ring()
    ring.run(init_s=11.0, compile_s=8.9, own_ms=[0.2], steps=4,
             blocked_ms=100.0, dispatch_ms=[0.5])
    whole, tail = ring.spans, ring.spans[1:]
    readers.check_not_wrapped(whole, len(whole) + 900, len(whole))  # intact
    readers.check_not_wrapped(tail, len(tail), len(tail))  # never wrapped
    with pytest.raises(RuntimeError, match="wrapped past the run's train.init"):
        readers.check_not_wrapped(tail, len(whole), len(tail))
    # a program older than the readers is told apart by its spans, not by n
    old = [types.SimpleNamespace(name="train.step", dur_s=0.1, thread=MAIN,
                                 args={}, depth=0)]
    readers.check_not_wrapped(old, 9000, 1)
    # and ring() asks the program's own recorder
    small = spans.SpanRecorder(capacity=4, feed_registry=False)
    for _ in range(6):
        with small.span("train.step"):
            pass
    monkeypatch.setattr(spans, "get_recorder", lambda: small)
    with pytest.raises(RuntimeError, match="6 spans completed, capacity 4"):
        readers.ring()


@pytest.mark.parametrize("spans", [
    None, [],
    # the parent's program: spans, but no train.init, no seq, no parent
    [types.SimpleNamespace(name="train.step", dur_s=0.1, thread=MAIN,
                           args={}, depth=0)],
], ids=["no-ring", "empty-ring", "a-program-older-than-the-readers"])
def test_without_the_programs_spans_every_reader_returns_none(monkeypatch,
                                                              spans):
    ring = types.SimpleNamespace(spans=spans)
    assert set(read_all(monkeypatch, ring, 10).values()) == {None}


def test_an_init_that_raised_is_not_a_set_up_time(monkeypatch):
    ring = Ring()
    ring.add("train.init", 3.0, error=True)
    assert read_all(monkeypatch, ring, 10)["setup_trainer_init_s"] is None


def test_the_ring_is_the_programs_own(monkeypatch):
    from pytorch_distributed_train_tpu.obs import spans

    with spans.span("bench.probe"):
        pass
    assert any(s.name == "bench.probe" for s in readers.ring())
