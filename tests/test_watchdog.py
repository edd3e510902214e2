"""Failure-detection shell (utils/watchdog.py — SURVEY C25/C26, §5.3a):
flight-recorder ring semantics, signal dump, and the heartbeat monitor's
stall abort (in a subprocess — it hard-kills)."""

import os
import subprocess
import sys

import pytest

from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ring_keeps_last_capacity_events():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("step", i)
    ev = fr.events()
    assert len(ev) == 4
    assert [e[2] for e in ev] == [6, 7, 8, 9]  # oldest→newest, last 4


def test_ring_partial_fill():
    fr = FlightRecorder(capacity=8)
    fr.record("epoch_start", 0, epoch=0)
    fr.record("step", 1)
    ev = fr.events()
    assert [(e[1], e[2]) for e in ev] == [("epoch_start", 0), ("step", 1)]
    assert ev[0][3] == {"epoch": 0}


def test_dump_writes_file(tmp_path):
    fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    fr.record("step", 1)
    fr.dump()
    files = [f for f in os.listdir(tmp_path) if "flight" in f]
    assert files, os.listdir(tmp_path)
    content = (tmp_path / files[0]).read_text()
    assert "step" in content


HEARTBEAT_WORKER = """
import sys, time
sys.path.insert(0, {repo!r})
from pytorch_distributed_train_tpu.utils.watchdog import FlightRecorder, Heartbeat

fr = FlightRecorder(capacity=8, dump_dir={out!r})
fr.record("step", 1)
hb = Heartbeat(timeout_s=1.0, recorder=fr)
hb.beat()
print("alive", flush=True)
time.sleep(30)  # stall: no further beats → monitor must abort the process
print("should-never-print", flush=True)
"""


def test_heartbeat_aborts_stalled_process(tmp_path):
    script = tmp_path / "stall.py"
    script.write_text(HEARTBEAT_WORKER.format(repo=REPO, out=str(tmp_path)))
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=60)
    assert "alive" in r.stdout
    assert "should-never-print" not in r.stdout
    assert r.returncode != 0  # hard abort, not clean exit
    # the ring was dumped on the way down
    combined = r.stdout + r.stderr
    assert "flight recorder" in combined.lower() or any(
        "flight" in f for f in os.listdir(tmp_path)
    )


def test_heartbeat_quiet_while_beats_arrive_and_after_stop():
    """The monitor must not fire while beats keep arriving, and stop()
    de-arms it (the Trainer stops it before teardown so shutdown can't
    race a late abort)."""
    import time

    from pytorch_distributed_train_tpu.utils.watchdog import Heartbeat

    fired = []
    hb = Heartbeat(timeout_s=0.4, abort=lambda: fired.append(1))
    for _ in range(6):
        time.sleep(0.15)
        hb.beat()
    assert not fired  # beats within timeout → no abort
    hb.stop()
    time.sleep(1.0)
    assert not fired  # stopped → stall after stop is not an abort


def test_heartbeat_custom_abort_dumps_recorder(capsys):
    import time

    from pytorch_distributed_train_tpu.utils.watchdog import (
        FlightRecorder,
        Heartbeat,
    )

    fr = FlightRecorder(capacity=4)
    fr.record("step", 7, loss=1.25)
    fired = []
    hb = Heartbeat(timeout_s=0.3, recorder=fr, abort=lambda: fired.append(1))
    time.sleep(1.2)
    assert fired  # stalled → custom abort invoked (instead of os._exit)
    hb.stop()


def test_heartbeat_zero_timeout_disabled():
    import time

    from pytorch_distributed_train_tpu.utils.watchdog import Heartbeat

    fired = []
    hb = Heartbeat(timeout_s=0.0, abort=lambda: fired.append(1))
    time.sleep(0.5)
    assert hb._thread is None and not fired
