"""tpurun gang launcher (SURVEY C10): env contract, store-mediated barrier,
whole-gang restart on worker failure — the behaviors torchrun's elastic
agent tests cover (torch:distributed/elastic/agent/server/api.py:906-970),
restart semantics adapted to SPMD (whole gang, not single rank).
"""

import os
import subprocess
import sys

import pytest

from pytorch_distributed_train_tpu.elastic import ElasticAgent, LaunchConfig

# (ends processes abruptly: tests/conftest.py on the run's compile cache)
pytestmark = pytest.mark.usefixtures("compile_cache_off")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OK_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
from pytorch_distributed_train_tpu.elastic import worker_store

rank = int(os.environ["PROCESS_ID"])
world = int(os.environ["NUM_PROCESSES"])
gen = os.environ["RESTART_GENERATION"]
store = worker_store()
store.set(f"hello/{{rank}}", f"gen{{gen}}".encode())
store.barrier(f"done-{{gen}}", world, rank, timeout_ms=20000)
with open(os.path.join({out!r}, f"rank{{rank}}.txt"), "w") as f:
    f.write(f"{{rank}}/{{world}} gen={{gen}}")
"""

FLAKY_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
rank = int(os.environ["PROCESS_ID"])
gen = int(os.environ["RESTART_GENERATION"])
marker = os.path.join({out!r}, "crashed-once")
if rank == 1 and not os.path.exists(marker):
    open(marker, "w").close()
    sys.exit(17)  # first generation: rank 1 dies
with open(os.path.join({out!r}, f"rank{{rank}}-gen{{gen}}.txt"), "w") as f:
    f.write("ok")
"""


def _launch(script_text, tmp_path, nprocs=2, max_restarts=2):
    script = tmp_path / "worker.py"
    script.write_text(script_text.format(repo=REPO, out=str(tmp_path)))
    cfg = LaunchConfig(nprocs=nprocs, max_restarts=max_restarts,
                       monitor_interval_s=0.1)
    agent = ElasticAgent(cfg, [sys.executable, str(script)])
    return agent.run()


def test_gang_runs_and_exchanges_via_store(tmp_path):
    rc = _launch(OK_WORKER, tmp_path, nprocs=3)
    assert rc == 0
    for r in range(3):
        content = (tmp_path / f"rank{r}.txt").read_text()
        assert content == f"{r}/3 gen=0"


def test_gang_restart_on_failure(tmp_path):
    rc = _launch(FLAKY_WORKER, tmp_path, nprocs=2)
    assert rc == 0
    # generation 1 completed for every rank (whole-gang restart)
    assert (tmp_path / "rank0-gen1.txt").exists()
    assert (tmp_path / "rank1-gen1.txt").exists()
    # generation 0: rank 1 died before writing; rank 0 was killed with the gang
    assert not (tmp_path / "rank1-gen0.txt").exists()


def test_restart_budget_exhausted(tmp_path):
    always_fail = (
        "import sys\nsys.exit(3)\n"
    )
    script = tmp_path / "worker.py"
    script.write_text(always_fail)
    cfg = LaunchConfig(nprocs=2, max_restarts=1, monitor_interval_s=0.1)
    rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    assert rc == 3


def test_multinode_gang_restart(tmp_path):
    """nnodes=2 on localhost: a failure on node 1 must restart BOTH nodes'
    gangs (whole-job restart, not per-node)."""
    import socket
    import threading

    script = tmp_path / "worker.py"
    script.write_text(FLAKY_WORKER.format(repo=REPO, out=str(tmp_path)))
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]

    rcs = {}

    def agent(node_rank):
        cfg = LaunchConfig(nprocs=1, max_restarts=2, monitor_interval_s=0.1,
                           nnodes=2, node_rank=node_rank,
                           master_addr="127.0.0.1", store_port=port)
        rcs[node_rank] = ElasticAgent(
            cfg, [sys.executable, str(script)]).run()

    threads = [threading.Thread(target=agent, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert rcs == {0: 0, 1: 0}
    # gen 1 completed on BOTH nodes (ranks 0 and 1)
    assert (tmp_path / "rank0-gen1.txt").exists()
    assert (tmp_path / "rank1-gen1.txt").exists()
    # node 0's gen-0 worker was killed by the cross-node restart before
    # writing (it sleeps on the barrier only in OK_WORKER; FLAKY_WORKER's
    # rank 0 writes immediately, so only assert rank1 never wrote gen 0)
    assert not (tmp_path / "rank1-gen0.txt").exists()


NODE_LOSS_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
rank = int(os.environ["PROCESS_ID"])
world = int(os.environ["NUM_PROCESSES"])
gen = int(os.environ["RESTART_GENERATION"])
if gen == 0 and rank == 2:
    sys.exit(21)  # "node 2 dies" — its agent exhausts restarts and leaves
with open(os.path.join({out!r}, f"gen{{gen}}-rank{{rank}}.txt"), "w") as f:
    f.write(f"{{rank}}/{{world}}")
"""


def test_degraded_restart_dynamic_world(tmp_path):
    """3-node gang loses a node; the restart generation rendezvouses the 2
    survivors within the window and training resumes with NUM_PROCESSES=2
    and dense re-ranked node indices (VERDICT r2 #7; SURVEY C11,
    torch:...dynamic_rendezvous.py:1148 is the behavioral anchor)."""
    import socket
    import threading

    script = tmp_path / "worker.py"
    script.write_text(NODE_LOSS_WORKER.format(repo=REPO, out=str(tmp_path)))
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]

    rcs = {}

    def agent(node_rank, max_restarts):
        cfg = LaunchConfig(nprocs=1, max_restarts=max_restarts,
                           monitor_interval_s=0.1,
                           nnodes=3, node_rank=node_rank,
                           master_addr="127.0.0.1", store_port=port,
                           min_nnodes=2, rendezvous_window_s=2.0)
        rcs[node_rank] = ElasticAgent(
            cfg, [sys.executable, str(script)]).run()

    # Node 2's agent gets no restart budget: after its worker dies at gen 0
    # it exits — the "machine lost" simulation (it never re-rendezvouses).
    threads = [threading.Thread(target=agent, args=(r, 0 if r == 2 else 2))
               for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert rcs[0] == 0 and rcs[1] == 0 and rcs[2] == 21, rcs
    # Generation 1 ran DEGRADED: two processes, dense ranks 0 and 1.
    assert (tmp_path / "gen1-rank0.txt").read_text() == "0/2"
    assert (tmp_path / "gen1-rank1.txt").read_text() == "1/2"
    assert not (tmp_path / "gen1-rank2.txt").exists()
    # Generation 0 ran full-world before the loss.
    assert (tmp_path / "gen0-rank0.txt").read_text() == "0/3"


def test_cli_smoke(tmp_path):
    out = tmp_path / "cli.txt"
    script = tmp_path / "w.py"
    script.write_text(
        f"import os\nopen({str(out)!r} + os.environ['PROCESS_ID'], 'w')"
        ".write('x')\n"
    )
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tpurun.py"), "--nprocs", "2",
         "--", str(script)],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert os.path.exists(str(out) + "0") and os.path.exists(str(out) + "1")


def test_sigterm_ignoring_worker_gets_sigkilled(tmp_path):
    """Shutdown escalation (ISSUE 2): a worker that ignores SIGTERM (a
    stand-in for one wedged in a collective) must be SIGKILLed after the
    grace period so the gang teardown cannot wedge. Rank 0 fails fast;
    rank 1 ignores SIGTERM and sleeps far beyond any test timeout — the
    run completing promptly IS the escalation working."""
    import time

    stubborn = """
import os, signal, sys, time
rank = int(os.environ["PROCESS_ID"])
if rank == 0:
    sys.exit(7)  # trigger the gang teardown immediately
signal.signal(signal.SIGTERM, signal.SIG_IGN)
with open(os.path.join({out!r}, "ignoring"), "w") as f:
    f.write("armed")
time.sleep(300)
"""
    script = tmp_path / "worker.py"
    script.write_text(stubborn.format(out=str(tmp_path)))
    cfg = LaunchConfig(nprocs=2, max_restarts=0, monitor_interval_s=0.1,
                       shutdown_grace_s=1.0)
    t0 = time.time()
    rc = ElasticAgent(cfg, [sys.executable, str(script)]).run()
    elapsed = time.time() - t0
    assert rc == 7  # the real failure surfaced, not a hang
    # grace 1s + monitor + process spawn slack; nowhere near the 300s nap
    assert elapsed < 60, f"teardown took {elapsed:.1f}s — SIGKILL not sent?"
