"""Every rehearsal a work directory of its own. Runs of ONE cell share
``benchmark/.work/<cell>`` and each removes it as it starts, while the
suite's workers (``--dist load``) may take one file's tests side by side:
two rehearsals of a cell at once then lose each other's files."""

import os

import bench_helpers
import pytest

SHARED = os.path.join(bench_helpers.BENCH, ".work")


@pytest.fixture(autouse=True)
def own_work_directory(monkeypatch, tmp_path):
    load = bench_helpers.load_run

    def load_run(*args, **kw):
        run = load(*args, **kw)
        # (a copied benchmark's runner keeps its own; a reader has none)
        if getattr(run, "WORK", None) == SHARED:
            run.WORK = str(tmp_path / "work")
        return run

    monkeypatch.setattr(bench_helpers, "load_run", load_run)
