"""The one rule for where the persistent compile cache lives
(utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR decides when set and
no code sets another directory; unset, one fixed directory inside the
checkout; the suite itself runs on a cache of the run's own, which a test
switches off for itself and its children (conftest)."""

import os
import subprocess
import sys

import jax
import pytest

from pytorch_distributed_train_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_on(monkeypatch, compile_cache_off):
    """Out of the run's own cache first (``compile_cache_off``); these tests
    are about the flag being on and WHERE the cache then lives. Every later
    jax.config.update is recorded, none is applied."""
    jax.config.update("jax_enable_compilation_cache", True)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    yield updates
    monkeypatch.undo()
    jax.config.update("jax_enable_compilation_cache", False)


def test_the_suite_runs_on_a_cache_of_the_runs_own():
    """A fresh directory the process that started the run made, taken from
    the environment by its workers and children; every program goes in."""
    import tempfile

    where = os.environ[compile_cache.ENV_VAR]
    assert os.path.dirname(where) == tempfile.gettempdir()
    assert os.path.isdir(where)
    assert jax.config.jax_enable_compilation_cache is True
    assert jax.config.jax_compilation_cache_dir == where
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert compile_cache.enable("/a/trainers/preference") == where


@pytest.mark.usefixtures("compile_cache_off")
def test_suite_runs_with_the_cache_off():
    """Under ``compile_cache_off``, as the whole suite ran before PR 44."""
    assert jax.config.jax_enable_compilation_cache is False
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert compile_cache.enable("/anywhere") is None


def test_env_var_decides_and_nothing_is_set_in_code(cache_on, monkeypatch,
                                                    tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    # a caller's preference never overrides the environment either
    assert compile_cache.enable(str(tmp_path / "mine")) == "/some/dir"
    assert cache_on == []
    assert os.environ[compile_cache.ENV_VAR] == "/some/dir"
    assert not (tmp_path / "mine").exists()


def test_unset_gives_the_fixed_in_checkout_directory(cache_on, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first, second = compile_cache.enable(), compile_cache.enable()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert cache_on == [("jax_compilation_cache_dir", first)] * 2
    assert compile_cache.ENV_VAR not in os.environ


def test_preference_replaces_only_the_default(cache_on, monkeypatch,
                                              tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    mine = str(tmp_path / "mine")
    assert compile_cache.enable(mine) == mine
    assert os.path.isdir(mine)
    assert cache_on == [("jax_compilation_cache_dir", mine)]


def test_two_processes_agree_on_the_directory():
    code = ("from pytorch_distributed_train_tpu.utils import compile_cache;"
            "import jax; print(compile_cache.enable());"
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env["PYTHONPATH"] = REPO
    outs = [subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120,
                           check=True).stdout.split()
            for cwd in (REPO, os.path.join(REPO, "tests"))]
    want = os.path.join(REPO, ".jax_cache")
    assert outs[0] == outs[1] == [want, want]
    env[compile_cache.ENV_VAR] = "/placed/from/outside"
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == ["/placed/from/outside"] * 2  # JAX read it itself


@pytest.mark.parametrize("configured,launcher,rank,want", [
    ("", "", None, ""),
    ("/cfg", "", None, "/cfg"),
    ("/cfg", "", "3", "/cfg/worker_3"),
    ("", "/base/worker_1", "1", "/base/worker_1"),
    ("/cfg", "/base/worker_1", "1", "/cfg/worker_1"),
    ("", "", "2", os.path.join(REPO, ".jax_cache", "worker_2")),
])
def test_trainer_preference_keeps_workers_apart(monkeypatch, configured,
                                                launcher, rank, want):
    from pytorch_distributed_train_tpu import trainer

    for name, value in (("PDTT_COMPILE_CACHE_DIR", launcher),
                        ("PROCESS_ID", rank)):
        if value:
            monkeypatch.setenv(name, value)
        else:
            monkeypatch.delenv(name, raising=False)
    assert trainer._compile_cache_preference(configured) == want


def test_no_other_code_sets_the_cache_directory():
    hits = []
    for root in ("pytorch_distributed_train_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(REPO, f) for f in
             ("train.py", "bench.py", "tpurun.py", "chip_smoke.py",
              "__graft_entry__.py")]
    setters = [os.path.relpath(p, REPO) for p in hits
               if "jax_compilation_cache_dir" in open(p).read()]
    assert setters == [os.path.join("pytorch_distributed_train_tpu",
                                    "utils", "compile_cache.py")]
