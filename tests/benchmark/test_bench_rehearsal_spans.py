"""The runner's tiny CPU rehearsal of the decoder cell, traced, prints the
five per-layer metrics that read the program's spans, as files and manifest
entries only (run.py is as it was). On the CPU the step runs inside its
dispatch call, so the loader is made slow here: a step's wall time is then
many times its dispatch, as it is on the chip, where the device sets it."""

import time

from bench_helpers import RESULT_KEYS, load_run, rehearse

CELL = "gpt2s-1chip-b16"
SPAN_METRICS = {"trainer_host_ms_per_step.tokens": "ms",
                "step_dispatch_ms.tokens": "ms",
                "setup_trainer_init_s": "s", "setup_step_compile_s": "s",
                "setup_first_log_s": "s"}
LOADER_S = 0.03


def test_traced_rehearsal_prints_the_five_span_metrics(capfd, monkeypatch,
                                                       tmp_path):
    from pytorch_distributed_train_tpu import trainer as trainer_mod

    class SlowLoader(trainer_mod.Trainer):
        def __init__(self, cfg, mesh=None):
            super().__init__(cfg, mesh)
            epoch_fn = self.train_epoch_fn

            def slow(*args, **kwargs):
                for batch in epoch_fn(*args, **kwargs):
                    time.sleep(LOADER_S)
                    yield batch

            self.train_epoch_fn = slow

    monkeypatch.setattr(trainer_mod, "Trainer", SlowLoader)
    # a work directory of its own: test_bench_rehearsal_lm.py rehearses the
    # same cell, and under --dist loadfile the two files may run at once
    run = load_run()
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    last, lines = rehearse(capfd, monkeypatch, CELL, trace=1, run=run)
    assert set(last) == RESULT_KEYS | {"breakdown"}
    assert last["correct"] is True
    got = last["metrics"]
    assert {k: got[k]["unit"] for k in SPAN_METRICS} == SPAN_METRICS
    # the metrics that were there are still there
    assert {"input_wait_pct.tokens", "step_device_ms.tokens",
            "device_idle_pct.tokens"} <= set(got)
    value = {k: got[k]["value"] for k in SPAN_METRICS}
    assert all(v > 0 for v in value.values())
    # the loader's sleep is a child of the turn (train.input_wait), not the
    # loop's own time; and a free dispatch is under half a step (the rule)
    assert value["trainer_host_ms_per_step.tokens"] < 1e3 * LOADER_S
    steps = next(ln for ln in lines if "steps" in ln)["steps"]
    assert (value["step_dispatch_ms.tokens"]
            < 0.5 * 1e3 * steps["window_s"] / steps["count"])
    setup = next(ln for ln in lines if "setup" in ln)["setup"]
    parts = (value["setup_trainer_init_s"] + value["setup_step_compile_s"]
             + value["setup_first_log_s"])
    assert parts < setup["setup_s"]
    assert value["setup_trainer_init_s"] < setup["trainer_built_s"]
