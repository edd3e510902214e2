"""train.py CLI end-to-end on the CPU harness: train → checkpoint →
eval-only restore (the reference's validate() mode)."""

import os
import sys

import pytest
from tiny import set_flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _overrides(tmp_path):
    return [
        "--set", "data.dataset=synthetic_images",
        "--set", "data.synthetic_size=256",
        "--set", "data.batch_size=32",
        "--set", "data.eval_batch_size=32",
        "--set", "obs.log_every_steps=2",
        "--set", f"checkpoint.dir={tmp_path}/ck",
        "--set", "checkpoint.save_every_steps=4",
        "--set", "checkpoint.async_save=false",
    ]


def _tiny(tmp_path):
    """The tiny trainer under the preset, for the tests whose contract is
    the CLI's control flow and not ResNet-18's shapes."""
    return [*set_flags(), *_overrides(tmp_path)]


def test_train_then_eval_only(tmp_path, capfd):
    sys.path.insert(0, REPO)
    import train

    rc = train.main(["--config", "resnet18_cifar10", "--steps", "4",
                     *_tiny(tmp_path)])
    assert rc == 0
    out = capfd.readouterr().out
    assert "[train] step=4" in out

    rc = train.main(["--config", "resnet18_cifar10", "--eval-only",
                     "--resume", "auto", *_tiny(tmp_path)])
    assert rc == 0
    out = capfd.readouterr().out
    assert "[resume] restored step 4" in out
    assert "[eval]" in out and "accuracy=" in out


def test_eval_only_refuses_random_init(tmp_path, capfd):
    sys.path.insert(0, REPO)
    import train

    rc = train.main(["--config", "resnet18_cifar10", "--eval-only",
                     "--resume", "auto", *_tiny(tmp_path)])
    assert rc == 2
    assert "refusing to validate" in capfd.readouterr().err


def test_show_sharding_tool():
    """tools/show_sharding.py prints the resolved partition table."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "show_sharding.py"),
         "--config", "gpt2_small", "--devices", "8",
         "--set", "mesh.data=2", "--set", "mesh.fsdp=4", "--top", "3"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "wte/embedding" in out.stdout
    assert "'fsdp'" in out.stdout
    assert "MB/device" in out.stdout


def test_bad_config_is_one_line_error_exit_2(capfd):
    import train as train_mod

    assert train_mod.main(["--config", "nope"]) == 2
    err = capfd.readouterr().err
    assert "unknown preset" in err and "Traceback" not in err

    assert train_mod.main(["--set", "optim.nope=1"]) == 2
    err = capfd.readouterr().err
    assert "optim.nope" in err and "Traceback" not in err


def test_generate_cli_end_to_end(tmp_path, capfd):
    """Export tiny-llama weights via the interop bridge, then drive the
    generation CLI: byte tokenizer, greedy decode, int8 path."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.interop import save_torch_safetensors
    from pytorch_distributed_train_tpu.models.registry import build_model

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate_cli

    shrink = ["model.vocab_size=300", "model.hidden_size=64",
              "model.num_layers=2", "model.num_heads=4",
              "model.num_kv_heads=4", "model.mlp_dim=128",
              "model.max_seq_len=64", "model.fused_lm_loss=false",
              "model.remat=false"]
    cfg = get_preset("llama2_7b")
    cfg.apply_overrides(shrink)
    model = build_model(cfg.model, cfg.precision)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 2), jnp.int32), train=False)["params"]
    st = tmp_path / "weights.st"
    save_torch_safetensors(params, str(st))

    rc = generate_cli.main(
        ["--config", "llama2_7b", "--safetensors", str(st),
         "--prompt", "hello", "--prompt", "world!",
         "--max-new-tokens", "6"]
        + [f"--set={s}" for s in shrink])
    out = capfd.readouterr().out
    assert rc == 0, out
    assert "prompt 0: 'hello'" in out and "prompt 1: 'world!'" in out

    rc = generate_cli.main(
        ["--config", "llama2_7b", "--safetensors", str(st),
         "--prompt", "hi", "--max-new-tokens", "4", "--quantize", "int8"]
        + [f"--set={s}" for s in shrink])
    assert rc == 0
    assert "prompt 0" in capfd.readouterr().out

    # continuous batching: greedy serving output == lockstep output
    rc = generate_cli.main(
        ["--config", "llama2_7b", "--safetensors", str(st),
         "--prompt", "hello", "--prompt", "world!",
         "--max-new-tokens", "6", "--serve-slots", "2"]
        + [f"--set={s}" for s in shrink])
    served = capfd.readouterr().out
    assert rc == 0, served

    def blocks(text):
        """(header, full-completion) pairs, order-independent — the
        completion spans every line until the next header (byte-tokenizer
        output can itself contain newlines)."""
        out, cur = {}, None
        for line in text.splitlines():
            if line.startswith("=== prompt"):
                cur = line
                out[cur] = []
            elif cur is not None:
                out[cur].append(line)
        return sorted((h, "\n".join(b)) for h, b in out.items())

    assert blocks(served) == blocks(out)

    rc = generate_cli.main(
        ["--config", "llama2_7b", "--safetensors", str(st),
         "--prompt", "x", "--serve-slots", "2", "--num-beams", "2"]
        + [f"--set={s}" for s in shrink])
    assert rc == 2
    assert "serve-slots" in capfd.readouterr().err


def test_generate_cli_user_errors_one_line(tmp_path, capfd):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate_cli

    rc = generate_cli.main(["--safetensors", str(tmp_path / "nope.st"),
                            "--prompt", "x"])
    err = capfd.readouterr().err
    assert rc == 2 and "Traceback" not in err and "error" in err


def test_generate_cli_t5(tmp_path, capfd):
    """Seq2seq serving through the same CLI: t5 weights via the interop
    bridge, byte tokenizer, greedy + int8; --tp refused loudly."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.interop import save_torch_safetensors
    from pytorch_distributed_train_tpu.models.registry import build_model

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import generate_cli

    shrink = ["model.vocab_size=300", "model.hidden_size=32",
              "model.num_layers=2", "model.decoder_layers=2",
              "model.num_heads=4", "model.mlp_dim=64",
              "model.max_seq_len=64", "model.dropout_rate=0.0"]
    cfg = get_preset("t5_small")
    cfg.apply_overrides(shrink)
    model = build_model(cfg.model, cfg.precision)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1, 2), jnp.int32),
                        train=False)["params"]
    st = tmp_path / "t5.st"
    save_torch_safetensors(params, str(st))

    rc = generate_cli.main(
        ["--config", "t5_small", "--safetensors", str(st),
         "--prompt", "translate this", "--max-new-tokens", "5"]
        + [f"--set={s}" for s in shrink])
    out = capfd.readouterr().out
    assert rc == 0, out
    assert "prompt 0: 'translate this'" in out

    rc = generate_cli.main(
        ["--config", "t5_small", "--safetensors", str(st),
         "--prompt", "hi", "--max-new-tokens", "3", "--quantize", "int8"]
        + [f"--set={s}" for s in shrink])
    assert rc == 0
    assert "prompt 0" in capfd.readouterr().out

    rc = generate_cli.main(
        ["--config", "t5_small", "--safetensors", str(st),
         "--prompt", "hi", "--max-new-tokens", "4", "--num-beams", "2"]
        + [f"--set={s}" for s in shrink])
    assert rc == 0
    assert "prompt 0" in capfd.readouterr().out

    # continuous batching serves t5 too; greedy == lockstep
    rc = generate_cli.main(
        ["--config", "t5_small", "--safetensors", str(st),
         "--prompt", "translate this", "--max-new-tokens", "5",
         "--serve-slots", "2"]
        + [f"--set={s}" for s in shrink])
    served = capfd.readouterr().out
    assert rc == 0, served
    assert served == out

    rc = generate_cli.main(
        ["--config", "t5_small", "--safetensors", str(st),
         "--prompt", "hi", "--max-new-tokens", "3", "--tp", "2"]
        + [f"--set={s}" for s in shrink])
    assert rc == 2
    assert "t5 serving" in capfd.readouterr().err


def test_chat_cli_multi_turn(tmp_path, capfd, monkeypatch):
    """Scripted REPL session: two turns share one KV session (resumes=1),
    /reset starts a fresh conversation forked off the system template."""
    import io

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.interop import save_torch_safetensors
    from pytorch_distributed_train_tpu.models.registry import build_model

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chat_cli

    shrink = ["model.vocab_size=300", "model.hidden_size=32",
              "model.num_layers=2", "model.num_heads=4",
              "model.num_kv_heads=4", "model.mlp_dim=64",
              "model.max_seq_len=96", "model.fused_lm_loss=false",
              "model.remat=false"]
    cfg = get_preset("llama2_7b")
    cfg.apply_overrides(shrink)
    model = build_model(cfg.model, cfg.precision)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 2), jnp.int32), train=False)["params"]
    st = tmp_path / "w.st"
    save_torch_safetensors(params, str(st))

    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO("hello\nsecond turn\n/stats\n/reset\nfresh\n/quit\n"))
    rc = chat_cli.main(
        ["--config", "llama2_7b", "--safetensors", str(st),
         "--system", "sys: ", "--max-new-tokens", "4",
         "--temperature", "0"] + [f"--set={s}" for s in shrink])
    out = capfd.readouterr().out
    assert rc == 0, out
    assert "system prompt preloaded" in out
    assert "'resumes': 1" in out      # turn 2 resumed turn 1's session
    assert "'forks': 1" in out  # /stats printed pre-reset: exactly one
    assert "[new conversation]" in out


def test_compile_only_memory_report(tmp_path, capfd):
    """--compile-only AOT-compiles the step and prints the per-device
    memory report without running a step (the 'will it fit' probe)."""
    import json as json_mod

    sys.path.insert(0, REPO)
    import train

    rc = train.main(["--config", "resnet18_cifar10", "--compile-only",
                     *_overrides(tmp_path)])
    assert rc == 0
    out = capfd.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("{"))
    rep = json_mod.loads(line)
    assert rep["compile_only"] is True
    assert rep["arg_bytes"] > 1_000_000  # resnet18 params + opt state
    assert rep["resident_bytes"] >= rep["arg_bytes"]
    assert "[train]" not in out  # no step ran


def test_find_batch_size_bisects_to_budget(tmp_path, capfd):
    """--find-batch-size probes the largest fitting GLOBAL batch via AOT
    memory accounting: doubles then bisects, never runs a step, honors
    an explicit budget, and a budget below the model's own footprint
    reports best 0 with rc 4."""
    import json as json_mod

    sys.path.insert(0, REPO)
    import train

    rc = train.main(["--config", "resnet18_cifar10", "--find-batch-size",
                     "--hbm-gb", "0.002", *_tiny(tmp_path)])
    assert rc == 0
    out = capfd.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("{"))
    rep = json_mod.loads(line)
    assert rep["find_batch_size"] is True
    assert rep["best_global"] > 0
    assert rep["best_per_chip"] == rep["best_global"] // 8  # 8 fake devs
    fits = {p["global_batch"]: p["fits"] for p in rep["probes"]}
    # monotone law: everything <= best fits, anything probed above fails
    assert all(f for g, f in fits.items() if g <= rep["best_global"])
    assert all(not f for g, f in fits.items() if g > rep["best_global"])
    assert "[train]" not in out  # no step ran

    # impossible budget: the configured batch itself does not fit
    rc = train.main(["--config", "resnet18_cifar10", "--find-batch-size",
                     "--hbm-gb", "0.000001", *_tiny(tmp_path)])
    assert rc == 4
    out = capfd.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("{"))
    assert json_mod.loads(line)["best_global"] == 0


def test_find_batch_size_takes_a_compiler_refusal_as_does_not_fit(
        tmp_path, monkeypatch):
    """On a TPU the compiler enforces the chip's memory itself: a batch
    past it is refused with RESOURCE_EXHAUSTED instead of reported (what
    `--find-batch-size` met on the v5e). That is an answer, not a crash;
    any other compiler error still propagates."""
    import jax

    sys.path.insert(0, REPO)
    import train
    from pytorch_distributed_train_tpu.trainer import Trainer

    trainer = Trainer(train.build_config(train.parse_args(
        ["--config", "resnet18_cifar10", *_tiny(tmp_path)])))
    try:
        def report(batch_size=None):
            if batch_size > 128:
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                    "Ran out of memory in memory space hbm.")
            return {"global_batch": batch_size,
                    "resident_bytes": batch_size}

        monkeypatch.setattr(trainer, "compile_report", report)
        rep = trainer.find_batch_size(budget_bytes=10**9)
        assert rep["best_global"] == 128
        refused = [p for p in rep["probes"] if "compiler_refused" in p]
        assert refused and not any(p["fits"] for p in refused)

        def broken(batch_size=None):
            raise jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed")

        monkeypatch.setattr(trainer, "compile_report", broken)
        with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
            trainer.find_batch_size(budget_bytes=10**9)
    finally:
        trainer.close()
