#!/usr/bin/env python
"""Text generation CLI — the serving-side entrypoint.

Loads model weights from a torch-layout safetensors file (the bridge
format: `python train.py --export-safetensors model.st` writes one from
any checkpoint; HF torch files of the same architecture import too),
tokenizes prompts (local HF tokenizer dir, or the asset-free byte
tokenizer), and runs KV-cache decode (generate.py) — optionally with
weight-only int8 (quant.py) and/or tensor-parallel over the local chips.

    python tools/generate_cli.py --config llama2_7b \
        --safetensors model.st --tokenizer /models/llama2-tok \
        --prompt "The capital of France is" --max-new-tokens 64 \
        --temperature 0.8 --top-k 40 [--quantize int8] [--tp 4]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="llama2_7b",
                   help="preset supplying the model architecture")
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="dotted config override (model.* mostly)")
    p.add_argument("--safetensors", required=True,
                   help="torch-layout safetensors weights (interop bridge)")
    p.add_argument("--tokenizer", default="",
                   help="local HF tokenizer dir; empty → byte tokenizer")
    p.add_argument("--prompt", action="append", default=[],
                   help="repeatable; '-' reads one prompt per stdin line")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=0.0,
                   help="nucleus sampling threshold in (0,1); 0 -> off")
    p.add_argument("--min-p", type=float, default=0.0,
                   help="min-p sampling: keep tokens with prob >= min_p "
                        "x max prob (entropy-adaptive; 0 -> off)")
    p.add_argument("--num-beams", type=int, default=0,
                   help="beam-search decoding; overrides temperature/"
                        "top-k/top-p/min-p (beams expand the full "
                        "distribution); 0 → off")
    p.add_argument("--repetition-penalty", type=float, default=1.0,
                   help="HF CTRL rule over prompt+generated (>1 "
                        "discourages repeats; 1 = off)")
    p.add_argument("--presence-penalty", type=float, default=0.0,
                   help="OpenAI additive penalty for any seen token")
    p.add_argument("--frequency-penalty", type=float, default=0.0,
                   help="OpenAI additive penalty x occurrence count")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--quantize", default="", choices=["", "int8", "int4"])
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel ways over local devices (0 → off)")
    p.add_argument("--serve-slots", type=int, default=0, metavar="SLOTS",
                   help="continuous batching (serving.ContinuousBatcher): "
                        "run ALL prompts concurrently through this many "
                        "cache slots instead of one lockstep generate() "
                        "per prompt; completions print as they finish "
                        "(causal + t5 families; 0 → off)")
    args = p.parse_args(argv)

    prompts = []
    for item in args.prompt or ["-"]:
        if item == "-":
            prompts.extend(line.rstrip("\n") for line in sys.stdin
                           if line.strip())
        else:
            prompts.append(item)
    if not prompts:
        print("generate_cli: no prompts", file=sys.stderr)
        return 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_train_tpu.config import get_preset
    from pytorch_distributed_train_tpu.data.text import load_tokenizer
    from pytorch_distributed_train_tpu.generate import (
        build_decode_model,
        generate,
        shard_decode_params,
    )
    from pytorch_distributed_train_tpu.utils import compile_cache

    compile_cache.enable()  # first thing: everything below compiles
    try:
        cfg = get_preset(args.config)
        cfg.apply_overrides(args.set)

        tok = load_tokenizer(args.tokenizer)
        encoded = [tok.encode(t) for t in prompts]
        if any(len(e) == 0 for e in encoded):
            raise ValueError("empty prompt after tokenization")

        model_cfg = cfg.model
        is_t5 = model_cfg.name.startswith("t5")
        # argument-compatibility refusals BEFORE the (potentially
        # tens-of-GB) weight load
        if is_t5 and args.tp > 1:
            raise ValueError(
                "--tp supports the causal-LM families; t5 serving is "
                "single-device for now")
        if args.num_beams >= 1 and args.tp > 1:
            raise ValueError(
                "--num-beams with --tp is unsupported (beam search "
                "drives the single-device step)")
        if args.serve_slots > 0 and args.num_beams >= 1:
            raise ValueError(
                "--serve-slots is continuous batching; it composes with "
                "sampling flags and --tp but not --num-beams")
        from pytorch_distributed_train_tpu.serving import (
            load_params_for_serving,
        )

        params = load_params_for_serving(cfg, args.safetensors,
                                         args.quantize)

        from pytorch_distributed_train_tpu.serving import trim_at_eos

        def emit(i, text, new):
            print(f"=== prompt {i}: {text!r}")
            print(tok.decode(trim_at_eos(new, tok.eos_id)))

        if is_t5:
            from pytorch_distributed_train_tpu.generate import (
                generate_seq2seq,
            )

            if args.serve_slots > 0:
                from pytorch_distributed_train_tpu.serving import (
                    Seq2SeqContinuousBatcher,
                )

                b = Seq2SeqContinuousBatcher(
                    model_cfg, cfg.precision, params,
                    slots=args.serve_slots, top_k=args.top_k,
                    top_p=args.top_p, min_p=args.min_p,
                    rng=jax.random.PRNGKey(args.seed))
                uid_to_i = {}
                for i, e in enumerate(encoded):
                    uid_to_i[b.submit(e, args.max_new_tokens,
                                      temperature=args.temperature,
                                      eos_id=tok.eos_id)] = i
                for c in b.run():
                    i = uid_to_i[c.uid]
                    emit(i, prompts[i], c.tokens)
                return 0

            for i, (text, e) in enumerate(zip(prompts, encoded)):
                ids = jnp.asarray(np.asarray(e, np.int32)[None, :])
                if args.num_beams >= 1:
                    from pytorch_distributed_train_tpu.generate import (
                        beam_search_seq2seq,
                    )

                    seqs, _ = beam_search_seq2seq(
                        model_cfg, cfg.precision, params, ids,
                        args.max_new_tokens, num_beams=args.num_beams,
                        eos_id=tok.eos_id)
                    out = np.asarray(seqs)
                else:
                    out = np.asarray(generate_seq2seq(
                        model_cfg, cfg.precision, params, ids,
                        args.max_new_tokens, temperature=args.temperature,
                        top_k=args.top_k, top_p=args.top_p,
                        min_p=args.min_p,
                        rng=jax.random.PRNGKey(args.seed + i),
                        eos_id=tok.eos_id))
                emit(i, text, out[0].tolist())
            return 0

        if args.serve_slots > 0:
            from pytorch_distributed_train_tpu.serving import (
                ContinuousBatcher,
            )

            serve_mesh = None
            if args.tp > 1:
                from pytorch_distributed_train_tpu.config import MeshConfig
                from pytorch_distributed_train_tpu.parallel.mesh import (
                    build_mesh,
                )

                serve_mesh = build_mesh(
                    MeshConfig(tensor=args.tp, data=1, fsdp=1))
                params = shard_decode_params(model_cfg.name, serve_mesh,
                                             params)
            b = ContinuousBatcher(
                model_cfg, cfg.precision, params,
                slots=args.serve_slots, top_k=args.top_k,
                top_p=args.top_p, min_p=args.min_p,
                rng=jax.random.PRNGKey(args.seed), mesh=serve_mesh)
            uid_to_i = {}
            for i, e in enumerate(encoded):
                uid_to_i[b.submit(
                    e, args.max_new_tokens,
                    temperature=args.temperature, eos_id=tok.eos_id,
                    repetition_penalty=args.repetition_penalty,
                    presence_penalty=args.presence_penalty,
                    frequency_penalty=args.frequency_penalty)] = i
            for c in b.run():
                i = uid_to_i[c.uid]
                emit(i, prompts[i], c.tokens)
            return 0

        model = build_decode_model(model_cfg, cfg.precision)
        mesh = None
        if args.tp > 1:
            from pytorch_distributed_train_tpu.config import MeshConfig
            from pytorch_distributed_train_tpu.parallel.mesh import build_mesh

            mesh = build_mesh(MeshConfig(tensor=args.tp, data=1, fsdp=1))
            params = shard_decode_params(model_cfg.name, mesh, params)

        # One generation per prompt: the decoder has no padding mask, so
        # batching mixed-length prompts with left-pad would let pad tokens
        # leak into attention (and shift RoPE positions). Equal-shape calls
        # reuse the same compiled executables.
        for i, (text, e) in enumerate(zip(prompts, encoded)):
            ids = jnp.asarray(np.asarray(e, np.int32)[None, :])
            if args.num_beams >= 1:  # 1 == greedy via the beam machinery
                from pytorch_distributed_train_tpu.generate import (
                    beam_search,
                )

                seqs, _ = beam_search(
                    model, params, ids, args.max_new_tokens,
                    num_beams=args.num_beams, eos_id=tok.eos_id)
                out = np.asarray(seqs)
            else:
                out = np.asarray(generate(
                    model, params, ids, args.max_new_tokens,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p, min_p=args.min_p,
                    rng=jax.random.PRNGKey(args.seed + i),
                    eos_id=tok.eos_id, mesh=mesh,
                    repetition_penalty=args.repetition_penalty,
                    presence_penalty=args.presence_penalty,
                    frequency_penalty=args.frequency_penalty))
            emit(i, text, out[0, len(e):].tolist())
        return 0
    except (KeyError, ValueError, FileNotFoundError, OSError) as e:
        # User-input mistakes (unknown preset, typo'd --set, missing or
        # foreign weights file, prompt longer than max_seq_len, bad --tp)
        # print one clear line and exit 2 — same contract as train.py.
        print(f"generate_cli: error: {e.args[0] if e.args else e}",
              file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
