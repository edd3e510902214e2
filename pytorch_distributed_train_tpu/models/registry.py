"""Model registry: config name → Flax module (SURVEY H3, §7.2 `models/`).

The reference selects its model from config ("ResNet/ViT ... behind the same
config and checkpoint interface", BASELINE.json:5); this is the same switch,
plus the BERT/Llama rows of the acceptance matrix.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp

_REGISTRY: dict[str, Callable] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def _populate():
    if _REGISTRY:
        return
    from pytorch_distributed_train_tpu.models import bert, llama, resnet, vit

    from pytorch_distributed_train_tpu.models import gpt2 as gpt2_mod

    _REGISTRY.update(
        {
            "resnet18": resnet.resnet18,
            "resnet50": resnet.resnet50,
            "vit_b16": vit.vit_b16,
            "bert_base": bert.bert_base,
            "llama": llama.llama,
            "gpt2": gpt2_mod.gpt2,
        }
    )
    from pytorch_distributed_train_tpu.models import pipeline_lm, t5

    _REGISTRY["llama_pp"] = pipeline_lm.llama_pp
    _REGISTRY["t5"] = t5.t5
    from pytorch_distributed_train_tpu.models import hybrid

    _REGISTRY["hybrid_lm"] = hybrid.hybrid_lm


def list_models() -> list[str]:
    _populate()
    return sorted(_REGISTRY)


def build_model(model_cfg, precision_cfg, mesh=None, mesh_cfg=None):
    """Build the Flax module for a ModelConfig under a PrecisionConfig.

    ``mesh`` + ``mesh_cfg`` activate context parallelism: when the mesh's
    context axis is >1 the transformer models route attention through
    ring/Ulysses (SURVEY §5.7) and constrain activations seq-sharded.
    Any mesh of more than one device hands attention its axes, so the
    Pallas kernel can run as a manual region inside the sharded program
    (ops/attention.py _sharded_flash).
    """
    _populate()
    name = model_cfg.name
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {list_models()}")
    # ModelConfig.attention_impl is threaded into the modules as a static
    # attr (attn_impl) by each model ctor — no process-global state, so two
    # models with different backends coexist in one process.
    dtype = jnp.dtype(precision_cfg.compute_dtype)
    param_dtype = jnp.dtype(precision_cfg.param_dtype)
    cp = None
    if mesh is not None and mesh_cfg is not None and mesh.size > 1:
        from pytorch_distributed_train_tpu.ops.attention import (
            ContextParallelConfig,
        )

        cp = ContextParallelConfig(
            mesh=mesh,
            impl=mesh_cfg.context_impl,
            layout=mesh_cfg.context_layout,
            batch_axes=tuple(mesh_cfg.batch_axes),
        )
    if name == "llama_pp":
        if mesh is None:
            raise ValueError("model 'llama_pp' needs a mesh (stage axis)")
        # the pipeline body is already a manual region over 'stage': it
        # takes the mesh axes only for an active context axis
        return _REGISTRY[name](model_cfg, dtype, param_dtype, mesh=mesh,
                               cp=cp if cp is not None and cp.active else None)
    if name.startswith(("llama", "bert", "gpt", "hybrid")):
        from pytorch_distributed_train_tpu.parallel.mesh import (
            activation_sharding_for,
        )

        act = activation_sharding_for(mesh, mesh_cfg)
        return _REGISTRY[name](model_cfg, dtype, param_dtype, cp=cp, act=act)
    return _REGISTRY[name](model_cfg, dtype, param_dtype, cp=cp)


def is_language_model(name: str) -> bool:
    return name.startswith(("bert", "llama", "gpt", "t5", "hybrid"))
