"""Train steps: the paper's Duplex regime (frozen backbone + reversible
branch) as the first-class path, plus the full-finetune baseline (paper's
FI/FR comparison arm).

Duplex step dataflow (paper Fig 9):
  1. backbone forward in bf16 under stop_gradient, collecting per-superblock
     taps — XLA stores no backbone residuals;
  2. reversible branch over pooled streams (O(1) residuals, custom_vjp);
  3. correction added to backbone hidden; frozen unembedding produces logits;
  4. gradients/optimizer touch ONLY the branch params (tiny optimizer state).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.common import ModelConfig
from repro.core import duplex as dx
from repro.models import layers as L
from repro.obs import runtime
from repro.optim import (AdamWConfig, OptConfig, SGDConfig, opt_init,
                         opt_update)
from repro.train.losses import lm_cross_entropy
from repro.utils import cast_tree

runtime.record_compiles()


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    mode: str = "duplex"                   # duplex | full
    duplex: dx.DuplexConfig = dx.DuplexConfig()
    opt: OptConfig = SGDConfig()
    lr: float = 1e-3
    lr_schedule: Callable | None = None    # step → lr (overrides .lr)
    z_loss: float = 1e-4
    aux_weight: float = 1e-2               # MoE load-balance weight (full mode)
    microbatch: int = 1                    # gradient-accumulation splits
    backbone_dtype: jnp.dtype = jnp.bfloat16   # frozen storage precision


def tap_indices(n_layers: int, n_blocks: int) -> np.ndarray:
    """Evenly spaced layers of the scanned stack feeding the branch blocks."""
    if n_layers <= 0:
        raise ValueError("backbone has no scanned layers to tap")
    return np.round(np.linspace(0, n_layers - 1, n_blocks)).astype(np.int32)


def tapped_layers(cfg: ModelConfig) -> int:
    """Layers of the scanned stack, which the branch may tap."""
    return cfg.n_rep * len(cfg.pattern)


def init_state(key: jax.Array, entry, cfg: ModelConfig, tcfg: TrainConfig,
               policy: L.Policy = L.Policy()) -> dict:
    kb, kd = jax.random.split(key)
    backbone = entry.module.init_params(kb, cfg)
    if tcfg.mode == "duplex":
        backbone = cast_tree(backbone, tcfg.backbone_dtype)  # frozen → bf16
        branch = dx.duplex_init(kd, tcfg.duplex, cfg.d_model)
        opt = opt_init(tcfg.opt, branch)
        return {"step": jnp.zeros((), jnp.int32), "backbone": backbone,
                "branch": branch, "opt": opt}
    opt = opt_init(tcfg.opt, backbone)
    return {"step": jnp.zeros((), jnp.int32), "backbone": backbone,
            "opt": opt}


def _lr(tcfg: TrainConfig, step):
    if tcfg.lr_schedule is not None:
        return tcfg.lr_schedule(step)
    return jnp.full((), tcfg.lr, jnp.float32)


def _microbatches(batch: dict, k: int) -> dict:
    return jax.tree_util.tree_map(
        lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)


def make_train_step(entry, cfg: ModelConfig, tcfg: TrainConfig,
                    policy: L.Policy = L.Policy()):
    """Returns ``train_step(state, batch) -> (state, metrics)``.

    batch: {"tokens" [B,S] int32, "labels" [B,S] int32, optional "mask",
    optional "frontend" dict of stub embeddings}.
    """
    module = entry.module

    if tcfg.mode == "duplex":
        idx = tap_indices(tapped_layers(cfg), tcfg.duplex.n_blocks)

        def loss_fn(branch, backbone, batch):
            fe = batch.get("frontend")
            kw = {} if fe is None else {"frontend": fe}
            out = module.forward(backbone, cfg, batch["tokens"],
                                 collect_taps=True, tap_indices=idx,
                                 tap_pool=tcfg.duplex.pool_factor,
                                 policy=policy, **kw)
            taps = out["taps"]               # [n_blocks,B,S/pool,D] pooled
            corr = dx.duplex_apply(branch, tcfg.duplex, out["emb"], taps,
                                   policy=policy, taps_pooled=True)
            hidden = jax.lax.stop_gradient(out["hidden"]) + corr
            with jax.named_scope("unembed_loss"):
                logits = module.lm_logits(backbone, cfg, hidden, policy)
                loss, metrics = lm_cross_entropy(logits, batch["labels"],
                                                 batch.get("mask"),
                                                 z_loss=tcfg.z_loss)
            return loss, metrics

        trainable = "branch"
    else:
        def loss_fn(backbone, _unused, batch):
            fe = batch.get("frontend")
            kw = {} if fe is None else {"frontend": fe}
            out = module.forward(backbone, cfg, batch["tokens"],
                                 policy=policy, **kw)
            with jax.named_scope("unembed_loss"):
                logits = module.lm_logits(backbone, cfg, out["hidden"],
                                          policy)
                loss, metrics = lm_cross_entropy(logits, batch["labels"],
                                                 batch.get("mask"),
                                                 z_loss=tcfg.z_loss)
            loss = loss + tcfg.aux_weight * out["aux"]
            return loss, metrics

        trainable = "backbone"

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        frozen = state["backbone"] if tcfg.mode == "duplex" else None

        if tcfg.microbatch > 1:
            mb = _microbatches(batch, tcfg.microbatch)

            def acc_body(carry, mbatch):
                gacc, lacc = carry
                (loss, metrics), g = grad_fn(state[trainable], frozen, mbatch)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                return (gacc, lacc + loss), metrics

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state[trainable])
            (gsum, lsum), ms = jax.lax.scan(
                acc_body, (zeros, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree_util.tree_map(
                lambda g: g / tcfg.microbatch, gsum)
            metrics = jax.tree_util.tree_map(jnp.mean, ms)
        else:
            (loss, metrics), grads = grad_fn(state[trainable], frozen, batch)

        lr = _lr(tcfg, state["step"])
        with jax.named_scope("optimizer"):
            new_p, new_opt, om = opt_update(tcfg.opt, grads, state["opt"],
                                            state[trainable], lr)
        new_state = dict(state)
        new_state[trainable] = new_p
        new_state["opt"] = new_opt
        new_state["step"] = state["step"] + 1
        return new_state, {**metrics, **om, "lr": lr}

    return train_step
