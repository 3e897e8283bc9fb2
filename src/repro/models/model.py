"""Top-level model API: init / loss / serve, uniform across families.

``init_params(cfg, rng)``        -> param pytree (real arrays)
``init_adapters(cfg, rng)``      -> LoRA adapter pytree (float32), and
``merge_adapters(base, adapters)`` the full tree the forward reads
``param_axes(cfg)``              -> parallel pytree of logical-axis tuples
``abstract_params(cfg, dtype)``  -> ShapeDtypeStruct pytree (no allocation)
``loss_fn(cfg)(params, batch)``  -> (loss, metrics)  [train objective]
``prefill_fn(cfg)``, ``decode_fn(cfg)`` for serving.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.cnn import cnn_forward, init_cnn
from repro.models.params import AxesOnly, ParamFactory, RealInit, ShapeOnly
from repro.models.transformer import NULL_CTX, ShardCtx


def _init(cfg: ModelConfig, fac: ParamFactory):
    if cfg.family == "cnn":
        return init_cnn(fac, cfg)
    return tfm.init_lm(fac, cfg)


def init_params(cfg: ModelConfig, rng: Optional[jax.Array] = None):
    rng = rng if rng is not None else jax.random.key(0)
    return _init(cfg, RealInit(rng, jnp.dtype(cfg.param_dtype)))


def init_adapters(cfg: ModelConfig, rng: Optional[jax.Array] = None):
    """float32 rank-``cfg.lora_rank`` LoRA adapters of every MLA projection."""
    rng = rng if rng is not None else jax.random.key(0)
    return tfm.init_lora(RealInit(rng, jnp.float32), cfg)


def merge_adapters(base, adapters):
    """The frozen base with the adapters in place: what ``loss_fn`` reads
    (without a base, ``adapters`` are the whole model)."""
    return adapters if base is None else tfm.merge_lora(base, adapters)


def param_axes(cfg: ModelConfig):
    return _init(cfg, AxesOnly())


def abstract_params(cfg: ModelConfig, dtype=None):
    return _init(cfg, ShapeOnly(jnp.dtype(dtype or cfg.param_dtype)))


def num_params(params) -> int:
    return sum(int(jnp.size(p)) if hasattr(p, "size") else 0
               for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Objectives
# ---------------------------------------------------------------------------

def _xent(logits, labels, ignore: int = -100):
    """Token cross-entropy with label masking. logits (B,S,V), labels (B,S)."""
    valid = labels != ignore
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32), safe[..., None],
                               axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def loss_fn(cfg: ModelConfig, ctx: ShardCtx = NULL_CTX, remat: str = "block"):
    """Returns fn(params, batch) -> (loss, metrics)."""
    if cfg.family == "cnn":
        def cnn_loss(params, batch):
            logits = cnn_forward(params, batch["images"])
            labels = batch["labels"]
            ll = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.take_along_axis(ll, labels[:, None], axis=-1).mean()
            acc = (logits.argmax(-1) == labels).mean()
            return loss, {"loss": loss, "acc": acc}
        return cnn_loss

    def lm_loss(params, batch):
        logits, aux, load = tfm.forward_train(params, cfg, batch, ctx,
                                              remat=remat)
        loss = _xent(logits, batch["labels"]) + aux
        metrics = {"loss": loss, "aux": aux}
        if load is not None:
            metrics["expert_load"] = load
        return loss, metrics

    return lm_loss


def predict_fn(cfg: ModelConfig, ctx: ShardCtx = NULL_CTX):
    """Forward producing logits (no loss) — used by prefill shape + MIA eval."""
    if cfg.family == "cnn":
        return lambda params, batch: cnn_forward(params, batch["images"])

    def fwd(params, batch):
        logits, _, _ = tfm.forward_train(params, cfg, batch, ctx, remat="none")
        return logits

    return fwd


def prefill_fn(cfg: ModelConfig, ctx: ShardCtx = NULL_CTX,
               max_len: Optional[int] = None):
    return functools.partial(_prefill, cfg, ctx, max_len)


def _prefill(cfg, ctx, max_len, params, batch):
    return tfm.forward_prefill(params, cfg, batch, ctx, max_len=max_len)


def decode_fn(cfg: ModelConfig, ctx: ShardCtx = NULL_CTX):
    def step(params, tokens, cache):
        return tfm.forward_decode(params, cfg, tokens, cache, ctx)
    return step


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None,
               enc_len: int = 0):
    return tfm.init_cache(cfg, batch, cache_len,
                          dtype=jnp.dtype(dtype or cfg.compute_dtype),
                          enc_len=enc_len)
