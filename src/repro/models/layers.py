"""Shared layers: norms, gated MLP, embeddings, RoPE."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamFactory

ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def pad_vocab(vocab: int, multiple: int = 512) -> int:
    """Pad vocab to a mesh/MXU-friendly multiple (production-style)."""
    return ((vocab + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(fac: ParamFactory, cfg: ModelConfig, name: str):
    if cfg.norm_type == "nonparametric":
        return {}
    return {"scale": fac.param(f"{name}.scale", (cfg.d_model,), ("embed",), init="ones")}


def apply_norm(p, x, cfg: ModelConfig):
    eps = cfg.norm_eps
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm" or cfg.norm_type == "nonparametric":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
    if p:
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------

def init_mlp(fac: ParamFactory, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    with fac.scope("mlp"):
        return {
            "wi_gate": fac.param("wi_gate", (cfg.d_model, d_ff), ("embed", "mlp")),
            "wi_up": fac.param("wi_up", (cfg.d_model, d_ff), ("embed", "mlp")),
            "wo": fac.param("wo", (d_ff, cfg.d_model), ("mlp", "embed")),
        }


def apply_mlp(p, x, cfg: ModelConfig):
    act = ACTS[cfg.act]
    h = act(x @ p["wi_gate"]) * (x @ p["wi_up"])
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(fac: ParamFactory, cfg: ModelConfig):
    v = pad_vocab(cfg.vocab_size)
    with fac.scope("embed"):
        p = {"table": fac.param("table", (v, cfg.d_model), ("vocab", "embed"), scale=1.0)}
        if not cfg.tie_embeddings:
            p["unembed"] = fac.param("unembed", (cfg.d_model, v), ("embed", "vocab"))
    return p


# Largest number of table rows the lookup reads as a one-hot matmul; a larger
# vocabulary gathers, since the one-hot's size grows with it.
ONEHOT_MAX_ROWS = 1024


def apply_embed(p, tokens, cfg: ModelConfig):
    """``table[tokens]``.

    With a small real vocabulary (``V' = round_up(vocab_size, 128) <=
    ONEHOT_MAX_ROWS``) and a float32 table, the lookup is a one-hot matmul
    on the MXU over the first ``V'`` rows (``onehot_lookup``) and so is its
    gradient, in place of a gather and a scatter-add that the TPU runs row
    by row.  Valid tokens (``< vocab_size``) read exactly what ``jnp.take``
    reads; rows from ``V'`` on get an exactly zero gradient, as valid tokens
    never index them.  Otherwise the table is gathered.
    """
    table = p["table"]
    rows = -(-cfg.vocab_size // 128) * 128
    if rows <= ONEHOT_MAX_ROWS and table.dtype == jnp.float32:
        with jax.named_scope("embed.onehot"):
            return onehot_lookup(table[:rows], tokens)
    with jax.named_scope("embed.gather"):
        return jnp.take(table, tokens, axis=0)


def _bf16_split(x):
    """``[hi, mid, lo]``, bfloat16, whose float32 sum ``(hi + mid) + lo`` is
    the float32 ``x`` exactly.  ``hi`` keeps the top 8 bits of ``x``'s
    24-bit significand (sign, exponent and 7 stored bits, by masking); the
    rest ``x - hi`` is exact in float32, and ``mid`` keeps its top 8
    significant bits the same way; what is left spans at most 8 bits and is
    ``lo``, exact in bfloat16.  Both sums are truncations of ``x``, so exact.
    (Masking and not rounding leaves no conversion pair a compiler could
    fold away.)  Entries below about 2**-100 in magnitude, whose ``lo``
    would fall under bfloat16's normal range, are the exception."""
    def head(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    hi = head(x)
    rest = x - hi
    mid = head(rest)
    return [part.astype(jnp.bfloat16) for part in (hi, mid, rest - mid)]


def _onehot_dot(onehot, x, contract):
    """``onehot`` contracted over ``contract`` (its axes, then ``x``'s) with
    ``x``'s three-way bfloat16 split side by side, accumulated in float32,
    and the three column groups summed back."""
    out = jax.lax.dot_general(onehot, jnp.concatenate(_bf16_split(x), -1),
                              (contract, ((), ())),
                              preferred_element_type=jnp.float32)
    hi, mid, lo = jnp.split(out, 3, axis=-1)
    return (hi + mid) + lo


@jax.custom_vjp
def onehot_lookup(table, tokens):
    """``table[tokens]`` for a float32 ``table`` of ``rows`` rows and tokens
    in ``[0, rows)`` (a token outside reads zeros): one bfloat16 matmul of
    ``one_hot(tokens, rows)`` against ``[hi | mid | lo]``, the table's exact
    three-way split (``_bf16_split``), accumulated in float32, then the
    three column groups summed.  The one-hot is exact in bfloat16, a product
    of bfloat16s is exact in float32, and each output element receives one
    nonzero product, so each group is its part exactly and their sum is the
    table's row bit for bit.  One matmul three parts wide, where
    ``precision=HIGHEST`` would take six passes.

    The VJP is the transposed matmul: ``d_table = one_hot^T @ [dx_hi |
    dx_mid | dx_lo]``, accumulated in float32 and summed over the groups.
    Each product is again exact, so this is the scatter-add's sum of each
    token's cotangent row in another order: equal to float32 round-off.
    (Plain autodiff of the forward would round the cotangent to bfloat16.)
    ``tokens`` gets no gradient."""
    return _onehot_lookup_fwd(table, tokens)[0]


def _onehot_lookup_fwd(table, tokens):
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=jnp.bfloat16)
    return _onehot_dot(onehot, table, ((onehot.ndim - 1,), (0,))), onehot


def _onehot_lookup_bwd(onehot, d_out):
    lead = tuple(range(onehot.ndim - 1))
    return _onehot_dot(onehot, d_out, (lead, lead)), None


onehot_lookup.defvjp(_onehot_lookup_fwd, _onehot_lookup_bwd)


def apply_unembed(p, x, cfg: ModelConfig):
    v = pad_vocab(cfg.vocab_size)
    # float32 logits: a low-precision compute dtype keeps its float32
    # accumulation here, for the softmax and the loss
    w = p["table"].T if cfg.tie_embeddings else p["unembed"]
    logits = jnp.matmul(x, w, preferred_element_type=jnp.promote_types(
        jnp.result_type(x, w), jnp.float32))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    # mask padded vocab entries
    mask = jnp.arange(v) < cfg.vocab_size
    return jnp.where(mask, logits, -1e9)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., seq, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
