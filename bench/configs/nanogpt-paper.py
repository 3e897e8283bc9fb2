"""Plain reference of ``nanogpt-paper`` and its operation count.

The block follows the program's description of the model (see ``assumed`` in
``nanogpt-paper.json``): pre-norm layernorm (no bias, eps 1e-6), RoPE on
queries and keys, causal softmax attention, a gated MLP with tanh-GELU, a
final layernorm and an untied output projection over the vocabulary padded
to 512, whose padding is masked.  The weights are drawn from the seed as the
program draws them (a normal per parameter, keyed by the FNV-1a hash of the
parameter's path, scaled by 1/sqrt(fan-in)).  Nothing here imports the
program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())
_M = CONFIG["model"]


def _fnv1a(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h


def _normal(key, path, shape, fan_in, scale=1.0):
    k = jax.random.fold_in(key, _fnv1a(path))
    return jax.random.normal(k, shape, jnp.float32) * (scale / math.sqrt(fan_in))


def init(seed: int):
    """float32 weights of the stage seeded by ``seed``."""
    key = jax.random.key(seed)
    n, d, h, e = _M["num_layers"], _M["d_model"], _M["num_heads"], _M["head_dim"]
    f, v = _M["d_ff"], _M["padded_vocab"]
    ones = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
    return {
        "embed": {"table": _normal(key, "embed/table", (v, d), v),
                  "unembed": _normal(key, "embed/unembed", (d, v), d)},
        "stack": {"p0": {
            "ln1": {"scale": ones(n, d)},
            "attn": {"wq": _normal(key, "stack_p0/attn/wq", (n, d, h, e), d),
                     "wk": _normal(key, "stack_p0/attn/wk", (n, d, h, e), d),
                     "wv": _normal(key, "stack_p0/attn/wv", (n, d, h, e), d),
                     "wo": _normal(key, "stack_p0/attn/wo", (n, h, e, d),
                                   h * e)},
            "ln2": {"scale": ones(n, d)},
            "ffn": {"wi_gate": _normal(key, "stack_p0/mlp/wi_gate", (n, d, f), d),
                    "wi_up": _normal(key, "stack_p0/mlp/wi_up", (n, d, f), d),
                    "wo": _normal(key, "stack_p0/mlp/wo", (n, f, d), f)},
        }},
        "rem": {},
        "final_ln": {"scale": ones(d)},
    }


def _layernorm(x, scale):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x):
    """x: (batch, seq, heads, head_dim); rotates the two halves."""
    e, s = x.shape[-1], x.shape[1]
    freqs = 1.0 / (_M["rope_theta"] ** (np.arange(0, e, 2) / e))
    ang = np.arange(s)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang)[:, None, :], x.dtype)
    sin = jnp.asarray(np.sin(ang)[:, None, :], x.dtype)
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(p, tokens):
    x = p["embed"]["table"][tokens]
    s = tokens.shape[1]
    causal = np.tril(np.ones((s, s), bool))
    blk = p["stack"]["p0"]
    for i in range(_M["num_layers"]):
        h = _layernorm(x, blk["ln1"]["scale"][i])
        q = _rope(jnp.einsum("bsd,dhe->bshe", h, blk["attn"]["wq"][i]))
        k = _rope(jnp.einsum("bsd,dhe->bshe", h, blk["attn"]["wk"][i]))
        v = jnp.einsum("bsd,dhe->bshe", h, blk["attn"]["wv"][i])
        sc = jnp.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(_M["head_dim"])
        sc = jnp.where(causal, sc, -1e30)
        a = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqk,bkhe->bqhe", a, v)
        x = x + jnp.einsum("bshe,hed->bsd", o, blk["attn"]["wo"][i])
        h = _layernorm(x, blk["ln2"]["scale"][i])
        ff = blk["ffn"]
        x = x + (_gelu(h @ ff["wi_gate"][i]) * (h @ ff["wi_up"][i])) @ ff["wo"][i]
    x = _layernorm(x, p["final_ln"]["scale"])
    out = x @ p["embed"]["unembed"]
    return jnp.where(np.arange(_M["padded_vocab"]) < _M["vocab_size"], out,
                     -1e9)


def loss(p, tokens, labels):
    """Mean next-token cross-entropy."""
    lg = logits(p, tokens)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def flops_per_example() -> float:
    """Forward plus backward (3x forward) of one sequence: every matmul, the
    full seq x seq score and value products of each head (the model computes
    them whole and masks), and the output projection over the vocabulary's
    real symbols (the padding to ``padded_vocab`` is no model work)."""
    s = CONFIG["data"]["seq_len"]
    d, h, e = _M["d_model"], _M["num_heads"], _M["head_dim"]
    qkvo = 2 * d * h * e * 4
    mlp = 2 * d * _M["d_ff"] * 3
    attn = 2 * s * h * e * 2
    forward = s * (_M["num_layers"] * (qkvo + mlp + attn)
                   + 2 * d * _M["vocab_size"])
    return 3.0 * forward


def make_clients(seed: int, num_clients: int, samples: int):
    from bench.harness.data import char_clients
    return char_clients(seed, num_clients, samples, CONFIG["data"]["seq_len"],
                        _M["vocab_size"])
