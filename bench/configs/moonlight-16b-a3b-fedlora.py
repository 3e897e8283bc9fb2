"""Plain reference of ``moonlight-16b-a3b-fedlora`` and its operation count.

The trainable tree is the LoRA adapter tree; ``loss(w, x, y)`` is the float32
forward and next-token loss of the cut model with the adapters ``w`` over the
frozen base, written from the published equations:

- multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 Sec 2.1) without
  query compression: ``q = x Wq``; ``[c, kr] = x Wkv_a``; ``[kn, v] =
  rmsnorm(c) Wkv_b``; RoPE on the last 64 dims of each query and on ``kr``,
  one key shared by the heads; causal softmax at ``1/sqrt(192)``; ``o Wo``;
- LoRA (arXiv:2106.09685) on those four projections: ``y = x W + (alpha / r)
  (x A) B``;
- the DeepSeek-V3 gate (arXiv:2412.19437 Sec 2.1.2, noaux_tc, one group):
  ``s = sigmoid(x R)``; the experts are the top 6 of ``s + bias``; their
  weights are their ``s`` normalised to sum 1, times 2.446;
- this chip's 8 held experts (ids 0-7 of 64), each a SwiGLU of 1,408, on every
  token routed to them, weighted, plus the 2 shared experts as one SwiGLU of
  2,816; the leading dense layer a SwiGLU of 11,264; RMSNorm eps 1e-5 (the
  latent norm 1e-6); untied head over the 20,480-id slice.

The base is drawn from the seed as the program draws it (a normal per
parameter keyed by the FNV-1a hash of its path, at 1/sqrt(fan-in); norm scales
1; the gate's bias at std 0.05), rounded to bfloat16 and computed in float32.
It is drawn inside the traced loss (each call draws it again), so that no
weight is a constant of the compiled program; each layer is rematerialised
and the loss is taken over blocks of the vocabulary, which changes no
arithmetic.  Given
bfloat16 adapters (the harness's control), everything is computed in
bfloat16.  Nothing here imports the program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())

LORA_B_STD = 0.01
ROUTER_BIAS_STD = 0.05
KV_NORM_EPS = 1e-6
VOCAB_BLOCK = 4096


def _fnv1a(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h


def _normal(key, path, shape, fan_in, scale=1.0):
    k = jax.random.fold_in(key, _fnv1a(path))
    return jax.random.normal(k, shape, jnp.float32) * (scale / np.sqrt(fan_in))


class Reference:
    """The reference at the sizes of ``model`` (a configuration's ``model``
    block); the module's functions are those of the configuration's own."""

    def __init__(self, model: dict, seq_len: int):
        self.m = dict(model)
        self.seq_len = seq_len
        self.seed = None
        if self.m["padded_vocab"] != self.m["vocab_size"]:
            raise ValueError("the head is over the whole slice: no padding")

    # ------------------------------------------------------------ shapes
    def _proj_shapes(self):
        m = self.m
        d, h, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
        nope, rope, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
        return {"q_proj": ((d,), (h, nope + rope)),
                "kv_a_proj_with_mqa": ((d,), (r + rope,)),
                "kv_b_proj": ((r,), (h, nope + v)),
                "o_proj": ((h, v), (d,))}

    def _moe_layers(self) -> int:
        return self.m["num_layers"] - self.m["first_dense_layers"]

    # -------------------------------------------------------------- init
    def _adapters(self, key, path, lead=()):
        out = {}
        r = self.m["lora_rank"]
        for t, (i, o) in self._proj_shapes().items():
            out[t] = {"a": _normal(key, f"{path}/attn/lora/{t}.a", lead + i + (r,),
                                   math.prod(i)),
                      "b": _normal(key, f"{path}/attn/lora/{t}.b", lead + (r,) + o,
                                   1, LORA_B_STD)}
        return {"attn": {"lora": out}}

    def init(self, seed: int):
        """float32 adapters of the stage seeded by ``seed``; the base of the
        same seed is what ``loss`` reads."""
        self.seed = int(seed)
        key = jax.random.key(seed)
        return {"lead": {f"l{j}": self._adapters(key, f"lead{j}")
                         for j in range(self.m["first_dense_layers"])},
                "stack": {"p0": self._adapters(key, "stack_p0",
                                               (self._moe_layers(),))}}

    # ------------------------------------------------------------- base
    def _w(self, path, shape, fan_in, dtype, scale=1.0):
        """One base weight: drawn, rounded to bfloat16, in ``dtype``."""
        w = _normal(jax.random.key(self.seed), path, shape, fan_in, scale)
        return w.astype(jnp.bfloat16).astype(dtype)

    def _block_base(self, path, dtype, moe: bool, lead=()):
        """One block's weights, or a stack of them (``lead``)."""
        m = self.m
        d = m["d_model"]
        w = lambda name, shape, fan_in, scale=1.0: self._w(  # noqa: E731
            f"{path}/{name}", lead + shape, fan_in, dtype, scale)
        p = {t: w(f"attn/{t}", i + o, math.prod(i))
             for t, (i, o) in self._proj_shapes().items()}
        p["kv_norm"] = jnp.ones(lead + (m["kv_lora_rank"],), dtype)
        if not moe:
            f = m["d_ff"]
            p["mlp"] = (w("mlp/wi_gate", (d, f), d), w("mlp/wi_up", (d, f), d),
                        w("mlp/wo", (f, d), f))
            return p
        e, held, f, fs = (m["num_experts"], m["experts_held"], m["moe_d_ff"],
                          m["moe_shared_d_ff"])
        p["router"] = w("moe/router", (d, e), d)
        p["bias"] = w("moe/router_bias", (e,), 1, ROUTER_BIAS_STD)
        p["experts"] = (w("moe/wi_gate", (held, d, f), d),
                        w("moe/wi_up", (held, d, f), d),
                        w("moe/wo", (held, f, d), f))
        p["shared"] = (w("moe/shared.wi_gate", (d, fs), d),
                       w("moe/shared.wi_up", (d, fs), d),
                       w("moe/shared.wo", (fs, d), fs))
        return p

    # ---------------------------------------------------------- forward
    def _rms(self, x, scale, eps):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps) * scale

    def _rope(self, x):
        """x: (b, s, heads, e); rotates the two halves of the last dim."""
        e, s = x.shape[-1], x.shape[1]
        freqs = 1.0 / (self.m["rope_theta"] ** (np.arange(0, e, 2) / e))
        ang = np.arange(s)[:, None] * freqs[None, :]
        cos = jnp.asarray(np.cos(ang)[:, None, :], x.dtype)
        sin = jnp.asarray(np.sin(ang)[:, None, :], x.dtype)
        x1, x2 = x[..., :e // 2], x[..., e // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def _proj(self, x, w, lora, target):
        n_in = len(self._proj_shapes()[target][0])
        a, b = lora[target]["a"], lora[target]["b"]
        s = self.m["lora_alpha"] / self.m["lora_rank"]
        return (jnp.tensordot(x, w, n_in)
                + s * jnp.tensordot(jnp.tensordot(x, a, n_in), b, 1))

    def _attention(self, p, lora, x):
        m = self.m
        nope, rope, r = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["kv_lora_rank"]
        q = self._proj(x, p["q_proj"], lora, "q_proj")
        ckv = self._proj(x, p["kv_a_proj_with_mqa"], lora, "kv_a_proj_with_mqa")
        c = self._rms(ckv[..., :r], p["kv_norm"], KV_NORM_EPS)
        kv = self._proj(c, p["kv_b_proj"], lora, "kv_b_proj")
        q = jnp.concatenate([q[..., :nope], self._rope(q[..., nope:])], -1)
        kr = self._rope(ckv[..., None, r:])
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(kr, kv.shape[:-1] + (rope,))], -1)
        v = kv[..., nope:]
        s = x.shape[1]
        sc = jnp.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(nope + rope)
        sc = jnp.where(np.tril(np.ones((s, s), bool)), sc, -1e30)
        o = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(sc, axis=-1), v)
        return self._proj(o, p["o_proj"], lora, "o_proj")

    def _swiglu(self, x, wg, wu, wo):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wo

    def _layer(self, x, lora, p):
        """One block with weights ``p`` and adapters ``lora``."""
        m = self.m
        eps = m["norm_eps"]
        x = x + self._attention(p, lora, self._rms(x, 1.0, eps))
        h = self._rms(x, 1.0, eps)
        if "mlp" in p:
            return x + self._swiglu(h, *p["mlp"])
        scores = jax.nn.sigmoid(h @ p["router"])
        _, chosen = jax.lax.top_k(scores + p["bias"], m["experts_per_token"])
        weights = jnp.take_along_axis(scores, chosen, -1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        weights = weights * m["moe_routed_scale"]
        held = jnp.arange(m["experts_held"])
        gate = jnp.sum(jnp.where(chosen[..., None] == held, weights[..., None],
                                 0.0), -2)                    # (b, s, held)
        wg, wu, wo = p["experts"]
        hid = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, wg))
               * jnp.einsum("bsd,edf->bsef", h, wu))
        # sum over e of gate_e (hid_e @ wo_e), the gate taken inside
        routed = jnp.einsum("bsef,efd->bsd", hid * gate[..., None], wo)
        return x + self._swiglu(h, *p["shared"]) + routed

    def hidden(self, w, tokens):
        """The final-normed hidden states, (b, s, d)."""
        m = self.m
        dtype = jax.tree.leaves(w)[0].dtype
        v, d = m["padded_vocab"], m["d_model"]
        x = self._w("embed/table", (v, d), v, dtype)[tokens]
        layer = jax.checkpoint(self._layer)
        for j in range(m["first_dense_layers"]):
            x = layer(x, w["lead"][f"l{j}"]["attn"]["lora"],
                      self._block_base(f"lead{j}", dtype, moe=False))
        stack = self._block_base("stack_p0", dtype, moe=True,
                                 lead=(self._moe_layers(),))
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, *lp), None), x,
                            (w["stack"]["p0"]["attn"]["lora"], stack))
        return self._rms(x, 1.0, m["norm_eps"])

    def loss(self, w, tokens, labels):
        """Mean next-token cross-entropy over the slice, the logsumexp taken
        over blocks of ``VOCAB_BLOCK`` ids."""
        m = self.m
        h = self.hidden(w, tokens)
        v, d = m["padded_vocab"], m["d_model"]
        unembed = self._w("embed/unembed", (d, v), d, h.dtype)
        lse = [jax.checkpoint(lambda h, u: jax.nn.logsumexp(h @ u, -1))(
                   h, unembed[:, a:a + VOCAB_BLOCK])
               for a in range(0, v, VOCAB_BLOCK)]
        logz = jax.nn.logsumexp(jnp.stack(lse, -1), -1)
        gold = jnp.einsum("bsd,bsd->bs", h, unembed.T[labels])
        return jnp.mean(logz - gold)

    # --------------------------------------------------------- counts
    def flops_per_example(self) -> float:
        """Model operations of one sequence: the forward (every matmul; the
        scores and values over all seq x seq positions; each token's held
        experts at 6 x 8 / 64 of an expert; the head over the slice), the
        backward through the frozen base (the same again, the score and
        value products twice, as both their operands take a gradient) and
        the adapters' gradients (their matmuls once more)."""
        m, s = self.m, self.seq_len
        d, h = m["d_model"], m["num_heads"]
        nope, rope, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
        proj = sum(2 * math.prod(i) * math.prod(o)
                   for i, o in self._proj_shapes().values())
        attn = 2 * s * h * (nope + rope) + 2 * s * h * v
        lora = sum(2 * m["lora_rank"] * (math.prod(i) + math.prod(o))
                   for i, o in self._proj_shapes().values())
        dense = 2 * 3 * d * m["d_ff"]
        share = m["experts_per_token"] * m["experts_held"] / m["num_experts"]
        moe = (2 * d * m["num_experts"] + 2 * 3 * d * m["moe_shared_d_ff"]
               + share * 2 * 3 * d * m["moe_d_ff"])
        head = 2 * d * m["vocab_size"]
        layers = m["num_layers"]
        forward = (layers * (proj + attn + lora) + m["first_dense_layers"] * dense
                   + self._moe_layers() * moe + head)
        return float(s * (2 * forward + layers * (attn + lora)))


_REF = Reference(CONFIG["model"], CONFIG["data"]["seq_len"])
init = _REF.init
loss = _REF.loss
flops_per_example = _REF.flops_per_example


def make_clients(seed: int, num_clients: int, samples: int):
    from bench.harness.data import char_clients
    return char_clients(seed, num_clients, samples, CONFIG["data"]["seq_len"],
                        CONFIG["model"]["vocab_size"],
                        n_words=CONFIG["data"]["n_words"])
