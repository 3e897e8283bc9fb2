"""Jit'd wrapper: folds (B, H) into the grid axis, broadcasts u per head,
pads S to the chunk, dispatches (interpret off-TPU).

The op is differentiable: the forward pass runs the Pallas kernel, and the
backward pass is the VJP of the pure-jnp oracle (``ref.wkv_ref``, vmapped
over heads).  This lets the rwkv6 model family *train* through the kernel
path (``ModelConfig.rwkv_impl == "pallas"``) in the federated scenario zoo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.wkv.kernel import wkv_kernel
from repro.kernels.wkv.ref import wkv_ref

# multi-head oracle matching the op signature: r,k,v,lw (B,S,H,N), u (H,N),
# h0 (B,H,N,N) -> (y (B,S,H,N), h_last (B,H,N,N))
_wkv_ref_mh = jax.vmap(wkv_ref, in_axes=(2, 2, 2, 2, 0, 1), out_axes=(2, 1))


def _wkv_fwd_only(r, k, v, lw, u, h0, chunk):
    b, s, h, n = r.shape
    chunk = min(chunk, max(8, s))
    pad_s = (-s) % chunk

    def fold(x):
        x = x.transpose(0, 2, 1, 3).reshape(b * h, s, n)
        if pad_s:
            x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0)))
        return x

    rf, kf, vf = fold(r), fold(k), fold(v)
    lwf = fold(lw)
    uf = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, 1, n)
    h0f = h0.reshape(b * h, n, n)
    y, h_last = wkv_kernel(rf, kf, vf, lwf, uf, h0f, chunk=chunk,
                           interpret=not on_tpu())
    y = y[:, :s].reshape(b, h, s, n).transpose(0, 2, 1, 3)
    return y, h_last.reshape(b, h, n, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _wkv(r, k, v, lw, u, h0, chunk):
    return _wkv_fwd_only(r, k, v, lw, u, h0, chunk)


def _wkv_vjp_fwd(r, k, v, lw, u, h0, chunk):
    return _wkv_fwd_only(r, k, v, lw, u, h0, chunk), (r, k, v, lw, u, h0)


def _wkv_vjp_bwd(chunk, res, cots):
    _, vjp = jax.vjp(_wkv_ref_mh, *res)
    return vjp(cots)


_wkv.defvjp(_wkv_vjp_fwd, _wkv_vjp_bwd)


def wkv(r, k, v, lw, u, h0, chunk: int = 256):
    """r,k,v,lw: (B,S,H,N) f32; u: (H,N); h0: (B,H,N,N).
    Returns (y (B,S,H,N) f32, h_last (B,H,N,N))."""
    return _wkv(r, k, v, lw, u, h0, chunk)
