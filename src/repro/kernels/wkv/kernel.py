"""Pallas TPU kernel: fused RWKV-6 WKV recurrence.

Same motivation as kernels/ssm_scan (§Perf pair 3): the chunk-parallel XLA
form materialises (B,H,c,c,N) pairwise-decay tensors in HBM every chunk
(rwkv6-3b train_4k is memory-bound ~250x at baseline). Here the (N,N) state
and all per-step intermediates stay in VMEM: HBM traffic per grid step is the
r/k/v/lw tiles in and the y tile out.

Layout: grid (B*H, S/CHUNK); chunk axis sequential, state (N,N) in VMEM
scratch; fori_loop over the CHUNK steps (each step: two (N,N) VPU FMAs + a
row reduction). u rides along as a (1, 1, N) resident operand per head:
Mosaic tiles the last two block dims, so they must equal the array's.

VMEM per step: 4 x (CHUNK x N) tiles + (N,N) state + y tile:
CHUNK=256, N=64 -> ~300 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, h0_ref, y_ref, hout_ref,
            h_ref, *, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)                # (N,)

    def step(t, _):
        r_t = r_ref[0, t, :].astype(jnp.float32)       # (N,)
        k_t = k_ref[0, t, :].astype(jnp.float32)
        v_t = v_ref[0, t, :].astype(jnp.float32)
        w_t = jnp.exp(lw_ref[0, t, :].astype(jnp.float32))
        kv = k_t[:, None] * v_t[None, :]               # (N,N)
        y = jnp.sum((h_ref[...] + u[:, None] * kv) * r_t[:, None], axis=0)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        h_ref[...] = w_t[:, None] * h_ref[...] + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_kernel(r, k, v, lw, u, h0, *, chunk: int = 256,
               interpret: bool = False):
    """r,k,v,lw: (BH, S, N); u: (BH, 1, N) (head-broadcast by the wrapper);
    h0: (BH, N, N). S % chunk == 0. Returns (y (BH,S,N) f32, h_last)."""
    bh, s, n = r.shape
    assert s % chunk == 0
    grid = (bh, s // chunk)
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, n), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, n, n), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, n, n), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u, h0)
