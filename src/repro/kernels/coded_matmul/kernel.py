"""Pallas TPU kernels: coefficient-matrix x parameter-block matmuls.

``coded_matmul_kernel`` — one (C, S) coefficient matrix against shard-stacked
parameters (S, P). The grid is 2-D, ``(C_tiles, P_tiles)``: the client
dimension C is tiled as well as the parameter dimension P, so large-C codes
(C in the hundreds/thousands — the ROADMAP's large-fleet regime) keep each
output tile inside VMEM instead of materialising a (C, block_p) stripe. The
(block_c, S) coefficient tile is revisited across the P tiles; the (S,
block_p) parameter tile across the C tiles. Output may be stored as bf16
(halves the coded-slice HBM/storage footprint; decode re-accumulates in f32).

``coded_matmul_rounds_kernel`` — the same coefficient matrix against a
G-round history ``(G, S, P)`` on a 3-D ``(G, C_tiles, P_tiles)`` grid: each
round's (S, block_p) tile streams through the MXU directly from its slot in
the stacked history — no host-side concatenate of the rounds (the 2-D kernel
required a (S, G*P) copy to batch rounds).  This is the encode the
stage program fuses into the training program.

``encode_decode_kernel`` — fused code round-trip ``D @ (B @ w)``: per P-tile
the (C, block_p) coded intermediate lives only in VMEM/registers, never HBM.
This is the verification path (encode then immediately re-decode to check a
round's slices) at one HBM read + one write of P instead of three passes.

Every dot runs at ``Precision.HIGHEST``: Mosaic's default runs an f32 dot
as one bf16 pass, which leaves ~5e-3 relative error in the slices — far
above what a Lagrange decode may add (see ``core/coding.py``).

VMEM working set per step (coded_matmul):
  (block_c*S + S*block_p + block_c*block_p) * 4B
  e.g. block_c=128, S=8, block_p=4096: ~2.2 MiB — well inside ~16 MiB/core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EXACT = jax.lax.Precision.HIGHEST


def _kernel(coeff_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot(
        coeff_ref[...], w_ref[...], precision=_EXACT,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_p", "out_dtype",
                                    "interpret"))
def coded_matmul_kernel(coeff: jnp.ndarray, w: jnp.ndarray, *,
                        block_c: int = 128,
                        block_p: int = 4096,
                        out_dtype=jnp.float32,
                        interpret: bool = False) -> jnp.ndarray:
    """coeff: (C, S); w: (S, P) with C a multiple of block_c, S a multiple of
    8 and P a multiple of block_p (the ops wrapper pads). Returns (C, P)."""
    c, s = coeff.shape
    s2, p = w.shape
    assert s == s2 and p % block_p == 0 and c % block_c == 0, \
        (coeff.shape, w.shape, block_c, block_p)
    grid = (c // block_c, p // block_p)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, s), lambda i, j: (i, 0)),   # C-tiled
            pl.BlockSpec((s, block_p), lambda i, j: (0, j)),   # P-streamed
        ],
        out_specs=pl.BlockSpec((block_c, block_p), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((c, p), out_dtype),
        interpret=interpret,
    )(coeff.astype(jnp.float32), w.astype(jnp.float32))


def _rounds_kernel(coeff_ref, w_ref, o_ref):
    o_ref[0] = jax.lax.dot(
        coeff_ref[...], w_ref[0], precision=_EXACT,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_p", "out_dtype",
                                    "interpret"))
def coded_matmul_rounds_kernel(coeff: jnp.ndarray, w: jnp.ndarray, *,
                               block_c: int = 128,
                               block_p: int = 4096,
                               out_dtype=jnp.float32,
                               interpret: bool = False) -> jnp.ndarray:
    """coeff: (C, S); w: (G, S, P) with C a multiple of block_c, S of 8 and P
    of block_p (the ops wrapper pads).  Returns (G, C, P): per-round
    ``coeff @ w[g]`` on a (G, C_tiles, P_tiles) grid — the (block_c, S)
    coefficient tile is revisited across rounds and P tiles; each round's
    (S, block_p) tile is read once, straight from the stacked history."""
    c, s = coeff.shape
    g, s2, p = w.shape
    assert s == s2 and p % block_p == 0 and c % block_c == 0, \
        (coeff.shape, w.shape, block_c, block_p)
    grid = (g, c // block_c, p // block_p)
    return pl.pallas_call(
        _rounds_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, s), lambda r, i, j: (i, 0)),
            pl.BlockSpec((1, s, block_p), lambda r, i, j: (r, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_p),
                               lambda r, i, j: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, c, p), out_dtype),
        interpret=interpret,
    )(coeff.astype(jnp.float32), w.astype(jnp.float32))


def _ed_kernel(enc_ref, dec_ref, w_ref, o_ref):
    coded = jax.lax.dot(enc_ref[...], w_ref[...], precision=_EXACT,
                        preferred_element_type=jnp.float32)      # (C, blk)
    o_ref[...] = jax.lax.dot(dec_ref[...], coded, precision=_EXACT,
                             preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def encode_decode_kernel(enc: jnp.ndarray, dec: jnp.ndarray, w: jnp.ndarray,
                         *, block_p: int = 4096,
                         interpret: bool = False) -> jnp.ndarray:
    """Fused round-trip: dec @ (enc @ w) without an HBM (C, P) intermediate.

    enc: (C, S); dec: (S, C); w: (S, P). C, S multiples of 8, P of block_p.
    Returns (S, P) f32 — equals w up to code conditioning.
    """
    c, s = enc.shape
    s2, c2 = dec.shape
    s3, p = w.shape
    assert (c, s) == (c2, s2) == (c2, s3) and p % block_p == 0, \
        (enc.shape, dec.shape, w.shape)
    grid = (p // block_p,)
    return pl.pallas_call(
        _ed_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, s), lambda i: (0, 0)),          # resident
            pl.BlockSpec((s, c), lambda i: (0, 0)),          # resident
            pl.BlockSpec((s, block_p), lambda i: (0, i)),    # streamed
        ],
        out_specs=pl.BlockSpec((s, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((s, p), jnp.float32),
        interpret=interpret,
    )(enc.astype(jnp.float32), dec.astype(jnp.float32),
      w.astype(jnp.float32))
