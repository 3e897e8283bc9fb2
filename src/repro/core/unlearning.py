"""SE (Sharding Eraser) unlearning engine: preparation (eq. 2) and calibrated
retraining (eq. 3), operating on parameter pytrees.

These are the *algebraic* operations; the FL loop that drives them lives in
``repro.fl.simulator`` (CPU paper-scale) and ``repro.fl.fedavg`` (pod-scale).
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp


def tree_mean(trees: Sequence):
    """Average a list of pytrees — eq. (2)'s aggregation."""
    n = float(len(trees))
    return jax.tree.map(lambda *xs: sum(x.astype(jnp.float32) for x in xs) / n,
                        *trees)


def tree_add(a, b, scale: float = 1.0):
    return jax.tree.map(lambda x, y: x + scale * y.astype(x.dtype), a, b)


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y.astype(x.dtype), a, b)


def tree_norm(tree) -> jnp.ndarray:
    """Global L2 norm of a pytree (f32 accumulate)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def tree_scale(tree, s):
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * s).astype(x.dtype), tree)


def stacked_mean(stacked) -> object:
    """FedAvg over a stacked (M, ...) pytree: one fused mean per leaf — the
    device-resident replacement for unstack + ``tree_mean``.

    The reduction is a strict left fold over the M rows, matching
    ``tree_mean``'s Python-``sum`` association exactly, so stage training
    reproduces a per-client ``tree_mean`` loop bit-for-bit (f32 adds are
    order-sensitive; XLA keeps strict semantics and still fuses the chain).
    """
    def mean_leaf(a):
        a = a.astype(jnp.float32)
        acc = a[0]
        for i in range(1, a.shape[0]):
            acc = acc + a[i]
        return acc / a.shape[0]
    return jax.tree.map(mean_leaf, stacked)


def stacked_norms(stacked) -> jnp.ndarray:
    """(M,) global L2 norms of the rows of a stacked (M, ...) pytree — one
    vmap-style reduction instead of M host-synced ``tree_norm`` calls."""
    leaves = jax.tree.leaves(stacked)
    m = leaves[0].shape[0]
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32).reshape(m, -1)), axis=1)
             for l in leaves)
    return jnp.sqrt(sq)


def stacked_sub(stacked, base):
    """Row-wise ``stacked - base`` (broadcast the unstacked base tree)."""
    return jax.tree.map(lambda a, b: a.astype(jnp.float32)
                        - b.astype(jnp.float32), stacked, base)


def calibrate_stacked(global_model, stacked_deltas, stored_norms: jnp.ndarray,
                      eps: float = 1e-12, use_kernel: bool = False):
    """eq. (3) on a stacked (M, ...) delta tree — the fused, device-resident
    form of ``calibrate``:

        w <- w + sum_m (||old_m|| / ||new_m|| / M) * new_m

    ``stored_norms``: (M,) historical update norms. With ``use_kernel`` the
    accumulate runs through the Pallas ``calibrate`` kernel on the flattened
    (M, P) delta matrix (one HBM pass); otherwise a per-leaf tensordot, which
    XLA fuses the same way.
    """
    m = jax.tree.leaves(stacked_deltas)[0].shape[0]
    new_norms = stacked_norms(stacked_deltas)
    coeffs = (stored_norms.astype(jnp.float32)
              / jnp.maximum(new_norms, eps)) / m
    if use_kernel:
        from repro.core import coding
        from repro.kernels.calibrate.ops import calibrate_update
        wf, spec = coding.tree_to_flat(global_model)
        df, _ = coding.tree_to_flat_stacked(stacked_deltas)
        return coding.flat_to_tree(calibrate_update(wf, df, coeffs), spec)
    return jax.tree.map(
        lambda w, d: (w.astype(jnp.float32)
                      + jnp.tensordot(coeffs, d.astype(jnp.float32), axes=1)
                      ).astype(w.dtype),
        global_model, stacked_deltas)


def prepare_initial_model(retained_locals: Sequence) -> object:
    """eq. (2): the initial unlearned global model is the average of the
    retained clients' stored local models (the unlearned clients' parameters
    have already been removed from the set)."""
    assert retained_locals, "no retained clients in shard"
    return tree_mean(retained_locals)


def calibrate(global_model, retrained_deltas: Sequence,
              stored_deltas: Sequence, eps: float = 1e-12):
    """eq. (3): one calibrated-retraining aggregation round.

        w^{g'+1} = w^{g'} + (1/M) * sum_m  (||w^g_m|| / ||w'^{g'}_m||) w'^{g'}_m

    ``retrained_deltas``: the retained clients' *new* local updates at
    unlearning round g' (trained with L/r epochs from the current unlearned
    global model).  ``stored_deltas``: the same clients' *historical* updates
    at the matching learning round g = g' — only their norms are used, to
    restore the update magnitude the full training had.
    """
    assert len(retrained_deltas) == len(stored_deltas)
    m = len(retrained_deltas)
    out = global_model
    for new, old in zip(retrained_deltas, stored_deltas):
        ratio = tree_norm(old) / jnp.maximum(tree_norm(new), eps)
        out = tree_add(out, tree_scale(new, ratio / m))
    return out


def remove_client_effect(all_locals: dict, unlearn_clients: Sequence[int]) -> dict:
    """Preparation step: drop the unlearning clients' stored parameters from a
    {client_id: pytree} mapping (w^g_{s_i} = w^g_{C_si} - w^g_{C'_si})."""
    return {c: p for c, p in all_locals.items() if c not in set(unlearn_clients)}
