"""Plain federated training, written from the paper's equations, for the
comparison that decides ``correct``.

Nothing here imports the program.  A ``FedReference`` is built from a
configuration's reference module (``init``, ``loss``) and its JSON; it trains
in float32 with every matmul at the configuration's ``matmul_precision``, or,
as the control, in bfloat16 throughout.

* Local training: ``local_epochs`` passes over a client's examples in order,
  in batches of ``local_batch``, each batch one SGD step ``w -= lr * grad``.
* A round of one shard: every client trains from the shard's global model,
  the update norm ``||w_c - w||`` of each client is kept, and the new global
  model is the mean of the clients' models (FedAvg).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def tree_stack(trees):
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def _row_norms(stacked):
    """(M,) L2 norm of each row of a stacked (M, ...) tree, f32 sums."""
    leaves = jax.tree.leaves(stacked)
    m = leaves[0].shape[0]
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32).reshape(m, -1)),
                                axis=1) for a in leaves))


class FedReference:
    """``half_batch`` plants a fault for the readings of the limits: each
    SGD step takes the mean over the first half of its batch only."""

    def __init__(self, model, cfg: dict, dtype=jnp.float32,
                 half_batch: bool = False):
        self.model = model
        self.half_batch = half_batch
        self.dtype = jnp.dtype(dtype)
        self.precision = (cfg["matmul_precision"] if self.dtype == jnp.float32
                          else "default")
        opt, fed = cfg["optimizer"], cfg["federation"]
        self.lr = float(opt["lr"])
        self.batch = int(opt["local_batch"])
        self.epochs = int(fed["local_epochs"])
        self._round = jax.jit(jax.vmap(
            lambda w, x, y: self._round_body(w, x, y, self.epochs)))
        self._grad = jax.jit(jax.grad(self._loss))

    # ------------------------------------------------------------ pieces
    def cast(self, tree):
        return jax.tree.map(lambda a: jnp.asarray(a).astype(self.dtype)
                            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                            else jnp.asarray(a), tree)

    def _loss(self, w, x, y):
        return self.model.loss(w, x, y)

    def _local(self, w, xs, ys, epochs):
        nb = xs.shape[0] // self.batch
        xb = xs[:nb * self.batch].reshape((nb, self.batch) + xs.shape[1:])
        yb = ys[:nb * self.batch].reshape((nb, self.batch) + ys.shape[1:])
        lr = jnp.asarray(self.lr, self.dtype)

        def step(w, xy):
            if self.half_batch:
                xy = tuple(a[:self.batch // 2] for a in xy)
            g = jax.grad(self._loss)(w, *xy)
            return jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype), w, g), None

        def epoch(w, _):
            return jax.lax.scan(step, w, (xb, yb))[0], None

        return jax.lax.scan(epoch, w, None, length=epochs)[0]

    def _round_body(self, w, xs, ys, epochs):
        """One shard's round: (new global, (M,) update norms, (M, ...) locals)."""
        locals_ = jax.vmap(lambda x, y: self._local(w, x, y, epochs))(xs, ys)
        deltas = jax.tree.map(lambda a, b: a - b, locals_, w)
        new_w = jax.tree.map(lambda a: jnp.mean(a.astype(jnp.float32), 0)
                             .astype(self.dtype), locals_)
        return new_w, _row_norms(deltas), locals_

    # ----------------------------------------------------------- entries
    def first_gradient(self, w0, x, y):
        """Gradient at ``w0`` on one batch (the leaf-exclusion rule)."""
        with jax.default_matmul_precision(self.precision):
            return self._grad(self.cast(w0), jnp.asarray(x[:self.batch]),
                              jnp.asarray(y[:self.batch]))

    def train(self, w0, xs, ys, rounds: int, keep_locals: int):
        """FedAvg of S shards from one initial model.  xs: (S, M, n, ...).
        Returns host arrays: ``globals`` (rounds+1, S, ...) tree, ``norms``
        (rounds, S, M) and ``locals`` (keep_locals, S, M, ...) tree."""
        s = xs.shape[0]
        w = jax.tree.map(lambda a: jnp.broadcast_to(a, (s,) + a.shape),
                         self.cast(w0))
        xs = self.cast(jnp.asarray(xs))
        ys = jnp.asarray(ys)
        globals_, norms, kept = [w], [], []
        with jax.default_matmul_precision(self.precision):
            for g in range(rounds):
                w, n, loc = self._round(w, xs, ys)
                globals_.append(w)
                norms.append(n)
                if g < keep_locals:
                    kept.append(loc)
        out = {"globals": jax.device_get(tree_stack(globals_)),
               "norms": np.asarray(jax.device_get(jnp.stack(norms)))}
        out["locals"] = jax.device_get(tree_stack(kept)) if kept else None
        return out
