"""Coded computing-based sharding (paper Sec 3.3).

The per-round, per-shard intermediate parameters ``w_{C_s}^g`` (one vector per
shard, stacked to ``W in R^{S x P}``) are Lagrange-encoded (eq. 5/6) at client
points ``alpha_i``:

    w~_i = u(alpha_i) = sum_s W[s] * l_s(alpha_i)        (a C x S matmul)

which is a Reed-Solomon code of dimension S and length C. Reconstruction:

  * erasure decode (eq. 7): any S intact slices determine W. We solve it in
    the *Lagrange basis* (re-interpolation matrix D[s,i] = l_i^{(I)}(omega_s))
    rather than inverting the power-basis Vandermonde — numerically stable at
    C=100 in float32. The paper's literal pseudo-inverse form is also provided
    (``decode_vandermonde``) for fidelity tests at small C.
  * error decode: up to floor((C-S)/2) corrupted slices are localized with
    Berlekamp-Welch (float64 least squares on a sample of coordinates,
    majority vote), then excluded and erasure-decoded. Matches the paper's
    ``2*mu*C <= C - S`` tolerance (eq. 11).

Encode/decode are *matmuls against small coefficient matrices*, so on TPU they
stream parameter blocks through the MXU — see kernels/coded_matmul for the
Pallas fast path; this module is the reference/driver layer.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class CodingBudgetExceeded(RuntimeError):
    """Corruption (or erasure) beyond the correctable budget of eq. 11.

    Carries the ``observed`` fault count and the scheme's ``max_errors`` so
    callers (and tests) can assert the failure mode instead of parsing an
    opaque stack trace.
    """

    def __init__(self, observed: int, max_errors: int,
                 kind: str = "corrupted slices"):
        self.observed = int(observed)
        self.max_errors = int(max_errors)
        self.kind = kind
        super().__init__(
            f"{kind} count {self.observed} exceeds the correctable budget "
            f"max_errors={self.max_errors} (2*mu*C <= C - S, eq. 11)")


def chebyshev_points(n: int, lo: float = -1.0, hi: float = 1.0) -> np.ndarray:
    """Chebyshev nodes — well-conditioned interpolation points."""
    k = np.arange(n)
    x = np.cos((2 * k + 1) / (2 * n) * np.pi)
    return (lo + hi) / 2 + (hi - lo) / 2 * x


def lagrange_coeff_matrix(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """M[j, i] = l_i^{(src)}(dst_j): evaluate the Lagrange basis over ``src``
    points at ``dst`` points. Encode: src=omega, dst=alpha. Decode: src=alpha
    subset, dst=omega."""
    src = np.asarray(src, np.float64)
    dst = np.asarray(dst, np.float64)
    n = len(src)
    m = np.ones((len(dst), n), np.float64)
    for i in range(n):
        for j in range(n):
            if j != i:
                m[:, i] *= (dst - src[j]) / (src[i] - src[j])
    return m


@dataclass(frozen=True)
class CodingScheme:
    """Evaluation-point layout for one (C clients, S shards) code."""
    num_shards: int                  # S — code dimension
    num_clients: int                 # C — code length
    alpha: np.ndarray = field(default=None)   # (C,) client points
    omega: np.ndarray = field(default=None)   # (S,) shard points

    def __post_init__(self):
        assert self.num_clients >= self.num_shards, "need C >= S"
        if self.alpha is None:
            object.__setattr__(self, "alpha",
                               chebyshev_points(self.num_clients, -1.0, 1.0))
        if self.omega is None:
            # interleave shard points strictly inside the alpha hull
            object.__setattr__(self, "omega",
                               chebyshev_points(self.num_shards, -0.95, 0.95))

    # -- matrices ----------------------------------------------------------
    def encode_matrix(self) -> np.ndarray:
        """(C, S): B[i, s] = l_s(alpha_i). eq. (6)."""
        return lagrange_coeff_matrix(self.omega, self.alpha)

    def decode_matrix(self, client_ids: Sequence[int]) -> np.ndarray:
        """(S, S): re-interpolation from a slice subset back to omega.

        When more than S slices are available we pick a well-spread subset
        (greedy farthest-point on the alpha line) — interpolation conditioning
        depends on node spread, and the first-S ids may cluster at one end of
        the Chebyshev layout."""
        ids = np.asarray(client_ids)
        assert len(ids) >= self.num_shards, "need at least S slices"
        if len(ids) > self.num_shards:
            pts = self.alpha[ids]
            chosen = [int(np.argmin(pts)), int(np.argmax(pts))]
            while len(chosen) < self.num_shards:
                dmin = np.min(np.abs(pts[:, None] - pts[chosen][None, :]), axis=1)
                dmin[chosen] = -1
                chosen.append(int(np.argmax(dmin)))
            ids = ids[np.sort(chosen)]
        return lagrange_coeff_matrix(self.alpha[ids], self.omega), ids

    def quorum(self, available: Optional[Sequence[int]] = None) -> np.ndarray:
        """The canonical S-slice read set: the well-spread subset
        ``decode_matrix`` selects from ``available`` (default: all C).

        Reads that only lose slices *outside* this subset decode through the
        identical re-interpolation matrix — bit-identical to the fault-free
        read (the greedy farthest-point choice never inspects rows it does
        not pick, so removing unpicked candidates cannot change it)."""
        ids = list(available) if available is not None \
            else list(range(self.num_clients))
        _, chosen = self.decode_matrix(ids)
        return np.asarray([int(i) for i in chosen])

    def reduced(self, available: Sequence[int]) -> "CodingScheme":
        """The code restricted to ``available`` slice rows: a valid RS code
        of the same dimension over the surviving alpha points, with the
        correspondingly tighter error budget ``(len(available) - S) // 2``.
        Used to run error localization after erasures."""
        avail = np.asarray(sorted(int(i) for i in available))
        return CodingScheme(self.num_shards, len(avail),
                            alpha=np.asarray(self.alpha)[avail],
                            omega=self.omega)

    @property
    def max_errors(self) -> int:
        """mu*C with 2*mu*C <= C - S (eq. 11)."""
        return (self.num_clients - self.num_shards) // 2


# ---------------------------------------------------------------------------
# Encode / decode on (stacked) parameter matrices
# ---------------------------------------------------------------------------

# The encode and decode dots run at full f32 precision.  At default precision
# a TPU runs an f32 dot as one bf16 pass: decoded rounds then differ from the
# stored parameters by ~1e-2 relative, and the robust decoder reads that
# residue as corrupted slices.
_EXACT = jax.lax.Precision.HIGHEST


def encode(scheme: CodingScheme, shard_params: jnp.ndarray,
           use_kernel: bool = False, out_dtype=None) -> jnp.ndarray:
    """shard_params: (S, P) -> coded slices (C, P). eq. (6).

    ``out_dtype``: optional storage dtype for the slices (bf16 halves the
    client-side storage footprint; decode accumulates in f32 regardless).
    """
    b = jnp.asarray(scheme.encode_matrix(), jnp.float32)
    w = shard_params.astype(jnp.float32)
    if use_kernel:
        from repro.kernels.coded_matmul.ops import coded_matmul
        return coded_matmul(b, w, out_dtype=out_dtype)
    out = jnp.matmul(b, w, precision=_EXACT)
    return out.astype(out_dtype) if out_dtype is not None else out


@partial(jax.jit, static_argnames=("out_dtype",))
def _encode_many(b: jnp.ndarray, mats: tuple, out_dtype=None) -> tuple:
    outs = tuple(jnp.matmul(b, m.astype(jnp.float32), precision=_EXACT)
                 for m in mats)
    if out_dtype is not None:
        outs = tuple(o.astype(out_dtype) for o in outs)
    return outs


def encode_batched(scheme: CodingScheme, mats: Sequence[jnp.ndarray],
                   use_kernel: bool = False, out_dtype=None) -> list:
    """Encode G (S, P_g) matrices in ONE dispatch.

    jnp path: all G encodes run inside a single jitted XLA program — one
    launch and zero host round-trips instead of G eager dispatches (the G
    matrices stay separate buffers; no concat copy). Kernel path: the rounds
    are concatenated to (S, sum_g P_g) and streamed through ONE 2-D-grid
    ``coded_matmul`` — on TPU the tiny (C, S) coefficient matrix then makes a
    single resident pass over the whole multi-round payload. Identical
    per-column math to per-round ``encode``; used by ``CodedStore`` to batch
    the history encodes.
    """
    if not use_kernel:
        b = jnp.asarray(scheme.encode_matrix(), jnp.float32)
        return list(_encode_many(b, tuple(mats), out_dtype=out_dtype))
    widths = [int(m.shape[1]) for m in mats]
    w = mats[0] if len(mats) == 1 else jnp.concatenate(list(mats), axis=1)
    coded = encode(scheme, w, use_kernel=True, out_dtype=out_dtype)
    outs, off = [], 0
    for p in widths:
        outs.append(coded[:, off:off + p])
        off += p
    return outs


def encode_rounds(enc: jnp.ndarray, hist: jnp.ndarray,
                  use_kernel: bool = False, out_dtype=None) -> jnp.ndarray:
    """All-rounds Lagrange encode: ``hist (G, S, P) -> (G, C, P)`` in one op.

    ``enc`` is the (C, S) encode matrix (``CodingScheme.encode_matrix`` as a
    device array).  Fully traceable — this is the encode the stage program
    fuses *into* the training program, replacing ``encode_batched``'s
    separate dispatch.  Per-round columns are identical math to
    ``encode(scheme, hist[g])``.  jnp path: one batched einsum over the round
    axis.  Kernel path: a (G, C_tiles, P_tiles)-grid Pallas matmul that
    streams each round's (S, block_p) tile through the MXU with NO
    concatenate copy (``encode_batched``'s kernel path concatenated the
    rounds host-visibly first).  Both paths run under the ``coding.encode``
    named scope, which names their operations in a device trace.
    """
    with jax.named_scope("coding.encode"):
        if use_kernel:
            from repro.kernels.coded_matmul.ops import coded_matmul_rounds
            return coded_matmul_rounds(enc, hist, out_dtype=out_dtype)
        out = jnp.einsum("cs,gsp->gcp", enc.astype(jnp.float32),
                         hist.astype(jnp.float32), precision=_EXACT)
        return out.astype(out_dtype) if out_dtype is not None else out


def encode_decode(scheme: CodingScheme, shard_params: jnp.ndarray,
                  client_ids: Optional[Sequence[int]] = None,
                  use_kernel: bool = False) -> jnp.ndarray:
    """Fused code round-trip: encode to C slices and immediately re-decode
    from ``client_ids`` (default: all C) — the slice-verification path.

    ``use_kernel``: the Pallas path streams ``D @ (B @ w_tile)`` per P-tile,
    so the (C, P) coded intermediate never touches HBM (the TPU form of the
    fusion). The jnp path exploits associativity instead: the (S, C) decode
    and (C, S) encode operators are precomposed into one (S, S) matrix on the
    host, turning the round-trip into a SINGLE small matmul over P — S*S*P
    FLOPs instead of 2*C*S*P (25x fewer at the paper's C=100, S=4).
    """
    ids = list(client_ids) if client_ids is not None else \
        list(range(scheme.num_clients))
    d, used = scheme.decode_matrix(ids)
    # (S, C) decode operator with zero columns for unused client slots
    dec = np.zeros((scheme.num_shards, scheme.num_clients), np.float64)
    dec[:, [int(i) for i in used]] = d
    enc_np = scheme.encode_matrix()
    if use_kernel:
        from repro.kernels.coded_matmul.ops import coded_encode_decode
        return coded_encode_decode(jnp.asarray(enc_np, jnp.float32),
                                   jnp.asarray(dec, jnp.float32),
                                   shard_params.astype(jnp.float32))
    composed = jnp.asarray(dec @ enc_np, jnp.float32)      # (S, S) ~ I
    return jnp.matmul(composed, shard_params.astype(jnp.float32),
                      precision=_EXACT)


def decode_erasure(scheme: CodingScheme, slices: jnp.ndarray,
                   client_ids: Sequence[int],
                   use_kernel: bool = False) -> jnp.ndarray:
    """Reconstruct (S, P) from >=S intact slices (rows of ``slices``).

    slices: (len(client_ids), P) — coded slices from those clients.
    """
    d, ids = scheme.decode_matrix(client_ids)
    dm = jnp.asarray(d, jnp.float32)
    rows = jnp.asarray([list(client_ids).index(int(i)) for i in ids])
    sl = slices[rows].astype(jnp.float32)
    if use_kernel:
        from repro.kernels.coded_matmul.ops import coded_matmul
        return coded_matmul(dm, sl)
    return jnp.matmul(dm, sl, precision=_EXACT)


def decode_vandermonde(scheme: CodingScheme, slices: jnp.ndarray) -> jnp.ndarray:
    """The paper's literal eq. (7): power-basis Vandermonde pseudo-inverse.

    Reconstructs the polynomial coefficients then evaluates at omega. Only
    numerically sane for small C; kept for fidelity testing.
    """
    a = np.vander(np.asarray(scheme.alpha), scheme.num_shards, increasing=True)
    pinv = np.linalg.pinv(a)                        # (S, C)
    coeffs = jnp.asarray(pinv, jnp.float32) @ slices.astype(jnp.float32)
    v_omega = np.vander(np.asarray(scheme.omega), scheme.num_shards,
                        increasing=True)            # (S, S)
    return jnp.asarray(v_omega, jnp.float32) @ coeffs


# ---------------------------------------------------------------------------
# Berlekamp-Welch error localization (float64, control-plane)
# ---------------------------------------------------------------------------

def _consistency_residual(scheme: CodingScheme, slices: np.ndarray,
                          trusted: np.ndarray) -> np.ndarray:
    """Decode from ``trusted[:S]`` rows, re-encode, return per-row residual."""
    d, ids = scheme.decode_matrix(list(trusted))
    rows = [list(trusted).index(int(i)) for i in ids]
    w = d @ slices[trusted[rows]]
    b = scheme.encode_matrix()
    recon = b @ w
    denom = np.abs(slices).mean() + 1e-12
    return np.abs(recon - slices).mean(axis=1) / denom


def locate_errors(scheme: CodingScheme, slices: np.ndarray,
                  num_probe: int = 8, seed: int = 0, tol: float = 1e-3,
                  method: str = "bw") -> np.ndarray:
    """Identify corrupted slice rows. slices: (C, P) float array.

    method="bw": Berlekamp-Welch — solve Q(a_i) = y_i E(a_i) (deg Q < S+e,
    E monic deg e) by float64 least squares on ``num_probe`` coordinates; the
    roots of E (|E(a_i)| ~ 0) are the corrupted clients; majority vote.
    method="ransac": consensus decoding — sample S-subsets, re-encode, pick
    the largest inlier set (robust production fallback at large C).
    A consistency pre-check short-circuits the no-error case.

    Raises ``CodingBudgetExceeded`` when the localized corruption exceeds
    ``scheme.max_errors`` — beyond eq. 11's budget localization is not
    information-theoretically sound, so failing loudly beats mis-decoding.
    """
    slices = np.asarray(slices, np.float64)
    c, p = slices.shape
    s = scheme.num_shards
    e = scheme.max_errors
    # fast path: no errors at all
    resid0 = _consistency_residual(scheme, slices, np.arange(c))
    if resid0.max() < tol:
        return np.array([], np.int64)
    if e == 0:
        raise CodingBudgetExceeded(int((resid0 >= tol).sum()), 0)
    a = np.asarray(scheme.alpha, np.float64)
    rng = np.random.default_rng(seed)

    if method == "ransac":
        best_bad, best_inliers = None, -1
        for _ in range(128):
            pick = rng.choice(c, size=s, replace=False)
            r = _consistency_residual(scheme, slices, pick)
            inliers = int((r < tol).sum())
            if inliers > best_inliers:
                best_inliers = inliers
                best_bad = np.where(r >= tol)[0]
            if inliers >= c - e:
                break
        bad = np.sort(best_bad)
        if len(bad) > e:
            raise CodingBudgetExceeded(len(bad), e)
        return bad

    cols = rng.choice(p, size=min(num_probe, p), replace=False)
    votes = np.zeros(c)
    va_q = np.vander(a, s + e, increasing=True)          # Q: deg < S+e
    va_e = np.vander(a, e, increasing=True)              # E: monic deg e
    for col in cols:
        y = slices[:, col]
        # Q(a_i) - y_i*(E_0 + ... + E_{e-1} a^{e-1}) = y_i * a^e
        lhs = np.concatenate([va_q, -y[:, None] * va_e], axis=1)
        rhs = y * a ** e
        sol, *_ = np.linalg.lstsq(lhs, rhs, rcond=None)
        e_coeffs = np.concatenate([sol[s + e:], [1.0]])  # monic
        e_vals = np.abs(np.polyval(e_coeffs[::-1], a))
        votes += e_vals < 0.05 * np.median(e_vals + 1e-300)
    bad = np.sort(np.where(votes > len(cols) / 2)[0])
    # verify: decoding without the located rows must be self-consistent on
    # EVERY surviving row — a median test would let one residual corruption
    # (beyond-budget under-localization) hide among the clean majority
    good = np.setdiff1d(np.arange(c), bad)
    if len(good) >= s and len(bad) <= e:
        r = _consistency_residual(scheme, slices, good)
        if r[good].max() < tol:
            return bad
    # fall back to consensus decoding
    return locate_errors(scheme, slices, num_probe, seed, tol, method="ransac")


def decode_with_errors(scheme: CodingScheme, slices: jnp.ndarray,
                       use_kernel: bool = False) -> Tuple[jnp.ndarray, np.ndarray]:
    """Full RS decode: localize corrupted slices, then erasure-decode without
    them. slices: (C, P). Returns (W (S,P), bad_ids).

    Raises ``CodingBudgetExceeded`` when corruption exceeds eq. 11's budget.
    """
    bad = locate_errors(scheme, np.asarray(slices, np.float64))
    good = np.setdiff1d(np.arange(scheme.num_clients), bad)
    if len(good) < scheme.num_shards:
        raise CodingBudgetExceeded(len(bad), scheme.max_errors)
    w = decode_erasure(scheme, slices[jnp.asarray(good)], list(good),
                       use_kernel=use_kernel)
    return w, bad


def decode_robust(scheme: CodingScheme, slices: jnp.ndarray,
                  available: Optional[Sequence[int]] = None,
                  use_kernel: bool = False, tol: float = 1e-3,
                  seed: int = 0
                  ) -> Tuple[jnp.ndarray, list, list]:
    """Quorum read: reconstruct (S, P) despite erased AND corrupted slices.

    ``slices``: the full (C, P) coded array (the content of unavailable rows
    is never read).  ``available``: the present row ids (None = all C).

    Pipeline: a consistency pre-check over the surviving rows; if clean,
    plain erasure decode from the canonical well-spread subset (bit-identical
    to the fault-free read whenever the faults spare ``scheme.quorum()``).
    Otherwise, error localization runs on the *reduced* scheme — the code
    restricted to surviving alpha points, a valid RS code whose budget
    ``(C - f - S) // 2`` tightens automatically with ``f`` erasures — and the
    located rows are excluded before the erasure decode.

    Returns ``(w, lost_ids, bad_ids)``.  Raises ``CodingBudgetExceeded``
    when the surviving-and-clean rows cannot determine the code.
    """
    c = scheme.num_clients
    avail = sorted(int(i) for i in (available if available is not None
                                    else range(c)))
    lost = sorted(set(range(c)) - set(avail))
    if len(avail) < scheme.num_shards:
        raise CodingBudgetExceeded(len(lost), c - scheme.num_shards,
                                   kind="erased slices")
    sub = np.asarray(jax.device_get(slices)).astype(np.float64)[avail]
    red = scheme if not lost else scheme.reduced(avail)
    resid = _consistency_residual(red, sub, np.arange(len(avail)))
    if resid.max() < tol:
        w = decode_erasure(scheme, slices[jnp.asarray(avail)], avail,
                           use_kernel=use_kernel)
        return w, lost, []
    bad_local = locate_errors(red, sub, tol=tol, seed=seed)
    bad = sorted(avail[int(i)] for i in bad_local)
    good = [i for i in avail if i not in set(bad)]
    if len(good) < scheme.num_shards:
        raise CodingBudgetExceeded(len(bad), red.max_errors)
    w = decode_erasure(scheme, slices[jnp.asarray(good)], good,
                       use_kernel=use_kernel)
    return w, lost, bad


# ---------------------------------------------------------------------------
# Pytree <-> flat parameter matrix
# ---------------------------------------------------------------------------

def tree_to_flat(tree) -> Tuple[jnp.ndarray, object]:
    """Flatten a param pytree to a 1-D f32 vector + re-assembly spec."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [(l.shape, l.dtype) for l in leaves]
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    return flat, (treedef, shapes)


def flat_to_tree(flat: jnp.ndarray, spec) -> object:
    treedef, shapes = spec
    leaves = []
    off = 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(flat[off: off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, leaves)


def tree_to_flat_stacked(tree) -> Tuple[jnp.ndarray, object]:
    """Flatten a stacked ``(M, ...)`` pytree to an ``(M, P)`` f32 matrix in
    one pass (one reshape+concat over leaves — NOT one flatten per client).

    Row ``i`` is bit-identical to ``tree_to_flat`` of the unstacked tree
    ``jax.tree.map(lambda a: a[i], tree)``, and the returned spec is the
    per-row spec: ``flat_to_tree(flat[i], spec)`` reassembles client ``i``.
    Traceable — usable inside jit (ignore the spec there).
    """
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)
    spec = (treedef, [(l.shape[1:], l.dtype) for l in leaves])
    return flat, spec


def flat_to_stacked_tree(flat: jnp.ndarray, spec) -> object:
    """Inverse of ``tree_to_flat_stacked``: (M, P) -> stacked (M, ...) tree."""
    treedef, shapes = spec
    m = flat.shape[0]
    leaves, off = [], 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(flat[:, off: off + n].reshape((m, *shape)).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, leaves)


@dataclass(frozen=True)
class StackedRowSpec:
    """Re-assembly spec for a shard vector laid out as M client rows.

    The shard's stored vector is ``stacked_flat.reshape(-1)`` — client-major
    concat of ``row_len``-sized rows, one per client in ``client_ids`` order.
    ``row_spec`` is the per-client spec from ``tree_to_flat_stacked``.
    """
    client_ids: Tuple[int, ...]
    row_len: int
    row_spec: object


def flat_to_client_trees(flat: jnp.ndarray, spec: StackedRowSpec) -> dict:
    """Reassemble a decoded shard vector into {client_id: param tree}."""
    rows = flat[: len(spec.client_ids) * spec.row_len].reshape(
        len(spec.client_ids), spec.row_len)
    return {c: flat_to_tree(rows[i], spec.row_spec)
            for i, c in enumerate(spec.client_ids)}


def encode_pytrees(scheme: CodingScheme, shard_trees: Sequence,
                   use_kernel: bool = False):
    """Encode S parameter pytrees (one per shard) into C coded slices.

    Returns (slices (C, P), spec) — spec reassembles decoded rows to pytrees.
    """
    flats, specs = zip(*[tree_to_flat(t) for t in shard_trees])
    pmax = max(f.shape[0] for f in flats)
    w = jnp.stack([jnp.pad(f, (0, pmax - f.shape[0])) for f in flats])
    return encode(scheme, w, use_kernel=use_kernel), specs


def decode_pytrees(scheme: CodingScheme, slices: jnp.ndarray,
                   client_ids: Sequence[int], specs,
                   use_kernel: bool = False):
    w = decode_erasure(scheme, slices, client_ids, use_kernel=use_kernel)
    return [flat_to_tree(w[s], specs[s]) for s in range(scheme.num_shards)]
