"""The arithmetic of the comparison that decides ``correct``: norms taken leaf
by leaf, and gaps between the program's norms and the reference's.

A gap of norms is ``|norm_program - norm_reference|`` divided by the larger of
the reference's norm of that leaf and the median over the kept leaves.  A
leaf is kept when the reference's first gradient of it is at least a
thousandth of the median leaf's: the others move by round-off alone.
"""
from __future__ import annotations

from typing import Dict, Iterable

import jax
import numpy as np


def leaf_norms(tree) -> Dict[str, float]:
    """{path: L2 norm in float64} over the leaves of ``tree``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(np.asarray(leaf, np.float64).ravel()))
    return out


def total_norm(tree) -> float:
    """L2 norm of the whole tree, in float64."""
    return float(np.sqrt(sum(np.sum(np.square(np.asarray(x, np.float64)))
                             for x in jax.tree.leaves(tree))))


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def kept_leaves(first_gradient) -> list:
    norms = leaf_norms(first_gradient)
    med = float(np.median(list(norms.values())))
    return sorted(k for k, v in norms.items() if v >= 1e-3 * med)


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              keep: Iterable[str]) -> Dict[str, float]:
    """Each kept leaf's gap of norms (see the module docstring)."""
    keep = list(keep)
    missing = [k for k in keep if k not in program]
    if missing:
        raise ValueError(f"program tree lacks leaves {missing}")
    med = float(np.median([reference[k] for k in keep]))
    return {k: abs(program[k] - reference[k]) / max(reference[k], med, 1e-30)
            for k in keep}


def norm_gap(program: Dict[str, float], reference: Dict[str, float],
             keep: Iterable[str]) -> float:
    """Worst leaf's gap of norms."""
    return max(leaf_gaps(program, reference, keep).values())


def exact_gap(program, reference) -> float:
    """Worst leaf's max |program - reference| over max |reference|; 0 when
    the trees are equal."""
    return max(float(np.max(np.abs(np.asarray(a, np.float64) - b)))
               / max(float(np.max(np.abs(np.asarray(b, np.float64)))), 1e-30)
               for a, b in zip(jax.tree.leaves(program),
                               jax.tree.leaves(reference)))


def scalar_gap(program, reference) -> float:
    """Worst relative gap of two arrays of positive scalars."""
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))
