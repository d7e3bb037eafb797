"""The plain reference every answer is held to, in float64 on the host.

It imports nothing of the program and uses only the benchmark's own
graph (``generators/``): the Laplacian ``L`` is built from the edge
list, so a solve of ``L x = b`` is checked by its true relative
residual ``‖P b − L x‖ / ‖P b‖`` (``P`` the mean-zero projection, since
``L`` is singular with null space span(1)).  :class:`DirectSolve` is
an exact float64 solve (SuperLU on the grounded Laplacian); the control
rounds its answer to bfloat16, the precision below the float32 the
service computes in.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class EdgeGraph(NamedTuple):
    """An undirected weighted graph as the configuration generates it."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


def host_relres(g: EdgeGraph, x: np.ndarray, b: np.ndarray) -> float:
    """True relative residual ``‖P b − L x‖ / ‖P b‖`` in float64."""
    b = np.asarray(b, np.float64)
    b = b - b.mean()
    x = np.asarray(x, np.float64)
    diff = np.asarray(g.w, np.float64) * (x[g.src] - x[g.dst])
    lx = (np.bincount(g.src, weights=diff, minlength=g.n)
          - np.bincount(g.dst, weights=diff, minlength=g.n))
    return float(np.linalg.norm(b - lx) / max(np.linalg.norm(b), 1e-300))


class DirectSolve:
    """Exact float64 solve of ``L x = P b`` (SuperLU on ``L`` with
    vertex 0 grounded, the answer projected to mean zero)."""

    def __init__(self, g: EdgeGraph):
        import scipy.sparse as sp
        import scipy.sparse.linalg as spl
        w = np.asarray(g.w, np.float64)
        a = sp.coo_matrix((np.r_[w, w], (np.r_[g.src, g.dst],
                                         np.r_[g.dst, g.src])),
                          shape=(g.n, g.n)).tocsr()
        lap = sp.diags(np.asarray(a.sum(axis=1)).ravel()) - a
        self.n = g.n
        self._lu = spl.splu(lap[1:, 1:].tocsc())

    def solve(self, b: np.ndarray) -> np.ndarray:
        b = np.asarray(b, np.float64)
        b = b - b.mean()
        x = np.zeros(self.n)
        x[1:] = self._lu.solve(b[1:])
        return x - x.mean()


def round_to(x: np.ndarray, dtype: str) -> np.ndarray:
    """``x`` stored in ``dtype`` (``"float32"`` or ``"bfloat16"``) and
    read back as float64."""
    if dtype == "bfloat16":
        import ml_dtypes
        return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)
    return np.asarray(x).astype(dtype).astype(np.float64)
