"""Work model of one PCG iteration, and the chip's peaks.

Bytes are counted from the true sizes of the problem — the factor's
``nnz``, the graph's ``m`` and ``n`` — never from padded shapes, panel
widths or lane counts, so the same work reads the same whatever pads
it.  The least an iteration must move through HBM:

* per distinct factor: its ``nnz`` off-diagonal entries (a float32
  value and an int32 index each), its diagonal (``n`` floats) and the
  graph's ``m`` edges (two int32 ends and a float32 weight) — each read
  once;
* per active lane: the four PCG vectors ``x``, ``r``, ``z``, ``p``
  (``n`` floats each), each read once and written once.

Sweeps per apply are counted from a factor's sweep plan, walking it as
``repro.kernels.ops.trisolve_fleet`` does for one lane, with the
program's own panel classes (``panel_class``, ``_k_classes``); a test
holds the count to the sweeps the program runs.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = I32 = 4


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def factor_bytes(nnz: int, n: int, m: int) -> int:
    """Bytes of one factor and its graph, read once."""
    return nnz * (F32 + I32) + n * F32 + m * (2 * I32 + F32)


def lane_bytes(n: int) -> int:
    """Bytes of one lane's PCG vectors, each read and written once."""
    return 4 * 2 * n * F32


def iteration_bytes(factors: Sequence[Tuple[int, int, int]],
                    lane_sizes: Sequence[int]) -> int:
    """Least bytes of one PCG pass: ``factors`` holds ``(nnz, n, m)``
    of each distinct factor the pass reads, ``lane_sizes`` the ``n`` of
    each lane it advances."""
    return (sum(factor_bytes(*f) for f in factors)
            + sum(lane_bytes(n) for n in lane_sizes))


def trisolve_sweeps(extent: np.ndarray, group_end: np.ndarray,
                    level_ptr: np.ndarray, n_levels: int, K: int,
                    width: int) -> int:
    """Sweeps one lane makes through one triangular solve of its sweep
    plan (``extent``/``group_end`` in plan order, ``level_ptr`` level
    starts, ``n_levels`` the factor's true level count) with panels
    ``K`` slots wide and ``width`` rows per sweep of the narrowest
    class."""
    from repro.kernels.ops import _k_classes, panel_class
    n = int(extent.shape[0])
    classes = _k_classes(int(K))
    cls_of = np.asarray(panel_class(np.asarray(extent)))
    rows_c = max(1, min(int(width), n))
    bound = min(max(int(n_levels), 1), int(level_ptr.shape[0]) - 1)
    start, stop = int(level_ptr[1]), int(level_ptr[bound])
    sweeps = 0
    while start < stop:
        cls = int(cls_of[start])
        kc = next((k for k in classes[:-1] if k >= cls), classes[-1])
        rows_k = max(1, rows_c * classes[0] // kc)
        start = min(start + rows_k, int(group_end[start]))
        sweeps += 1
    return sweeps
