"""7-point 3D grid Laplacian.  ``kind="contrast"``: coefficients
log-uniform over 1e-3..1e3 from ``seed`` (high-contrast 3D Poisson);
``"uniform"``: unit weights.  The same graph as
``repro.data.graphs.grid3d`` builds for the same arguments; kept here
so that the benchmark's data does not change with the program."""
import numpy as np


def generate(nx: int, ny: int, nz: int, kind: str, seed: int):
    rng = np.random.default_rng(seed)
    ii, jj, kk = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    vid = (ii * ny * nz + jj * nz + kk).astype(np.int32)
    src = np.concatenate([vid[:-1, :, :].ravel(), vid[:, :-1, :].ravel(),
                          vid[:, :, :-1].ravel()])
    dst = np.concatenate([vid[1:, :, :].ravel(), vid[:, 1:, :].ravel(),
                          vid[:, :, 1:].ravel()])
    m = src.shape[0]
    if kind == "contrast":
        w = 10.0 ** rng.uniform(-3, 3, m)
    elif kind == "uniform":
        w = np.ones(m)
    else:
        raise ValueError(f"unknown grid3d kind {kind!r}")
    return (nx * ny * nz, src.astype(np.int32), dst.astype(np.int32),
            w.astype(np.float32))
