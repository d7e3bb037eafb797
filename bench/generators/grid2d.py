"""Weighted 5-point 2D grid Laplacian: edge weights uniform on
[0.5, 2.0] from ``seed`` (a 2D diffusion operator).  The same graph as
``repro.data.graphs.grid2d`` builds for the same arguments; kept here
so that the benchmark's data does not change with the program."""
import numpy as np


def generate(nx: int, ny: int, seed: int):
    rng = np.random.default_rng(seed)
    ii, jj = np.meshgrid(np.arange(nx), np.arange(ny), indexing="ij")
    vid = (ii * ny + jj).astype(np.int32)
    src = np.concatenate([vid[:-1, :].ravel(), vid[:, :-1].ravel()])
    dst = np.concatenate([vid[1:, :].ravel(), vid[:, 1:].ravel()])
    w = rng.uniform(0.5, 2.0, src.shape[0])
    return (nx * ny, src.astype(np.int32), dst.astype(np.int32),
            w.astype(np.float32))
