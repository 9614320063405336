"""Corpus and queries of a run, drawn from ``--seed``.

The corpus is the clustered distribution of ``repro.data.vector_dataset``
(Gaussian centres, Gaussian noise about them), drawn in float32 on the
host by a pool of threads, one generator per block of rows seeded from
(seed, block): the same seed gives the same rows whatever the threads do.
numpy's generators and ufuncs release the interpreter lock on large
arrays, so the blocks draw in parallel; a float64 draw, as
``vector_dataset`` makes, took about 42 s per million GIST rows. Nothing
is drawn on the device, so its peak memory reads the serving footprint.
Queries are corpus rows plus Gaussian noise, as ``query_stream`` draws
them.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 32768


def corpus(seed: int, rows: int, dim: int, data: dict) -> np.ndarray:
    """(rows, dim) float32 corpus, the same for the same seed."""
    centers = np.random.default_rng([seed, 0]).standard_normal(
        (data["clusters"], dim), dtype=np.float32)
    centers *= np.float32(data["center_scale"])
    noise = np.float32(data["noise"])
    out = np.empty((rows, dim), np.float32)

    def block(i: int) -> None:
        s = i * BLOCK_ROWS
        rng = np.random.default_rng([seed, 1, i])
        part = out[s: s + BLOCK_ROWS]
        pick = rng.integers(0, len(centers), len(part))
        rng.standard_normal(out=part, dtype=np.float32)
        part *= noise
        part += centers[pick]

    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(-(-rows // BLOCK_ROWS))))
    return out


def query_pool(x: np.ndarray, seed: int, size: int, noise: float) -> np.ndarray:
    """(size, dim) float32 queries near corpus rows."""
    rng = np.random.default_rng([seed, 2])
    rows = rng.integers(0, x.shape[0], size)
    z = rng.standard_normal((size, x.shape[1]), dtype=np.float32)
    return (x[rows] + np.float32(noise) * z).astype(np.float32)
