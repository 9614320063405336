"""Faults planted underneath the served path, for the tests: each must
turn ``correct`` false.

    alter  one answer altered where it is produced: the engine's best row
           of the first query of each dispatch is replaced by another row,
           with the score it had
    half   half of each dispatch left out: the engine answers the first
           half of the batch and hands those answers to the other half too
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _patched(transform):
    """Wrap ``ExactKNN.search``; returns the function that unwraps it."""
    from repro.core.engine import ExactKNN
    from repro.core.topk import TopK

    original = ExactKNN.search

    def search(self, request, *args, **kwargs):
        res = original(self, request, *args, **kwargs)
        scores = np.array(res.topk.scores)
        ids = np.array(res.topk.indices)
        if ids.ndim == 2:
            transform(scores, ids, self.n)
        return dataclasses.replace(res, topk=TopK(scores, ids))

    ExactKNN.search = search

    def undo():
        ExactKNN.search = original

    return undo


def _alter(scores, ids, n):
    ids[0, 0] = (ids[0, 0] + n // 2) % n


def _half(scores, ids, n):
    m = ids.shape[0]
    if m > 1:
        h = m // 2
        ids[h:] = ids[: m - h]
        scores[h:] = scores[: m - h]


def install(name: str):
    """Plant fault `name` (or none); returns the function that removes it."""
    if name == "none":
        return lambda: None
    return _patched({"alter": _alter, "half": _half}[name])
