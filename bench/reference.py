"""The plain reference and the comparison that decides ``correct``.

The reference is a float64 brute force over the generated corpus, in
numpy, importing nothing of the program. It ranks rows by the same
"smaller is better" scores the service returns:

    l2  squared Euclidean distance ||q - x||^2
    ip  the negated inner product -<q, x>

and breaks ties by row id. The service computes in float32, so two rows
whose scores differ by less than float32 rounding may come back in either
order. Each served answer is held to three numbers, each with a limit:

    bad_answers  sampled requests that got no answer, or an answer that is
                 not k distinct rows of the corpus with finite scores;
    score_err    the largest |served score - float64 score of the served
                 row|, in units of u * s;
    rank_gap     the largest |float64 score of the j-th served row - the
                 j-th best float64 score|, in the same units;

where u = 2**-24 is float32's unit roundoff and s the scale of the terms a
score sums: (|q| + max|x|)^2 for l2, |q| * max|x| for ip, the maximum over
the rows served and the rows the reference ranks first.
"""
from __future__ import annotations

import dataclasses

import numpy as np

U = 2.0 ** -24


def f64_scores(x: np.ndarray, qs: np.ndarray, metric: str) -> np.ndarray:
    """(m, n) float64 scores of queries `qs` against rows `x`."""
    q64 = qs.astype(np.float64)
    x64 = x.astype(np.float64)
    dot = q64 @ x64.T
    if metric == "ip":
        return -dot
    if metric == "l2":
        return ((q64 * q64).sum(1)[:, None] - 2.0 * dot
                + (x64 * x64).sum(1)[None, :])
    raise ValueError(f"the reference knows l2 and ip, not {metric!r}")


def f64_topk(x: np.ndarray, qs: np.ndarray, k: int, metric: str,
             block: int = 65536) -> tuple[np.ndarray, np.ndarray]:
    """Exact float64 brute force: (ids, scores) of the k best rows of every
    query, ordered by (score, id), computed in blocks of corpus rows (which
    rows of an exact tie at the k-th score are kept is arbitrary)."""
    m = len(qs)
    best_s = np.full((m, 0), np.inf)
    best_i = np.zeros((m, 0), np.int64)
    for s in range(0, x.shape[0], block):
        sc = f64_scores(x[s: s + block], qs, metric)
        ids = np.broadcast_to(np.arange(s, s + sc.shape[1]), sc.shape)
        cs = np.concatenate([best_s, sc], 1)
        ci = np.concatenate([best_i, ids], 1)
        if cs.shape[1] > k:
            keep = np.argpartition(cs, k - 1, axis=1)[:, :k]
            cs = np.take_along_axis(cs, keep, 1)
            ci = np.take_along_axis(ci, keep, 1)
        best_s, best_i = cs, ci
    order = np.lexsort((best_i, best_s), axis=1)
    return (np.take_along_axis(best_i, order, 1),
            np.take_along_axis(best_s, order, 1))


def term_scale(q: np.ndarray, rows: np.ndarray, metric: str) -> float:
    """The magnitude s of the terms one score sums (module docstring)."""
    qn = float(np.linalg.norm(q.astype(np.float64)))
    xn = float(np.sqrt((rows.astype(np.float64) ** 2).sum(1).max()))
    return (qn + xn) ** 2 if metric == "l2" else qn * xn


def exact_topk(x: np.ndarray, qs: np.ndarray, k: int, metric: str,
               block: int = 131072) -> tuple[np.ndarray, np.ndarray]:
    """The same answer as :func:`f64_topk`, faster.

    A float32 pass keeps each query's 2k best rows by float32 scores; the
    candidates are rescored in float64. Any other row's float32 score is at
    least the 2k-th candidate's, and a float32 score of a d-term sum is off
    by at most gamma_d = d u / (1 - d u) times the sum of the magnitudes of
    its terms (any summation order, Higham, Accuracy and Stability of
    Numerical Algorithms, section 3.1), bounded here with a factor 2 to
    spare. Where the k-th float64 score of the candidates lies below the
    2k-th candidate's float32 score minus that bound, no other row can
    enter the top k and the answer is the brute force's; a query where it
    does not is answered by :func:`f64_topk`."""
    m, d = qs.shape
    k2 = min(2 * k, x.shape[0])
    q32 = qs.astype(np.float32)
    cand_s = np.full((m, 0), np.inf, np.float32)
    cand_i = np.zeros((m, 0), np.int64)
    max_norm_sq = 0.0
    for s in range(0, x.shape[0], block):
        xb = x[s: s + block]
        xn = np.einsum("ij,ij->i", xb, xb)
        max_norm_sq = max(max_norm_sq, float(xn.max()))
        dot = q32 @ xb.T
        sc = (xn[None, :] - 2.0 * dot) if metric == "l2" else -dot
        cs = np.concatenate([cand_s, sc], 1)
        ci = np.concatenate([cand_i, np.broadcast_to(
            np.arange(s, s + xb.shape[0]), sc.shape)], 1)
        if cs.shape[1] > k2:
            keep = np.argpartition(cs, k2 - 1, axis=1)[:, :k2]
            cs = np.take_along_axis(cs, keep, 1)
            ci = np.take_along_axis(ci, keep, 1)
        cand_s, cand_i = cs, ci
    gamma = 2.0 * d * U / (1.0 - d * U) + 4.0 * U
    big = np.sqrt(max_norm_sq)
    ids = np.empty((m, k), np.int64)
    scores = np.empty((m, k), np.float64)
    redo = []
    for j in range(m):
        q = qs[j].astype(np.float64)
        qn = float(np.linalg.norm(q))
        exact = f64_scores(x[cand_i[j]], qs[j][None, :], metric)[0]
        order = np.lexsort((cand_i[j], exact))[:k]
        ids[j], scores[j] = cand_i[j][order], exact[order]
        if metric == "l2":
            bound = gamma * (big * big + 2.0 * qn * big)
            floor = float(cand_s[j].max()) + qn * qn - bound
        else:
            bound = gamma * qn * big
            floor = float(cand_s[j].max()) - bound
        if k2 < x.shape[0] and not scores[j, -1] < floor:
            redo.append(j)
    if redo:
        ids[redo], scores[redo] = f64_topk(x, qs[redo], k, metric)
    return ids, scores


@dataclasses.dataclass
class Readings:
    """The compared numbers of one run."""

    checked: int = 0
    bad_answers: int = 0
    score_err: float = 0.0
    rank_gap: float = 0.0

    def numbers(self) -> dict:
        return {"bad_answers": self.bad_answers, "score_err": self.score_err,
                "rank_gap": self.rank_gap}


class Reference:
    """The float64 top-k of the pool entries `entries`, computed once, and
    the comparison of served answers with it."""

    def __init__(self, x: np.ndarray, pool: np.ndarray, metric: str, k: int,
                 entries):
        self.x, self.pool, self.metric, self.k = x, pool, metric, k
        entries = np.unique(np.asarray(entries, np.int64))
        self.row_of = {int(p): j for j, p in enumerate(entries)}
        if len(entries):
            self.ids, self.scores = exact_topk(x, pool[entries], k, metric)

    def compare(self, pool_idx: np.ndarray, ids: np.ndarray,
                scores: np.ndarray) -> Readings:
        """Hold served answers to the reference.

        pool_idx  (a,) the pool entry (query) of each answer
        ids       (a, k) served row ids; -1 where an answer never came
        scores    (a, k) served scores
        """
        out = Readings()
        x, k, n = self.x, self.k, self.x.shape[0]
        for p, got_i, got_s in zip(np.asarray(pool_idx).tolist(), ids, scores):
            out.checked += 1
            got_i = np.asarray(got_i, np.int64)
            got_s = np.asarray(got_s, np.float64)
            valid = (got_i.shape == (k,) and got_i.min() >= 0
                     and got_i.max() < n and len(np.unique(got_i)) == k
                     and np.isfinite(got_s).all())
            if not valid:
                out.bad_answers += 1
                continue
            j = self.row_of[p]
            q = self.pool[p]
            true_s = f64_scores(x[got_i], q[None, :], self.metric)[0]
            s = U * term_scale(q, x[np.concatenate([got_i, self.ids[j]])],
                               self.metric)
            out.score_err = max(out.score_err,
                                float(np.abs(got_s - true_s).max()) / s)
            out.rank_gap = max(out.rank_gap,
                               float(np.abs(true_s - self.scores[j]).max()) / s)
        return out

