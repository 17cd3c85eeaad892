"""The plain reference: a float64 brute force on the host.

It imports nothing of the program. For each sampled query it finds every
point within the radius (exact squared distances, ascending) and judges a
search result row against the interface of ``repro.api.query``:

* ``knn``   the min(K, in-range) nearest in-range points;
* ``range`` a bounded-K subset of the in-range points: all of them when
            they number fewer than K, else any K of them.

A float32 search can rank two points whose exact squared distances lie
within its rounding of each other either way, and can put a point within
that rounding of r^2 on either side of the sphere. ``band`` is that
rounding, and such answers are ambiguous, not wrong. ``band`` is the
cell's ``d2_err_max`` limit: the largest error of a returned squared
distance that the comparison admits.
"""
from __future__ import annotations

import numpy as np


class Oracle:
    """In-range neighbours of ``queries`` among ``points``, in float64."""

    def __init__(self, points, queries, radius: float, k: int, mode: str,
                 band: float, chunk: int = 64):
        if mode not in ("knn", "range"):
            raise ValueError(f"unknown search mode {mode!r}")
        p = np.asarray(points, np.float64)
        q = np.asarray(queries, np.float64)
        self.r2, self.k, self.mode, self.band = (float(radius) ** 2, int(k),
                                                 mode, float(band))
        self.q, self.p = q, p
        pn = np.einsum("ij,ij->i", p, p)
        self.ids, self.d2 = [], []
        for s in range(0, len(q), chunk):
            qc = q[s:s + chunk]
            # the expanded form only prefilters, with room to spare; every
            # distance that decides anything is taken in difference form
            dd = (np.einsum("ij,ij->i", qc, qc)[:, None] + pn[None, :]
                  - 2.0 * (qc @ p.T))
            rows, cols = np.nonzero(dd <= self.r2 + self.band + 1e-5)
            for i, ids in enumerate(np.split(
                    cols, np.searchsorted(rows, np.arange(1, len(qc))))):
                d = np.sum((qc[i] - p[ids]) ** 2, axis=1)
                keep = d <= self.r2 + self.band
                order = np.argsort(d[keep], kind="stable")
                self.ids.append(ids[keep][order])
                self.d2.append(d[keep][order])

    def check(self, idx, d2, cnt) -> tuple[list, float]:
        """Judge result rows aligned with the oracle's queries.

        Returns (the reason each wrong row is wrong, or None for a row
        that is right; the largest |returned d2 - exact d2| over every
        returned neighbour)."""
        idx, d2, cnt = (np.asarray(a) for a in (idx, d2, cnt))
        verdicts, err_max = [], 0.0
        for i in range(len(self.ids)):
            why, err = self._check_row(i, idx[i], d2[i], int(cnt[i]))
            verdicts.append(why)
            err_max = max(err_max, err)
        return verdicts, err_max

    def _check_row(self, i, idx, d2, cnt):
        ids, od2 = self.ids[i], self.d2[i]
        got = idx[idx >= 0]
        if len(got) != cnt or len(np.unique(got)) != len(got):
            return f"count {cnt} with ids {got.tolist()}", 0.0
        if np.any(got >= len(self.p)):
            return f"ids out of range {got.max()}", 0.0
        dg = np.sum((self.q[i] - self.p[got]) ** 2, axis=1)
        err = float(np.max(np.abs(d2[idx >= 0] - dg))) if cnt else 0.0
        n_strict = int(np.sum(od2 < self.r2 - self.band))
        lo, hi = min(self.k, n_strict), min(self.k, len(ids))
        if not lo <= cnt <= hi:
            return f"count {cnt} outside [{lo}, {hi}]", err
        if cnt == 0:
            return None, err
        if np.any(dg > self.r2 + self.band):
            return f"returned a point beyond r: d2 {dg.max()}", err
        if self.mode == "range":
            if cnt < self.k:
                must = ids[od2 < self.r2 - self.band]
                if not np.all(np.isin(must, got)):
                    return (f"left out in-range ids "
                            f"{np.setdiff1d(must, got).tolist()}"), err
            return None, err
        kth = od2[cnt - 1]
        must = ids[od2 < kth - self.band]
        if not np.all(np.isin(must, got)):
            return (f"missed nearer ids "
                    f"{np.setdiff1d(must, got).tolist()}"), err
        if np.any(dg > kth + self.band):
            return f"returned an id beyond the K-th distance {kth}", err
        return None, err
