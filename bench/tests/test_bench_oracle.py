"""The float64 oracle and its lower-precision control, on the CPU.

A search result computed at float32 in difference form passes; the same
brute force with its cross term at bf16 (one pass) or at ``high`` (three
bf16 passes) fails, by wrong rows or by the squared-distance error."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib.control import brute_force  # noqa: E402
from bench.lib.names import load_module  # noqa: E402
from bench.lib.oracle import Oracle  # noqa: E402
from bench.lib.rng import rng_for  # noqa: E402

LIMIT = 2e-6


def exact_f32(points, queries, radius, k):
    """Bounded-K nearest in-range points, float32 difference form."""
    d2 = np.sum((queries[:, None, :] - points[None, :, :]) ** 2, axis=-1,
                dtype=np.float32)
    d2 = np.where(d2 <= np.float32(radius) ** 2, d2, np.inf)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    dk = np.take_along_axis(d2, order, axis=1)
    ok = np.isfinite(dk)
    return np.where(ok, order, -1), dk, ok.sum(axis=1)


def knn_case():
    gen = load_module("scenes", "kitti_like_cloud").generate
    pts = gen(rng_for(2**40 + 11), points=4000, z_range=0.04)
    rows = np.sort(rng_for(2**40 + 11, 3).choice(len(pts), 300,
                                                 replace=False))
    return pts, pts[rows], 0.05, 16, "knn"


def range_case():
    gen = load_module("scenes", "jittered_lattice").generate
    pts = gen(rng_for(12345), lattice=(16, 8, 8), spacing=1 / 16,
              jitter=0.1)
    rows = np.sort(rng_for(12345, 3).choice(len(pts), 300, replace=False))
    return pts, pts[rows], 0.125, 64, "range"


CASES = {"knn": knn_case, "range": range_case}


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_result_passes(case):
    pts, q, r, k, mode = CASES[case]()
    oracle = Oracle(pts, q, r, k, mode, band=LIMIT)
    verdicts, err = oracle.check(*exact_f32(pts, q, r, k))
    assert [v for v in verdicts if v is not None] == []
    assert err <= LIMIT
    # the case exercises what it is for: full rows in knn, all in-range
    # neighbours under K in range mode
    counts = np.array([len(ids) for ids in oracle.ids])
    assert (counts > k).any() if mode == "knn" else counts.max() < k


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("precision", ["bf16", "high"])
def test_lower_precision_control_fails(case, precision):
    pts, q, r, k, mode = CASES[case]()
    oracle = Oracle(pts, q, r, k, mode, band=LIMIT)
    verdicts, err = oracle.check(*brute_force(pts, q, r, k, precision))
    assert err > 3 * LIMIT or any(v is not None for v in verdicts)


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_search_passes(case):
    import repro.api as api
    pts, q, r, k, mode = CASES[case]()
    params = api.SearchParams(radius=r, k=k, mode=mode,
                              knn_window="exact")
    res = api.query(api.build_index(pts, params), q)
    oracle = Oracle(pts, q, r, k, mode, band=LIMIT)
    verdicts, err = oracle.check(res.indices, res.distances2, res.counts)
    assert [v for v in verdicts if v is not None] == []
    assert err <= LIMIT


def test_oracle_names_each_fault():
    pts, q, r, k, mode = knn_case()
    oracle = Oracle(pts, q, r, k, mode, band=LIMIT)
    idx, d2, cnt = exact_f32(pts, q, r, k)
    full = int(np.argmax(cnt))
    assert cnt[full] == k
    faults = {}
    i2 = idx.copy()
    i2[full, 0] = i2[full, 1]                      # a duplicate id
    faults["count"] = (i2, d2, cnt)
    c2 = cnt.copy()
    c2[full] -= 1                                   # a row cut short
    i3 = idx.copy()
    i3[full, -1] = -1
    faults["outside"] = (i3, d2, c2)
    far = int(np.argmax(np.sum((pts - q[full]) ** 2, axis=1)))
    i4 = idx.copy()
    i4[full, 0] = far                               # a point beyond r
    faults["beyond r"] = (i4, d2, cnt)
    for what, (a, b, c) in faults.items():
        verdicts, _ = oracle.check(a, b, c)
        assert verdicts[full] is not None, what
        assert sum(v is not None for v in verdicts) == 1, what
    d5 = d2.copy()
    d5[full, 0] += 10 * LIMIT                       # a distance off
    assert oracle.check(idx, d5, cnt)[1] > LIMIT
