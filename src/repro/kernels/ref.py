"""Pure-jnp oracles for every kernel in this package.

These are the ground truth the Pallas kernels are validated against
(tests/test_kernels_*.py sweep shapes/dtypes and assert_allclose), and the
brute-force neighbor-search oracle the whole library is validated against.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Array = jax.Array


def pairwise_d2(q: Array, p: Array) -> Array:
    """Squared Euclidean distances [Nq, Np] between q [Nq, 3] and p [Np, 3].

    Expanded form |q|^2 + |p|^2 - 2 q.p^T: the -2 q.p^T term is a matmul —
    on TPU this is the MXU formulation the distance kernel uses (DESIGN.md
    section 2, Step 2); the oracle uses the same math so tolerance behaviour
    matches. The cross term runs at ``Precision.HIGHEST``: at the default
    precision the TPU multiplies f32 operands in one bf16 pass, an error
    (~4e-3 on unit-scale coordinates) far above a typical r^2. The CPU
    computes f32 products either way.
    """
    qn = jnp.sum(q * q, axis=-1, keepdims=True)            # [Nq, 1]
    pn = jnp.sum(p * p, axis=-1, keepdims=True).T          # [1, Np]
    cross = jnp.matmul(q, p.T,                              # [Nq, Np] (MXU)
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qn + pn - 2.0 * cross, 0.0)


def topk_select(d2: Array, idx: Array, k: int) -> tuple[Array, Array]:
    """Smallest-k selection along the last axis.

    ``d2`` [..., M] distances (inf = invalid), ``idx`` [..., M] candidate ids
    (-1 = invalid). Returns ([..., k] d2, [..., k] idx), ascending, padded
    with (inf, -1). Ties broken by candidate id for determinism.
    """
    m = d2.shape[-1]
    if m < k:
        pad = [(0, 0)] * (d2.ndim - 1) + [(0, k - m)]
        d2 = jnp.pad(d2, pad, constant_values=jnp.inf)
        idx = jnp.pad(idx, pad, constant_values=-1)
        m = k
    # tie-break on id: compose a sortable key
    order = jnp.argsort(d2, axis=-1, stable=True)
    d2s = jnp.take_along_axis(d2, order, axis=-1)[..., :k]
    idxs = jnp.take_along_axis(idx, order, axis=-1)[..., :k]
    idxs = jnp.where(jnp.isinf(d2s), -1, idxs)
    return d2s, idxs


@partial(jax.jit, static_argnames=("k", "mode", "chunk"))
def brute_force_search(
    points: Array,
    queries: Array,
    radius: float,
    k: int,
    mode: str = "knn",
    chunk: int = 512,
) -> tuple[Array, Array, Array]:
    """Exhaustive oracle. Returns (idx [Nq,k], d2 [Nq,k], counts [Nq]).

    Both modes return the *nearest* k within ``radius`` (for range search
    any k inside r is acceptable per the paper's bounded interface; nearest-k
    is a deterministic valid choice, which makes oracle comparison exact).
    """
    nq = queries.shape[0]
    npad = (-nq) % chunk
    qp = jnp.pad(queries, ((0, npad), (0, 0)))
    r2 = jnp.float32(radius) ** 2
    cand_idx = jnp.arange(points.shape[0], dtype=jnp.int32)

    def one_chunk(qc):
        d2 = pairwise_d2(qc, points)
        d2 = jnp.where(d2 <= r2, d2, jnp.inf)
        idx = jnp.broadcast_to(cand_idx, d2.shape)
        idx = jnp.where(jnp.isinf(d2), -1, idx)
        d2k, idxk = topk_select(d2, idx, k)
        cnt = jnp.sum((~jnp.isinf(d2k)).astype(jnp.int32), axis=-1)
        return d2k, idxk, cnt

    d2c, idxc, cntc = jax.lax.map(
        one_chunk, qp.reshape(-1, chunk, 3))
    return (
        idxc.reshape(-1, k)[:nq],
        d2c.reshape(-1, k)[:nq],
        cntc.reshape(-1)[:nq],
    )


def streaming_topk_ref(d2_tiles: Array, idx_tiles: Array, k: int
                       ) -> tuple[Array, Array]:
    """Oracle for the kernel's streaming top-k merge: given candidate tiles
    [T, n_tiles, tile_m] it must equal top-k over the flattened last axes."""
    t = d2_tiles.shape[0]
    d2 = d2_tiles.reshape(t, -1)
    idx = idx_tiles.reshape(t, -1)
    return topk_select(d2, idx, k)


def range_count_ref(q: Array, p: Array, radius: float) -> Array:
    """Number of points within ``radius`` per query (Step-2 call counter for
    the fig08 benchmark)."""
    d2 = pairwise_d2(q, p)
    return jnp.sum(d2 <= radius**2, axis=-1)
