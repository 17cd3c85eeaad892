"""Pallas TPU kernel: range-search candidate counting.

Counts, per query, the candidates within r (the paper's IS-call / Step-2
counter — fig08 benchmark — and the counting half of bounded range search).
Lane-partial sums are accumulated in a [TQ, 128] block across candidate
tiles and reduced in the wrapper, keeping every store lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_TQ = 256
DEFAULT_TM = 512
COORD_PAD = 8
LANES = 128


def _range_count_kernel(q_ref, pt_ref, idx_ref, out_ref, *, r2: float,
                        tm: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[...]
    p = pt_ref[0]
    idx = idx_ref[0][None, :]
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    pn = jnp.sum(p * p, axis=0, keepdims=True)
    cross = jnp.dot(q, p, preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn + pn - 2.0 * cross, 0.0)
    hit = (d2 <= r2) & jnp.broadcast_to(idx >= 0, d2.shape)
    tq = q.shape[0]
    partial = jnp.sum(
        hit.astype(jnp.int32).reshape(tq, tm // LANES, LANES), axis=1)
    out_ref[...] = out_ref[...] + partial


@functools.partial(
    jax.jit, static_argnames=("r2", "tq", "tm", "interpret"))
def range_count(
    q: jax.Array,          # [Nq, 3], Nq % tq == 0
    wnd_pos: jax.Array,    # [n_tiles, M, 3]
    wnd_idx: jax.Array,    # [n_tiles, M]
    *,
    r2: float,
    tq: int = DEFAULT_TQ,
    tm: int = DEFAULT_TM,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-query count of window candidates within sqrt(r2). Returns [Nq]."""
    assert tm % LANES == 0
    n_tiles, m, _ = wnd_pos.shape
    m_pad = (-m) % tm
    wnd_pos = jnp.pad(wnd_pos.astype(jnp.float32),
                      ((0, 0), (0, m_pad), (0, COORD_PAD - 3)))
    wnd_idx = jnp.pad(wnd_idx, ((0, 0), (0, m_pad)), constant_values=-1)
    wnd_pos_t = jnp.swapaxes(wnd_pos, 1, 2)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, COORD_PAD - 3)))
    n_m = wnd_pos_t.shape[2] // tm

    kernel = functools.partial(_range_count_kernel, r2=float(r2), tm=tm)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles, n_m),
        in_specs=[
            pl.BlockSpec((tq, COORD_PAD), lambda i, j: (i, 0)),
            pl.BlockSpec((1, COORD_PAD, tm), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, tm), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tq, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tq, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(qp, wnd_pos_t, wnd_idx)
    return jnp.sum(out, axis=1)
