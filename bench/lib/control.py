"""The control: the plain reference put in the program's place, one step
down in precision.

The program computes squared distances in float32 in the expanded form
|q|^2 + |p|^2 - 2 q.p, with the cross term at ``HIGHEST`` precision. The
control is the brute force a later change might be tempted by: the same
expanded form over every point, with the cross term at ``high`` precision
(the TPU's three bf16 passes, written out here so that it computes the
same on every backend), or at ``bf16`` (one pass). The comparison that
decides ``correct`` has to fail it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(x):
    # reduce_precision, not a round trip through bfloat16, which the
    # compiler may drop as excess precision
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _cross(q, p, precision: str):
    """q [B, 3] . p [N, 3]^T with bf16 operands and float32 sums."""
    dot = lambda a, b: jnp.dot(a, b.T,
                               precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        return dot(_split(q)[0], _split(p)[0])
    if precision == "high":
        (qh, ql), (ph, pl) = _split(q), _split(p)
        return dot(qh, ph) + (dot(qh, pl) + dot(ql, ph))
    raise ValueError(f"unknown control precision {precision!r}")


@partial(jax.jit, static_argnames=("k", "precision"))
def _block(qb, p, pn, r2, k: int, precision: str):
    """One block of queries over every point; the points are an argument,
    not a constant, so that every block and every unit of one shape runs
    one compiled program."""
    d2 = (jnp.sum(qb * qb, axis=-1, keepdims=True) + pn[None, :]
          - 2.0 * _cross(qb, p, precision))
    d2 = jnp.where(d2 <= r2, jnp.maximum(d2, 0.0), jnp.inf)
    neg, sel = jax.lax.top_k(-d2, k)
    ok = jnp.isfinite(neg)
    return (jnp.where(ok, sel, -1), jnp.where(ok, -neg, jnp.inf),
            jnp.sum(ok, axis=-1))


def brute_force(points, queries, radius: float, k: int, precision: str,
                block: int = 256):
    """Bounded-K nearest in-range points of each query over every point:
    (indices [Q, K], distances2 [Q, K], counts [Q]) as host arrays."""
    p = jnp.asarray(points, jnp.float32)
    pn = jnp.sum(p * p, axis=-1)
    r2 = jnp.float32(radius) ** 2
    q = np.asarray(queries, np.float32)
    pad = (-len(q)) % block
    qp = np.concatenate([q, np.repeat(q[-1:], pad, axis=0)])
    outs = [jax.device_get(_block(jnp.asarray(qp[s:s + block]), p, pn, r2,
                                  k, precision))
            for s in range(0, len(qp), block)]
    idx, d2, cnt = (np.concatenate(a)[:len(q)] for a in zip(*outs))
    return idx, d2, cnt
