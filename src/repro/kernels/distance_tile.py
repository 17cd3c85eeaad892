"""Pallas TPU kernel: tiled pairwise squared distances.

The paper's Step 2 (IS-shader sphere test) re-expressed for the MXU
(DESIGN.md section 2): ||q - p||^2 = ||q||^2 + ||p||^2 - 2 q.p^T, where the
cross term is a (TQ x D) @ (D x TP) matmul on the systolic array. The
coordinate dimension D is padded to 8 sublanes in the wrapper (zeros do not
change distances) so the MXU operand is hardware-aligned.

Grid: (Nq / TQ, Np / TP); each step computes one [TQ, TP] distance tile
entirely in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_TQ = 256
DEFAULT_TP = 512
COORD_PAD = 8  # sublane-aligned coordinate dim (3 -> 8)


def _distance_kernel(q_ref, pt_ref, out_ref):
    """q_ref [TQ, 8] f32; pt_ref [8, TP] f32 (pre-transposed); out [TQ, TP]."""
    q = q_ref[...]
    p = pt_ref[...]
    qn = jnp.sum(q * q, axis=1, keepdims=True)                 # [TQ, 1]
    pn = jnp.sum(p * p, axis=0, keepdims=True)                 # [1, TP]
    cross = jnp.dot(q, p, preferred_element_type=jnp.float32)  # MXU
    out_ref[...] = jnp.maximum(qn + pn - 2.0 * cross, 0.0)


@functools.partial(jax.jit, static_argnames=("tq", "tp", "interpret"))
def distance_tile(
    q: jax.Array,
    p: jax.Array,
    *,
    tq: int = DEFAULT_TQ,
    tp: int = DEFAULT_TP,
    interpret: bool | None = None,
) -> jax.Array:
    """Pairwise squared distances [Nq, Np] of q [Nq, 3] and p [Np, 3].

    Shapes are padded to tile multiples; padding rows produce garbage
    distances that the caller slices away.
    """
    nq, _ = q.shape
    npts, _ = p.shape
    nq_pad = (-nq) % tq
    np_pad = (-npts) % tp
    qp = jnp.pad(q.astype(jnp.float32), ((0, nq_pad), (0, COORD_PAD - 3)))
    pp = jnp.pad(p.astype(jnp.float32), ((0, np_pad), (0, COORD_PAD - 3)))
    pt = pp.T  # [8, Np_pad]

    grid = (qp.shape[0] // tq, pt.shape[1] // tp)
    out = pl.pallas_call(
        _distance_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, COORD_PAD), lambda i, j: (i, 0)),
            pl.BlockSpec((COORD_PAD, tp), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, tp), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], pt.shape[1]),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(qp, pt)
    return out[:nq, :npts]
