"""Pallas TPU kernel: RWKV6 recurrence with VMEM-resident state.

The XLA scan spills the [hd, hd] f32 state to HBM every token (~2 MB/token/
layer on rwkv6-7b — the dominant memory-roofline term of the train cell,
EXPERIMENTS.md Perf iteration 4). This kernel keeps the state in VMEM
scratch for the whole sequence: HBM traffic collapses to the r/k/v/w/out
streams. The chunked-parallel XLA form (layers.py) is the differentiable
production path; this kernel is the inference/prefill fast path and the
record of what a fused TPU implementation achieves.

Layout: inputs reshaped to [B*H, S, hd]; grid = (B*H,); one grid step owns
one (batch, head) pair's full sequence. Recurrence per token:
    out_t = r_t (S + u * k_t^T v_t) ;  S <- diag(w_t) S + k_t^T v_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 out_ref, sT_ref, state, *, seq: int):
    state[...] = s0_ref[0]

    def body(t, _):
        r_t = r_ref[0, t, :][None, :]                    # [1, hd]
        k_t = k_ref[0, t, :][None, :]
        v_t = v_ref[0, t, :][None, :]
        w_t = w_ref[0, t, :][None, :]
        u = u_ref[0][None, :]
        kv = k_t.T @ v_t                                 # [hd, hd] outer
        out = jnp.dot(r_t, state[...] + u.T * kv,
                      preferred_element_type=jnp.float32)
        out_ref[0, t, :] = out[0]
        state[...] = w_t.T * state[...] + kv
        return 0

    jax.lax.fori_loop(0, seq, body, 0)
    sT_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, state0: jax.Array, *,
              interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """r/k/v/w [B, S, H, hd] f32, u [H, hd], state0 [B, H, hd, hd] f32
    -> (out [B, S, H, hd], state_T [B, H, hd, hd])."""
    b, s, h, hd = r.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    rf, kf, vf, wf = (fold(t.astype(jnp.float32)) for t in (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, hd)
    s0 = state0.reshape(b * h, hd, hd).astype(jnp.float32)

    kernel = functools.partial(_rwkv_kernel, seq=s)
    out, s_t = pl.pallas_call(
        kernel,
        grid=(b * h,),
        in_specs=[
            pl.BlockSpec((1, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, hd), lambda i: (i, 0)),
            pl.BlockSpec((1, hd, hd), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rf, kf, vf, wf, uf, s0)
    out = out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return out, s_t.reshape(b, h, hd, hd)
