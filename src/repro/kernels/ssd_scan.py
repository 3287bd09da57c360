"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

State-space duality: within a chunk of Q timesteps the recurrence is
evaluated in its dual quadratic (attention-like) form — two MXU matmuls over
(Q x Q) and (Q x N) tiles — while the chunk-to-chunk state (P x N per head)
is carried sequentially in VMEM scratch across the last grid dimension.

Layout: the wrapper flattens (batch, head) into the first grid dim; B/C
projections are shared across heads (single SSD group) and indexed via the
BlockSpec index map. Validated in interpret mode against the sequential
recurrence oracle ``ref.ssd_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
                h_scr, *, chunk: int, nheads: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt_l = dt_ref[0].astype(jnp.float32)      # (Q, 128), dt on every lane
    A = a_ref[0].astype(jnp.float32)          # (1, 128), A on every lane
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    # inclusive prefix sum of the log decays as a lower-triangular matmul
    # (Mosaic has no cumsum); lanes stay replicated, so the row forms of
    # dt and cs come from lane-aligned (Q, 128) transposes
    iot_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iot_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iot_i >= iot_j
    cs_l = jax.lax.dot(causal.astype(jnp.float32), dt_l * A,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)   # (Q, 128)
    dt, cs = dt_l[:, :1], cs_l[:, :1]          # (Q,1)
    dt_row, cs_row = dt_l.T[:1, :], cs_l.T[:1, :]   # (1,Q)
    seg = cs[-1:, :]                          # (1,1) total chunk decay (log)

    # intra-chunk dual form
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    decay = cs - cs_row                        # (Q,Q) log decay i<-j
    L = jnp.where(causal, jnp.exp(decay), 0.0)
    M = scores * L * dt_row                    # (Q,Q), dt_j on columns
    y_intra = jax.lax.dot(M, x, preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    h_prev = h_scr[...]                        # (P, N)
    y_inter = jnp.exp(cs) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (Q, P)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(seg) * h_prev + sum_j exp(seg - cs_j) dt_j B_j x_j
    w = jnp.exp(seg - cs) * dt                 # (Q,1)
    # the chunk decay as an (1, N) row cut from the lane-replicated cs:
    # Mosaic cannot broadcast a (1,1) value over sublanes and lanes at once
    N = h_prev.shape[1]
    seg_l = jnp.concatenate([cs_l[-1:, :]] * pl.cdiv(N, 128), axis=1)
    new_state = jnp.exp(seg_l[:, :N]) * h_prev + jax.lax.dot_general(
        x * w, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (P,N)
    h_scr[...] = new_state

    @pl.when(ci == nc - 1)
    def _final():
        state_ref[0] = new_state.astype(state_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H) (post-softplus); A: (H,) negative;
    Bm, Cm: (B,S,N) shared across heads.

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, "seq len must divide the chunk size"
    nc = S // Q

    # flatten (B,H) into the parallel grid dim; chunk dim is sequential
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = jnp.broadcast_to(dt.transpose(0, 2, 1).reshape(B * H, S)[..., None],
                           (B * H, S, 128))
    # per-head decay padded to a full lane row, like dt: a (1, 1) block
    # over a (B*H, 1) array is not (8, 128)-tileable for Mosaic
    af = jnp.broadcast_to(jnp.tile(A, B).reshape(B * H, 1, 1),
                          (B * H, 1, 128))

    kernel = functools.partial(_ssd_kernel, chunk=Q, nheads=H)
    y, state = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, Q, 128), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, 128), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, c: (i // H, c, 0)),
            pl.BlockSpec((1, Q, N), lambda i, c: (i // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, P, N), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), x.dtype),
        ],
        scratch_shapes=_scratch(P, N),
        interpret=interpret,
    )(xf, dtf, af, Bm, Cm)

    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    state = state.reshape(B, H, P, N)
    return y, state


def _scratch(P, N):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((P, N), jnp.float32)]
