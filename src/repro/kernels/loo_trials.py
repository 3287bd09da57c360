"""Batched Cholesky-bordering LOO trial scorer — Pallas TPU kernel.

GreedyTL's greedy source selection scores every candidate column j of the
Gram system G = AᵀA + diag(λ) by the closed-form leave-one-out (LOO) error
of the ridge over the active set S ∪ {j}. Instead of re-inverting the
(bordered) Gram per candidate, the caller factors G_S = LLᵀ once per greedy
step and hands this kernel the *shared* triangular solves

    Ut  = (L⁻¹ A_Sᵀ)ᵀ                  (R, D)  whitened data rows
    Cc  = L⁻¹ G[:, :M]                 (D, M)  candidate borderings
    zⱼ, d⁻¹                            (M,)    bordered RHS / Schur pivots
    fitted_base, h_base                (R,)    active-set fit and leverage

so each trial reduces to a rank-1 bordering (Schur complement of the added
row/column): tᵢⱼ = (Aᵢⱼ − uᵢᵀcⱼ)·dⱼ⁻¹, hᵢⱼ = h_baseᵢ + tᵢⱼ²,
fittedᵢⱼ = fitted_baseᵢ + tᵢⱼ·zⱼ — one (R,D)x(D,M) matmul plus an
elementwise epilogue and a row reduction, fused here into a single kernel
launch over row tiles (grid is sequential; a VMEM scratch accumulates the
per-candidate objectives across tiles).

The program scores its trials with the jnp form of the same contract,
``repro.core.greedytl.loo_trials``, which XLA fuses; on a TPU v5e every
tile of this kernel was slower than it, so no program code calls the
kernel. tests/test_kernels.py holds the kernel to that scorer and
tests/test_tpu_compile.py compiles it for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MAX_CANDIDATES = 128      # one lane tile; M_CAP=16 in the HTL layer


def _loo_trials_kernel(ut_ref, cc_ref, ac_ref, fb_ref, hb_ref, y_ref,
                       rm_ref, zj_ref, dinv_ref, o_ref, acc_scr, *, M: int):
    ri = pl.program_id(0)
    nr = pl.num_programs(0)

    @pl.when(ri == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    u = ut_ref[...].astype(jnp.float32)                          # (bR, D)
    t = (ac_ref[...].astype(jnp.float32)
         - jax.lax.dot(u, cc_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)) * dinv_ref[...]
    fitted = fb_ref[...] + t * zj_ref[...]                       # (bR, M)
    resid = (fitted - y_ref[...]) * rm_ref[...]
    h = hb_ref[...] + t * t
    loo = resid / jnp.maximum(1.0 - h, 0.1)
    acc_scr[:1, :M] += jnp.sum(loo * loo, axis=0, keepdims=True)

    @pl.when(ri == nr - 1)
    def _finalize():
        o_ref[...] = acc_scr[:1, :M]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def loo_trials(ut, cc, a_cand, fitted_base, h_base, y, rmask, zj, dinv, *,
               block_r: int = 256, interpret: bool = False):
    """Pallas trial scorer; same contract as ``greedytl.loo_trials``.

    Row-padding is handled here (padded rows carry rmask=0, so they add 0 to
    every objective); candidate masking (already-selected / invalid columns)
    is the caller's job — pass dinv=0 there and overwrite the result.
    """
    R, D = ut.shape
    M = cc.shape[1]
    assert M <= MAX_CANDIDATES, M
    if block_r < 1:
        raise ValueError(f"block_r must be >= 1, got {block_r}")
    # Clamp the tile to the padded row count, then snap it UP to the sublane
    # multiple: a tuned/odd block_r (or R < 8) must never produce a tile
    # that is not a multiple of 8, and the grid padding below must hold for
    # any (R, block_r) combination — tail rows carry rmask=0 and add 0.
    bR = _round_up(max(1, min(block_r, _round_up(R, 8))), 8)
    Rp = _round_up(R, bR)
    if Rp != R:
        pad = ((0, Rp - R),)
        ut = jnp.pad(ut, pad + ((0, 0),))
        a_cand = jnp.pad(a_cand, pad + ((0, 0),))
        fitted_base, h_base, y, rmask = (
            jnp.pad(v, pad) for v in (fitted_base, h_base, y, rmask))
    col = lambda v: v.reshape(-1, 1).astype(jnp.float32)
    row = lambda v: v.reshape(1, -1).astype(jnp.float32)

    kernel = functools.partial(_loo_trials_kernel, M=M)
    out = pl.pallas_call(
        kernel,
        grid=(Rp // bR,),
        in_specs=[
            pl.BlockSpec((bR, D), lambda i: (i, 0)),      # ut
            pl.BlockSpec((D, M), lambda i: (0, 0)),       # cc
            pl.BlockSpec((bR, M), lambda i: (i, 0)),      # a_cand
            pl.BlockSpec((bR, 1), lambda i: (i, 0)),      # fitted_base
            pl.BlockSpec((bR, 1), lambda i: (i, 0)),      # h_base
            pl.BlockSpec((bR, 1), lambda i: (i, 0)),      # y
            pl.BlockSpec((bR, 1), lambda i: (i, 0)),      # rmask
            pl.BlockSpec((1, M), lambda i: (0, 0)),       # zj
            pl.BlockSpec((1, M), lambda i: (0, 0)),       # dinv
        ],
        out_specs=pl.BlockSpec((1, M), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.float32),
        scratch_shapes=_scratch(),
        interpret=interpret,
    )(ut, cc, a_cand, col(fitted_base), col(h_base), col(y), col(rmask),
      row(zj), row(dinv))
    return out[0]


def _scratch():
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((8, MAX_CANDIDATES), jnp.float32)]  # obj acc (row 0)
