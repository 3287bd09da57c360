"""The city fleet's base SVMs, every step in VMEM — Pallas TPU kernel.

A city round trains one tiny SVM per Data Collector: ``K`` observations
of ``F`` features, ``C`` one-vs-rest classes, ``iters`` momentum steps
(:func:`repro.core.svm._train_svm_lanes` calls it on a TPU). The DCs lie
on the lane axis, so each ``(F, 128)`` slab of a DC block holds one
feature column of 128 DCs, and both contractions are elementwise products summed over sublanes or over ``k``:

    s[k]  = sum_f x[k, f] * W[f] + b            (one row per observation)
    gW    = sum_k x[k] * dS[k] + 2 lam W

The grid is (DC block, class): classes are independent one-vs-rest
problems, so one grid cell carries one class's ``W``, ``b`` and their
momenta through every step in registers, and reads its block's data from
VMEM, ``iters`` times, after one copy from HBM. Per DC the arithmetic does
not depend on the block it lies in, so a DC's model is the same bits in
any fleet or shard.

Each product's operands are rounded to bfloat16 (the data before the
call, ``W`` and the score gradient in the kernel), and products and sums
stay float32: what a TPU's float32 ``dot`` does at its default precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_L = 128             # DCs per grid cell: one lane tile


def round_bf16(a: jax.Array) -> jax.Array:
    """float32 rounded to the nearest bfloat16 (ties to even), kept in
    float32; integer arithmetic, so no compiler pass can skip it."""
    u = jax.lax.bitcast_convert_type(a, jnp.int32)
    u = u + (0x7FFF + (jax.lax.shift_right_logical(u, 16) & 1))
    return jax.lax.bitcast_convert_type(u & -65536, jnp.float32)


def _svm_lanes_kernel(rate_ref, x_ref, y_ref, mw_ref, w_ref, b_ref,
                      x_scr, ypm_scr, neg_scr, negr_scr, *, iters: int,
                      lam: float):
    K, F, bL = x_ref.shape
    c = pl.program_id(1)
    x_scr[...] = x_ref[...].astype(jnp.float32)
    ypm = jnp.where(y_ref[...] == c, 1.0, -1.0)                 # (K, bL)
    neg = -ypm * mw_ref[...]            # d(mean hinge)/d(score), if active
    ypm_scr[...] = ypm
    neg_scr[...] = neg
    negr_scr[...] = round_bf16(neg)

    def step(i, carry):
        W, b, vW, vb = carry                          # (F, bL), (1, bL)
        Wr = round_bf16(W)
        gW = jnp.zeros((F, bL), jnp.float32)
        gb = jnp.zeros((1, bL), jnp.float32)
        for k in range(K):
            xk = x_scr[k]                                       # (F, bL)
            s = jnp.sum(xk * Wr, axis=0, keepdims=True) + b
            active = 1.0 - ypm_scr[k:k + 1, :] * s > 0.0
            gb = gb + jnp.where(active, neg_scr[k:k + 1, :], 0.0)
            gW = gW + xk * jnp.where(active, negr_scr[k:k + 1, :], 0.0)
        gW = gW + 2.0 * lam * W
        rate = rate_ref[i]
        vW = 0.9 * vW - rate * gW
        vb = 0.9 * vb - rate * gb
        return W + vW, b + vb, vW, vb

    zW = jnp.zeros((F, bL), jnp.float32)
    zb = jnp.zeros((1, bL), jnp.float32)
    W, b, _, _ = jax.lax.fori_loop(0, iters, step, (zW, zb, zW, zb))
    w_ref[0] = W
    b_ref[0] = b


def svm_lanes(x, y, mw, rate, *, num_classes: int, lam: float,
              interpret: bool = False):
    """x: (K, F, L) bfloat16; y: (K, L) int32;
    mw: (K, L) each observation's weight in the mean (mask / count);
    rate: (iters,) float32 step sizes. Returns W (C, F, L), b (C, L)."""
    from jax.experimental.pallas import tpu as pltpu

    K, F, L = x.shape
    C = num_classes
    bL = min(BLOCK_L, L)
    kernel = functools.partial(_svm_lanes_kernel, iters=rate.shape[0],
                               lam=lam)
    dc = pl.BlockSpec((K, bL), lambda j, c: (0, j))
    W, b = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(L, bL), C),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((K, F, bL), lambda j, c: (0, 0, j)), dc, dc],
        out_specs=[pl.BlockSpec((1, F, bL), lambda j, c: (c, 0, j)),
                   pl.BlockSpec((1, 1, bL), lambda j, c: (c, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((C, F, L), jnp.float32),
                   jax.ShapeDtypeStruct((C, 1, L), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((K, F, bL), jnp.float32)]
        + [pltpu.VMEM((K, bL), jnp.float32)] * 3,
        interpret=interpret,
        name="svm_lanes",
    )(rate, x, y, mw)
    return W, b[:, 0]
