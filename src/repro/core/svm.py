"""Linear multiclass SVM (one-vs-rest hinge + L2), trained in JAX.

This is the paper's Step-0 base learner. No sklearn in this environment —
full-batch gradient descent with momentum on the (masked) hinge objective.
Masking lets one jitted trainer handle every Data Collector regardless of its
local sample count (samples are padded to a fixed capacity).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import count_dispatch
from repro.kernels.svm_lanes import svm_lanes


SAMPLE_BUCKETS = (16, 64)   # bucketed per-DC sample capacities (< config cap)


def sample_cap(n: int, cap: int) -> int:
    """Bucketed per-DC sample capacity for n local samples.

    Masked (padded) rows contribute exactly zero to the hinge gradient and
    to GreedyTL's Gram system, so training a DC at the smallest bucket that
    holds its data gives the same model as padding to the full scenario
    ``cap`` — while skipping the dead rows' compute, which dominates for
    the paper's Zipf-allocated fleets (most mules hold <16 of a window's
    100 observations but were padded to cap=160). The bucket set is tiny so
    the jit cache stays small; ``cap`` itself is always the last bucket.
    """
    n = min(n, cap)
    for b in SAMPLE_BUCKETS:
        if n <= b < cap:
            return b
    return cap


def svm_scores(w: jax.Array, x: jax.Array) -> jax.Array:
    """w: (F+1, C) with bias row last; x: (n, F)."""
    return x @ w[:-1] + w[-1]


def svm_predict(w, x) -> jax.Array:
    return jnp.argmax(svm_scores(w, x), axis=-1)


def _hinge_loss(w, x, y_onehot_pm, mask, lam):
    scores = svm_scores(w, x)                       # (n, C)
    margins = jnp.maximum(0.0, 1.0 - y_onehot_pm * scores)
    per_sample = jnp.sum(margins, axis=-1) * mask
    denom = jnp.maximum(1.0, jnp.sum(mask))
    return jnp.sum(per_sample) / denom + lam * jnp.sum(w[:-1] ** 2)


def _rate(i, lr: float, iters: int):
    """The cosine-decayed learning rate of step ``i``."""
    return lr * 0.5 * (1 + jnp.cos(jnp.pi * i / iters))


def _train_svm(x: jax.Array, y: jax.Array, mask: jax.Array, *,
               num_classes: int, lam: float = 1e-3, lr: float = 0.5,
               iters: int = 200, w0: jax.Array = None) -> jax.Array:
    """Unjitted trainer core — also the vmap target of the fleet trainer."""
    n, F = x.shape
    y_pm = 2.0 * jax.nn.one_hot(y, num_classes) - 1.0
    w_init = jnp.zeros((F + 1, num_classes)) if w0 is None else w0
    grad_fn = jax.grad(_hinge_loss)

    def body(i, carry):
        w, v = carry
        g = grad_fn(w, x, y_pm, mask, lam)
        v = 0.9 * v - _rate(i, lr, iters) * g
        return w + v, v

    w, _ = jax.lax.fori_loop(0, iters, body, (w_init, jnp.zeros_like(w_init)))
    return w


def _train_svm_lanes(x: jax.Array, y: jax.Array, mask: jax.Array, *,
                     num_classes: int, lam: float = 1e-3, lr: float = 0.5,
                     iters: int = 200) -> jax.Array:
    """The city fleet's trainer: :func:`_train_svm` for every DC at once,
    the DCs on the minor (lane) axis.

    x: (K, F, L); y: (K, L) int labels; mask: (K, L) {0,1}, an all-zero
    column a padding or dead DC. Returns (C, F+1, L), the bias at F.

    Each DC's model is only ``K x F x C`` products, so a batched matmul
    fills 7 of the TPU's 128 lanes. On a TPU both contractions are
    elementwise products summed over the non-lane axes, in one Pallas
    kernel that keeps each DC block's steps in VMEM
    (:mod:`repro.kernels.svm_lanes`), its operands rounded to bfloat16 as
    the TPU's default precision rounds a float32 matmul. Elsewhere it is
    ``vmap(_train_svm)``, which computes a float32 matmul as a CPU does."""
    if jax.default_backend() == "tpu":
        return _svm_lanes_pallas(x, y, mask, num_classes=num_classes,
                                 lam=lam, lr=lr, iters=iters)
    w = jax.vmap(lambda xi, yi, mi: _train_svm(
        xi, yi, mi, num_classes=num_classes, lam=lam, lr=lr, iters=iters),
        in_axes=(2, 1, 1))(x, y, mask)                    # (L, F+1, C)
    return jnp.transpose(w, (2, 1, 0))


def _svm_lanes_pallas(x, y, mask, *, num_classes: int, lam: float,
                      lr: float, iters: int, interpret: bool = False):
    """:func:`_train_svm_lanes` through the kernel; the data is rounded to
    bfloat16 once, here, and stored so."""
    denom = jnp.maximum(1.0, jnp.sum(mask, axis=0))
    W, b = svm_lanes(x.astype(jnp.bfloat16), y.astype(jnp.int32),
                     mask / denom, _rate(jnp.arange(iters), lr, iters),
                     num_classes=num_classes, lam=lam, interpret=interpret)
    return jnp.concatenate([W, b[:, None]], axis=1)


@count_dispatch("train_svm")
@partial(jax.jit, static_argnames=("num_classes", "iters"))
def train_svm(x: jax.Array, y: jax.Array, mask: jax.Array, *,
              num_classes: int, lam: float = 1e-3, lr: float = 0.5,
              iters: int = 200, w0: jax.Array = None) -> jax.Array:
    """x: (n,F) padded; y: (n,) int labels; mask: (n,) {0,1}.

    Returns w: (F+1, C). Momentum GD with cosine-decayed lr; warm start w0.
    """
    return _train_svm(x, y, mask, num_classes=num_classes, lam=lam, lr=lr,
                      iters=iters, w0=w0)


@count_dispatch("train_svm_fleet")
@partial(jax.jit, static_argnames=("num_classes", "iters"))
def train_svm_fleet(x: jax.Array, y: jax.Array, mask: jax.Array, *,
                    num_classes: int, lam: float = 1e-3, lr: float = 0.5,
                    iters: int = 200) -> jax.Array:
    """Batched base training over a padded DC fleet — ONE dispatch per window.

    x: (L, cap, F); y: (L, cap); mask: (L, cap) row validity (an all-zero
    mask row is a padding DC and trains to a harmless zero-ish model).
    Returns w: (L, F+1, C).
    """
    return jax.vmap(
        lambda xi, yi, mi: _train_svm(xi, yi, mi, num_classes=num_classes,
                                      lam=lam, lr=lr, iters=iters)
    )(x, y, mask)


def pad_fleet(xs, ys, cap: int, fleet_cap: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad a list of local datasets to a (fleet_cap, cap, F) fleet block.

    Returns (x, y, mask, dc_mask) where dc_mask (fleet_cap,) marks real DCs.
    """
    assert len(xs) <= fleet_cap
    F = xs[0].shape[1]
    x = np.zeros((fleet_cap, cap, F), np.float32)
    y = np.zeros((fleet_cap, cap), np.int32)
    m = np.zeros((fleet_cap, cap), np.float32)
    dcm = np.zeros((fleet_cap,), np.float32)
    for i, (xi, yi) in enumerate(zip(xs, ys)):
        x[i], y[i], m[i] = pad_local(xi, yi, cap)
        dcm[i] = 1.0
    return x, y, m, dcm


def pad_local(x: np.ndarray, y: np.ndarray, cap: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a local dataset to ``cap`` rows with a validity mask."""
    n = min(len(x), cap)
    F = x.shape[1]
    xp = np.zeros((cap, F), np.float32)
    yp = np.zeros((cap,), np.int32)
    mp = np.zeros((cap,), np.float32)
    xp[:n] = x[:n]
    yp[:n] = y[:n]
    mp[:n] = 1.0
    return xp, yp, mp
