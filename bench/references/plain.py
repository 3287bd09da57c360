"""The plain reference's arithmetic: the paper's base SVM, GreedyTL and the
streamed evaluation, written from their published description in NumPy.

Nothing here imports the program. Every array is float64; explicit solves
stand in for the program's Cholesky factor carry and bordering identity.
A :class:`Num` says how results are rounded: ``q`` is applied to the result
of every arithmetic step, ``d`` to the operands of every matrix product.

* ``tpu_default``, the precision the configurations state: float32
  results, and products whose operands are rounded to bfloat16 and
  accumulated in float32 (what the TPU does with a float32 product at its
  default precision);
* ``float32``: float32 throughout, what the program computes on a CPU;
* ``control``: the contract's control, one precision below the stated
  one: every result rounded to bfloat16 and every product's operands to
  float8 (e4m3, saturating at its largest finite value).

The small linear solves round their inputs and outputs and solve in
between at float64 (in the control too, which is, if anything, kinder
than a solve done in low precision throughout).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import ml_dtypes
import numpy as np

Round = Callable[[np.ndarray], np.ndarray]

M_CAP = 16                 # source hypotheses per GreedyTL call


def bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


E4M3_MAX = float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)


def f32(x):
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def fp8(x):
    x = np.clip(np.asarray(x, np.float64), -E4M3_MAX, E4M3_MAX)
    return x.astype(ml_dtypes.float8_e4m3fn).astype(np.float64)


class Num(NamedTuple):
    q: Round               # every arithmetic result
    d: Round               # every matrix product's operands

    def mm(self, a, b):
        return self.q(self.d(a) @ self.d(b))

    def ein(self, spec: str, a, b):
        return self.q(np.einsum(spec, self.d(a), self.d(b)))


TPU_DEFAULT = Num(f32, bf16)
NUMS = {"tpu_default": TPU_DEFAULT, "float32": Num(f32, f32),
        "control": Num(bf16, fp8)}


def onehot(y: np.ndarray, c: int) -> np.ndarray:
    return np.eye(c)[np.asarray(y, np.int64)]


# ---------------------------------------------------------------------------
# base learner: one-vs-rest hinge + L2, momentum GD, cosine learning rate
# ---------------------------------------------------------------------------

def svm_train(x: np.ndarray, y: np.ndarray, mask: np.ndarray, *,
              classes: int, iters: int, num: Num = TPU_DEFAULT,
              lam: float = 1e-3, lr: float = 0.5) -> np.ndarray:
    """Batched over a leading DC axis: x (B, n, F), y (B, n), mask (B, n).
    Minimises mean_i mask_i sum_c max(0, 1 - y_ic s_ic) + lam |W|^2 (the
    bias row unregularised) from zero by ``iters`` momentum steps (0.9)
    with the rate ``lr * (1 + cos(pi i / iters)) / 2``. Returns
    (B, F+1, C), the bias in the last row."""
    q = num.q
    x = q(x)
    B, n, F = x.shape
    ypm = 2.0 * onehot(y, classes) - 1.0                      # (B, n, C)
    m = np.asarray(mask, np.float64)
    denom = np.maximum(1.0, m.sum(axis=1))                    # (B,)
    W = np.zeros((B, F, classes))
    b = np.zeros((B, classes))
    vW = np.zeros_like(W)
    vb = np.zeros_like(b)
    xt = np.ascontiguousarray(np.swapaxes(x, 1, 2))
    for i in range(iters):
        s = q(num.mm(x, W) + b[:, None, :])
        active = q(1.0 - q(ypm * s)) > 0.0
        dS = q(-ypm * active * (m / denom[:, None])[:, :, None])
        gW = q(num.mm(xt, dS) + q(2.0 * lam * W))
        gb = q(dS.sum(axis=1))
        rate = lr * 0.5 * (1.0 + np.cos(np.pi * i / iters))
        vW = q(q(0.9 * vW) - q(rate * gW))
        vb = q(q(0.9 * vb) - q(rate * gb))
        W = q(W + vW)
        b = q(b + vb)
    return np.concatenate([W, b[:, None, :]], axis=1)


def scores(w: np.ndarray, x: np.ndarray, num: Num = TPU_DEFAULT) -> np.ndarray:
    return num.q(num.mm(x, w[:-1]) + w[-1])


# ---------------------------------------------------------------------------
# GreedyTL (Kuzborskij et al.): greedy source selection by closed-form
# leave-one-out error of a ridge, then a LOO-gated per-class correction
# ---------------------------------------------------------------------------

def _solve(G: np.ndarray, rhs: np.ndarray, q: Round) -> np.ndarray:
    return q(np.linalg.solve(q(G), q(rhs)))


def _loo(G: np.ndarray, A: np.ndarray, g: np.ndarray, y: np.ndarray,
         rmask: np.ndarray, num: Num) -> Tuple[np.ndarray, np.ndarray]:
    """Ridge systems of one size, batched over a leading trial axis:
    G (T, k, k) with the ridge on its diagonal, A (T, R, k) the row-masked
    columns, g (T, k) = A^T y. Returns (LOO sum of squares (T,), v (T, k)).
    Leverage h_i = a_i^T G^-1 a_i; LOO residual r_i / max(1 - h_i, 0.1)."""
    q = num.q
    sol = _solve(G, np.concatenate([g[..., None], np.swapaxes(A, 1, 2)],
                                   axis=2), q)
    v, GiAt = sol[..., 0], sol[..., 1:]                       # (T,k) (T,k,R)
    h = num.ein("trk,tkr->tr", A, GiAt)
    fit = num.ein("trk,tk->tr", A, v)
    resid = q((fit - y[None]) * rmask[None])
    loo = q(resid / np.maximum(q(1.0 - h), 0.1))
    return q((loo ** 2).sum(axis=1)), v


def greedytl(x: np.ndarray, y: np.ndarray, mask: np.ndarray,
             src_w: np.ndarray, src_mask: np.ndarray, *, classes: int,
             num: Num = TPU_DEFAULT, lam_src: float = 0.1, lam_x: float = 10.0,
             lam_bias: float = 2.0, k_max: int = 16) -> np.ndarray:
    """x (n, F), y (n,), mask (n,); src_w (M, F+1, C), src_mask (M,).
    Returns the combined linear model (F+1, C)."""
    q = num.q
    C = classes
    n, F = x.shape
    M = src_w.shape[0]
    m = np.asarray(mask, np.float64)
    xm = q(x * m[:, None])
    Yoh = (2.0 * onehot(y, C) - 1.0) * m[:, None]              # (n, C)

    # each source's predictions on the local data, scaled to unit RMS
    H = q(num.ein("nf,mfc->mnc", xm, src_w[:, :F])
          + src_w[:, F][:, None, :]) * m[None, :, None]
    denom = max(1.0, m.sum()) * C
    s = q(np.sqrt(q(q((H ** 2).sum(axis=(1, 2))) / denom)) + 1e-6)
    Hn = q(H / s[:, None, None])

    # stage 1: one coefficient per source (shared across classes) and one
    # bias per class, over the stacked (n*C) rows
    R = n * C
    rmask = np.repeat(m, C)
    A = np.concatenate([Hn.transpose(1, 2, 0).reshape(R, M),
                        np.tile(np.eye(C), (n, 1))], axis=1)
    A = A * rmask[:, None]
    yr = Yoh.reshape(R) * rmask
    lam = np.concatenate([np.full(M, lam_src), np.full(C, lam_bias)]) + 1e-4
    G = num.mm(A.T, A)
    g = num.mm(A.T, yr)

    def objective(sets):
        cols = np.asarray(sets)                                # (T, k)
        Gs = q(G[cols[:, :, None], cols[:, None, :]]
               + np.einsum("tk,kj->tkj", lam[cols], np.eye(cols.shape[1])))
        As = np.transpose(A[:, cols], (1, 0, 2))               # (T, R, k)
        return _loo(Gs, As, g[cols], yr, rmask, num)

    active = list(range(M, M + C))                             # biases
    best = objective([active])[0][0]
    chosen = []
    for _ in range(min(k_max, M)):
        cands = [j for j in range(M) if src_mask[j] > 0 and j not in chosen]
        if not cands:
            break
        objs, _ = objective([active + [j] for j in cands])
        i = int(np.argmin(objs))
        if not objs[i] < best:
            break
        best = objs[i]
        chosen.append(cands[i])
        active = active + [cands[i]]

    _, v = objective([active])
    coef = np.zeros(M + C)
    coef[active] = v[0]
    alpha = q(coef[:M] / s)
    bias = coef[M:]
    w = num.ein("m,mfc->fc", alpha, src_w)
    w[F] = q(w[F] + bias)

    # stage 2: per-class ridge correction on the residual, kept only when
    # its summed LOO error beats the uncorrected residual
    fit = q(num.ein("m,mnc->nc", coef[:M], Hn) + bias[None, :])
    resid = q((Yoh - fit) * m[:, None])                        # (n, C)
    G2 = q(num.mm(xm.T, xm) + np.diag(np.full(F, lam_x + 1e-4)))
    loo_x, vx = _stage2(G2, xm, resid, m, num)
    if q(loo_x.sum()) < q((resid ** 2).sum()):
        w[:F] = q(w[:F] + vx.T)
    return w


def _stage2(G2, xm, resid, m, num: Num):
    """Per-class ridge of the residual on the local features (one Gram
    system shared by the classes): returns the per-class LOO sums (C,) and
    coefficients (C, F)."""
    q = num.q
    sol = _solve(G2, np.concatenate([num.mm(xm.T, resid), xm.T], axis=1), q)
    C = resid.shape[1]
    V, GiXt = sol[:, :C], sol[:, C:]                          # (F,C) (F,n)
    h = q((xm * GiXt.T).sum(axis=1))
    res = q((num.mm(xm, V) - resid) * m[:, None])
    loo = q(res / np.maximum(q(1.0 - h), 0.1)[:, None])
    return q((loo ** 2).sum(axis=0)), V.T


# ---------------------------------------------------------------------------
# evaluation: confusion counts and the paper's F-measure (Sec. 5.2)
# ---------------------------------------------------------------------------

def confusion(w: np.ndarray, x_test: np.ndarray, y_test: np.ndarray,
              classes: int, num: Num = TPU_DEFAULT) -> np.ndarray:
    pred = np.argmax(scores(w, x_test, num), axis=1)
    cm = np.zeros((classes, classes), np.int64)
    np.add.at(cm, (np.asarray(y_test, np.int64), pred), 1)
    return cm


def f_measure(cm: np.ndarray) -> float:
    """Precision is the overall accuracy, recall the macro per-class
    accuracy, F their harmonic mean."""
    p = np.trace(cm) / cm.sum()
    rows = cm.sum(axis=1)
    rec = [cm[c, c] / rows[c] for c in range(len(cm)) if rows[c] > 0]
    r = float(np.mean(rec)) if rec else 0.0
    return 0.0 if p + r == 0 else float(2.0 * p * r / (p + r))


def entropy(y: np.ndarray, classes: int) -> float:
    """Label entropy, log base ``classes``: -sum_c p_c ln(p_c) / ln(K)
    over the classes present, summed in class order. The order is part of
    the election's definition: two DCs whose counts are a permutation of
    each other tie exactly only when both sums run in one order."""
    if len(y) == 0:
        return 0.0
    cnt = np.bincount(np.asarray(y, np.int64), minlength=classes).astype(
        np.float64)
    p = cnt / cnt.sum()
    p = p[p > 0]
    return float(-(p * np.log(p) / np.log(classes)).sum())
