"""GreedyTL — transfer learning through greedy source selection
(Kuzborskij, Orabona, Caputo, ICIAP 2015 [28] / CVIU 2017 [37]).

The paper (Section 4, Step 2) describes it as solving "an optimisation
problem to find the linear combination of models m(0) which maximises the
prediction accuracy with respect to the local dataset". We implement exactly
that, in two regularized-least-squares stages, both gated by the closed-form
leave-one-out (LOO) error — the selection criterion of [28]:

* **Stage 1 — greedy source combination.** Candidate pool = source
  hypotheses; each source j enters with a single scalar coefficient alpha_j
  shared across classes (this preserves the source's cross-class calibration
  — the multiclass adaptation of the binary algorithm in [28]). Exact greedy
  forward selection: at every step each remaining source is trial-added and
  the LOO error of the joint ridge recomputed; the best is kept only if it
  improves.
* **Stage 2 — local correction.** A per-class ridge over the original
  features fits the residual; it is kept only if it improves the stacked LOO
  error (with few local samples it usually is not — which is exactly why
  GreedyTL works with 2-10 points per class, paper Section 7).

Because the base hypotheses are linear (paper: linear SVM), the result
collapses EXACTLY into one linear model:

    w_eff = sum_j (alpha_j / s_j) W_src_j + W_correction (+ biases)

so the deployed model is identical to the fitted one, the on-wire model size
stays constant, and the paper's Step-4 averaging is well-posed.

**Factorized LOO (DESIGN.md §4).** Every ridge here is solved through one
masked Cholesky factor G = LLᵀ of the column-masked Gram system — never
``jnp.linalg.inv``. Trial scoring in the greedy loop reuses the factor of
the *current* active set across all M candidates via the bordering identity
(Schur complement of the added row/column), which drops per-candidate cost
from O(D³) to O(D²) and collapses the whole trial sweep into one
(R,D)x(D,M) product and an elementwise epilogue (:func:`loo_trials`, plain
jnp that XLA fuses). The Pallas kernel of the same contract
(``repro.kernels.loo_trials``) lost to it at every shape on a TPU v5e and
is not on this path.

**Incremental factor carry (DESIGN.md §11).** The greedy loop never
refactorizes at all: the factor of the active set is carried ACROSS
accepted steps in acceptance-permuted order. Accepting candidate j extends
the carried factor by the bordering column already computed during trial
scoring — c_j = L⁻¹g_j (a column of the carried ``Cc``), Schur pivot d_j —
so the whitened rows ``Ut``, the whitened RHS ``z``, the candidate
borderings ``Cc``, and the base fit/leverage all grow by one O(R) /
O(M) append instead of an O(D³) refactorization plus O(R·D²) re-solve.
All carries are fixed-shape (padded to C + min(k_max, M) active slots), so
``lax.scan``/``shard_map`` engines compile the loop once. The final
coefficients still come from one full masked factorization of the selected
set (one per call, as before), so downstream numerics are unchanged by the
carry. The tests hold the carried loop to a float64 host loop that
refactorizes at every step (``kernels/ref.greedy_select_refactor_reference``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.dispatch import count_dispatch
from repro.core.svm import svm_scores


def _chol_masked(AtA, lam_d, cmask):
    """Cholesky factor of the column-masked ridge Gram system.

    Masked-out rows/columns reduce to their diagonal λ (the 0/1 mask zeroes
    every off-diagonal entry), so the factor keeps the same masked sparsity
    and the active block factors independently — no shape change needed.
    """
    cm2 = cmask[:, None] * cmask[None, :]
    return jnp.linalg.cholesky(AtA * cm2 + jnp.diag(lam_d))


def _loo_ridge_chol(AtA, Aty, A_rm, y, rmask, cmask, lam_d):
    """Column-masked ridge + closed-form LOO error from a PRECOMPUTED Gram
    system, via Cholesky. A_rm is the row-masked data (R, D); the O(R D²)
    products AᵀA and Aᵀy are shared across callers instead of rebuilt.

    Returns (loo_sse, coeffs (D,)). The LOO identity uses the whitened rows
    Ut = (L⁻¹ Amᵀ)ᵀ: leverage h_i = ‖u_i‖² and fit ŷ_i = u_iᵀz with
    z = L⁻¹(Aᵀy) — both O(D²) per row, no inverse materialised.
    """
    L = _chol_masked(AtA, lam_d, cmask)
    Am = A_rm * cmask[None, :]
    Ut = solve_triangular(L, Am.T, lower=True).T            # (R, D)
    z = solve_triangular(L, Aty * cmask, lower=True)        # (D,)
    v = solve_triangular(L.T, z, lower=False) * cmask
    resid = (Ut @ z - y) * rmask
    h = jnp.sum(Ut ** 2, axis=-1)
    loo = resid / jnp.maximum(1.0 - h, 0.1)
    return jnp.sum(loo ** 2), v


def _loo_ridge(A, y, rmask, cmask, lam):
    """Ridge with LOO error from raw data. A: (R,D); y: (R,); rmask: (R,);
    cmask: (D,). ``lam`` may be a scalar or a per-column vector (D,).
    Thin Gram-building wrapper over :func:`_loo_ridge_chol` (the Stage-2
    per-class correction shares the factorized path with Stage 1).
    """
    D = A.shape[1]
    A_rm = A * rmask[:, None]
    lam_d = jnp.broadcast_to(lam, (D,)) + 1e-4
    return _loo_ridge_chol(A_rm.T @ A_rm, A_rm.T @ (y * rmask), A_rm, y,
                           rmask, cmask, lam_d)


def loo_trials(ut, cc, a_cand, fitted_base, h_base, y, rmask, zj, dinv):
    """GreedyTL's trial scorer: the LOO SSE of every bordered candidate.

    ut: (R, D) whitened rows of the active set; cc: (D, M) candidate
    borderings; a_cand: (R, M) candidate columns; fitted_base/h_base/y/rmask:
    (R,); zj/dinv: (M,) bordered RHS and inverse Schur pivots. Each trial is
    a rank-1 bordering t_ij = (A_ij − u_iᵀc_j)·d_j⁻¹ of the shared fit and
    leverage. Returns (M,).
    """
    t = (a_cand - ut @ cc) * dinv[None, :]                       # (R, M)
    fitted = fitted_base[:, None] + t * zj[None, :]
    resid = (fitted - y[:, None]) * rmask[:, None]
    h = h_base[:, None] + t * t
    loo = resid / jnp.maximum(1.0 - h, 0.1)
    return jnp.sum(loo * loo, axis=0)


def _score_trials(AtA, Aty, A_rm, y, rmask, cmask, lam_d, M):
    """LOO SSE of every candidate bordering j < M of the active set cmask.

    Factors the active system once, then scores all M candidates through
    the bordering identity: with c_j = L⁻¹g_j and Schur pivot
    d_j² = (G_jj + λ_j) − ‖c_j‖², the bordered factor extends every shared
    solve by one entry — t_ij = (A_ij − u_iᵀc_j)/d_j — so leverage and fit
    update by rank 1 per row; :func:`loo_trials` sweeps all (R,M) trials.
    Candidates already active (or masked) get finite garbage here; the
    greedy loop overwrites them with +inf.
    """
    L = _chol_masked(AtA, lam_d, cmask)
    Am = A_rm * cmask[None, :]
    Ut = solve_triangular(L, Am.T, lower=True).T            # (R, D)
    z = solve_triangular(L, Aty * cmask, lower=True)        # (D,)
    h_base = jnp.sum(Ut ** 2, axis=-1)
    fitted_base = Ut @ z
    Cc = solve_triangular(L, AtA[:, :M] * cmask[:, None], lower=True)
    dsq = jnp.diagonal(AtA)[:M] + lam_d[:M] - jnp.sum(Cc ** 2, axis=0)
    # already-active candidates have a degenerate (≈0) Schur pivot whose
    # rsqrt would blow up; the kernel contract wants dinv=0 for them (their
    # objective then reads as the base set's — still finite, still masked
    # to +inf by the greedy body before argmin)
    dinv = jax.lax.rsqrt(jnp.maximum(dsq, 1e-8)) * (1.0 - cmask[:M])
    zj = (Aty[:M] - Cc.T @ z) * dinv
    return loo_trials(Ut, Cc, A_rm[:, :M], fitted_base, h_base, y, rmask,
                      zj, dinv)


def _greedy_select(AtA, Aty, A_rm, yr, rmask, src_mask, lam_d, *,
                   M: int, C: int, k_max: int):
    """Greedy source selection with the factorization CARRIED across steps.

    The active set's Cholesky factor is maintained in acceptance-permuted
    order (bias columns in slots 0..C-1, then accepted sources in the order
    they were accepted) inside fixed-shape padded carries:

        Ut     (R, Dk)  whitened rows  (L⁻¹ A_activeᵀ)ᵀ, zero-padded cols
        Cc     (Dk, M)  candidate borderings L⁻¹ G[active, :M], zero rows
        z      (Dk,)    whitened RHS L⁻¹ (Aᵀy)_active
        fitted (R,)     active-set fit   Ut z
        h      (R,)     active-set leverage ‖u_i‖²

    with Dk = C + min(k_max, M). Per step, the Schur pivots d_j and
    bordered RHS z_j come straight from the carries (no factorization), the
    M-candidate sweep is one :func:`loo_trials` call, and
    accepting j appends the bordering column t_j = (A_:j − Ut c_j)/d_j to
    ``Ut``, the row (G_j: − c_jᵀCc)/d_j to ``Cc``, and z_j to ``z`` — the
    exact forward-substitution rows a from-scratch factor of the grown set
    would produce (DESIGN.md §11). No downdates are ever needed: the loop
    only accepts (it exits on the first non-improving step), so the active
    set grows monotonically. Returns (sel, best)."""
    R = A_rm.shape[0]
    Kmax = min(k_max, M)
    Dk = C + Kmax

    # bias-only seed factor (the initial active set), permuted to the front
    Lb = jnp.linalg.cholesky(AtA[M:, M:] + jnp.diag(lam_d[M:]))
    Utb = solve_triangular(Lb, A_rm[:, M:].T, lower=True).T      # (R, C)
    zb = solve_triangular(Lb, Aty[M:], lower=True)               # (C,)
    Ccb = solve_triangular(Lb, AtA[M:, :M], lower=True)          # (C, M)

    Ut0 = jnp.zeros((R, Dk)).at[:, :C].set(Utb)
    Cc0 = jnp.zeros((Dk, M)).at[:C].set(Ccb)
    z0 = jnp.zeros((Dk,)).at[:C].set(zb)
    fitted0 = Utb @ zb
    h0 = jnp.sum(Utb ** 2, axis=-1)
    resid0 = (fitted0 - yr) * rmask
    obj0 = jnp.sum((resid0 / jnp.maximum(1.0 - h0, 0.1)) ** 2)
    diagG = jnp.diagonal(AtA)[:M] + lam_d[:M]

    def cond(state):
        k, sel, best, done = state[:4]
        return (~done) & (k < Kmax)

    def body(state):
        k, sel, best, done, Ut, Cc, z, fitted, h = state
        active = sel * src_mask
        dsq = diagG - jnp.sum(Cc ** 2, axis=0)
        dinv = jax.lax.rsqrt(jnp.maximum(dsq, 1e-8)) * (1.0 - active)
        zj = (Aty[:M] - Cc.T @ z) * dinv
        objs = loo_trials(Ut, Cc, A_rm[:, :M], fitted, h, yr, rmask, zj,
                          dinv)
        objs = jnp.where((sel > 0) | (src_mask == 0), jnp.inf, objs)
        j = jnp.argmin(objs)
        improved = (objs[j] < best) & ~done
        # border append at the next free slot (every prior step accepted,
        # or the loop would already have exited)
        slot = C + k
        tcol = (A_rm[:, j] - Ut @ Cc[:, j]) * dinv[j]            # (R,)
        ccrow = (AtA[j, :M] - Cc.T @ Cc[:, j]) * dinv[j]         # (M,)
        pick = lambda new, old: jnp.where(improved, new, old)
        return (k + 1,
                pick(sel.at[j].set(1.0), sel),
                pick(objs[j], best),
                done | ~improved,
                pick(Ut.at[:, slot].set(tcol), Ut),
                pick(Cc.at[slot].set(ccrow), Cc),
                pick(z.at[slot].set(zj[j]), z),
                pick(fitted + tcol * zj[j], fitted),
                pick(h + tcol * tcol, h))

    state0 = (0, jnp.zeros(M), obj0, jnp.asarray(False),
              Ut0, Cc0, z0, fitted0, h0)
    out = jax.lax.while_loop(cond, body, state0)
    return out[1], out[2]


def _greedytl(x, y, mask, src_w, src_mask, *, num_classes: int,
              lam_src: float = 0.1, lam_x: float = 10.0,
              lam_bias: float = 2.0, k_max: int = 16):
    """Unjitted GreedyTL core — also the map target of the fleet refiner."""
    n, F = x.shape
    M, _, C = src_w.shape
    xm = x * mask[:, None]
    Yoh = (2.0 * jax.nn.one_hot(y, num_classes) - 1.0) * mask[:, None]  # (n,C)

    # source predictions H (M, n, C), normalised per source to unit RMS
    H = jax.vmap(lambda w: svm_scores(w, xm))(src_w) * mask[None, :, None]
    denom = jnp.maximum(1.0, jnp.sum(mask)) * C
    s = jnp.sqrt(jnp.sum(H ** 2, axis=(1, 2)) / denom) + 1e-6    # (M,)
    Hn = H / s[:, None, None]

    # ---- Stage 1: stacked system over (n*C) rows, unknowns = alpha + bias_c
    R = n * C
    A_src = Hn.transpose(1, 2, 0).reshape(R, M)          # (R, M)
    A_bias = jnp.tile(jnp.eye(C), (n, 1))                # (R, C)
    A = jnp.concatenate([A_src, A_bias], axis=1)         # (R, M+C)
    yr = Yoh.reshape(R)
    rmask = jnp.repeat(mask, C)
    lam_vec = jnp.concatenate([jnp.full((M,), lam_src),
                               jnp.full((C,), lam_bias)])

    # Gram system shared by every trial of every greedy step
    A_rm = A * rmask[:, None]
    AtA = A_rm.T @ A_rm
    Aty = A_rm.T @ (yr * rmask)
    lam_d = jnp.broadcast_to(lam_vec, (A.shape[1],)) + 1e-4

    # Early-exit greedy selection: once no trial improves, further steps are
    # provable no-ops, so a while_loop saves the (typically ~4x) dead steps
    # a fixed-length scan would still execute. The loop carries the
    # active-set factor across accepted steps.
    sel, _ = _greedy_select(AtA, Aty, A_rm, yr, rmask, src_mask, lam_d,
                            M=M, C=C, k_max=k_max)

    cm = jnp.concatenate([sel * src_mask, jnp.ones(C)])
    # one full factorization of the SELECTED set per call (not per step)
    # keeps the final coefficients on the exact PR-2 numerical path
    _, v1 = _loo_ridge_chol(AtA, Aty, A_rm, yr, rmask, cm, lam_d)
    alpha = v1[:M] / s                                   # undo normalisation
    bias1 = v1[M:]                                       # (C,)

    w_src_part = jnp.einsum("m,mfc->fc", alpha, src_w)   # (F+1, C)
    w_src_part = w_src_part.at[F].add(bias1)

    # ---- Stage 2: per-class local correction on the residual, LOO-gated
    fitted = jnp.einsum("m,mnc->nc", v1[:M], Hn) + bias1[None, :]
    resid = (Yoh - fitted) * mask[:, None]               # (n, C)

    def fit_class(rc):
        return _loo_ridge(xm, rc, mask, jnp.ones(F), lam_x)

    loo_x, Vx = jax.vmap(fit_class, in_axes=1, out_axes=(0, 0))(resid)
    # gate: correction kept only if summed LOO improves over zero correction
    loo_zero = jnp.sum(resid ** 2)
    keep = jnp.sum(loo_x) < loo_zero
    Vx = jnp.where(keep, Vx.T, 0.0)                      # (F, C)

    w_eff = w_src_part.at[:F].add(Vx)
    return w_eff, sel


@count_dispatch("greedytl")
@partial(jax.jit, static_argnames=("num_classes", "k_max"))
def greedytl(x, y, mask, src_w, src_mask, *, num_classes: int,
             lam_src: float = 0.1, lam_x: float = 10.0,
             lam_bias: float = 2.0, k_max: int = 16):
    """Greedy source combination + gated local correction (see module doc).

    x: (n, F) padded local data; y: (n,); mask: (n,) row validity.
    src_w: (M, F+1, C) stacked source hypotheses; src_mask: (M,).
    Returns (w_eff (F+1, C), selected (M,) 0/1 source-selection mask).
    """
    return _greedytl(x, y, mask, src_w, src_mask, num_classes=num_classes,
                     lam_src=lam_src, lam_x=lam_x, lam_bias=lam_bias,
                     k_max=k_max)


@count_dispatch("greedytl_fleet")
@partial(jax.jit, static_argnames=("num_classes", "k_max"))
def greedytl_fleet(x, y, mask, src_w, src_mask, *, num_classes: int,
                   lam_src: float = 0.1, lam_x: float = 10.0,
                   lam_bias: float = 2.0, k_max: int = 16):
    """GreedyTL at every DC of a padded fleet — ONE dispatch per window.

    x: (L, cap, F); y: (L, cap); mask: (L, cap). The source pool
    src_w (M, F+1, C) / src_mask (M,) is SHARED across the fleet (paper
    Algorithm 1: every DC refines against the same m(0) exchange).
    Returns (w_eff (L, F+1, C), selected (L, M)).

    Uses ``lax.map`` rather than ``vmap``: each DC's slice then runs the
    exact per-call computation graph, so the result is bitwise identical to
    L separate :func:`greedytl` calls (the loop engine) — vmap's batched
    linalg is not — while still costing a single dispatch. Padding DCs
    (all-zero masks) leave the greedy while_loop after one step, so they
    are nearly free.
    """
    return jax.lax.map(
        lambda t: _greedytl(t[0], t[1], t[2], src_w, src_mask,
                            num_classes=num_classes, lam_src=lam_src,
                            lam_x=lam_x, lam_bias=lam_bias, k_max=k_max),
        (x, y, mask))


@count_dispatch("greedytl_fleet_stacked")
@partial(jax.jit, static_argnames=("num_classes", "k_max"))
def greedytl_fleet_stacked(x, y, mask, src_w, src_mask, *, num_classes: int,
                           lam_src: float = 0.1, lam_x: float = 10.0,
                           lam_bias: float = 2.0, k_max: int = 16):
    """GreedyTL over a fleet where every DC carries its OWN source pool.

    Seed-stacked variant of :func:`greedytl_fleet`: several scenario
    replicas' fleets concatenate into one flat DC axis (ROADMAP: batched
    multi-seed rounds), and since each replica's window exchanged different
    base models, the pool gains a leading DC axis. x: (N, cap, F); y/mask:
    (N, cap); src_w: (N, M, F+1, C); src_mask: (N, M).
    Returns (w_eff (N, F+1, C), selected (N, M)).

    ``lax.map`` keeps the per-DC slice graph identical to :func:`greedytl`,
    so results are bitwise equal to N separate calls — one executable
    launch serves every seed replica of a sweep configuration.
    """
    return jax.lax.map(
        lambda t: _greedytl(t[0], t[1], t[2], t[3], t[4],
                            num_classes=num_classes, lam_src=lam_src,
                            lam_x=lam_x, lam_bias=lam_bias, k_max=k_max),
        (x, y, mask, src_w, src_mask))
