"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
used by the shape/dtype sweep tests)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def mha_reference(q, k, v, *, causal=True, window=0, q_offset=0):
    """q: (B,H,Sq,d); k,v: (B,KV,Skv,d) -> (B,H,Sq,d). Exact softmax."""
    B, H, Sq, d = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    q_pos = q_offset + jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def ssd_reference(x, dt, A, Bm, Cm):
    """Sequential SSD recurrence (the literal state-space form).

    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm, Cm: (B,S,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp                       # (B,H,P),(B,H),(B,N),(B,N)
        decay = jnp.exp(dtt * A)                    # (B,H)
        h = h * decay[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt)
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = (x.astype(jnp.float32).transpose(1, 0, 2, 3),
          dt.astype(jnp.float32).transpose(1, 0, 2),
          Bm.astype(jnp.float32).transpose(1, 0, 2),
          Cm.astype(jnp.float32).transpose(1, 0, 2))
    hN, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), hN.astype(x.dtype)


def loo_trials_inv_reference(AtA, Aty, A_rm, y, rmask, cmask, lam_d, M):
    """Inverse-based greedy-trial scorer — the O(M·D³) formulation the
    Cholesky-bordering kernel replaces. For each candidate column j < M it
    solves the column-masked ridge over active ∪ {j} via ``jnp.linalg.inv``
    and returns the closed-form LOO SSE (M,). Ground truth for the
    trial scorers' parity tests (``greedytl.loo_trials`` and the Pallas
    ``kernels.loo_trials``).
    """
    def one(j):
        cm = jnp.where(jnp.arange(cmask.shape[0]) == j, 1.0, cmask)
        cm2 = cm[:, None] * cm[None, :]
        G = AtA * cm2 + jnp.diag(lam_d)
        Ginv = jnp.linalg.inv(G)
        v = (Ginv @ (Aty * cm)) * cm
        resid = (A_rm @ v - y) * rmask
        h = jnp.sum((A_rm @ (Ginv * cm2)) * A_rm, axis=-1)
        loo = resid / jnp.maximum(1.0 - h, 0.1)
        return jnp.sum(loo ** 2)

    return jax.vmap(one)(jnp.arange(M))


def greedy_select_refactor_reference(AtA, Aty, A_rm, y, rmask, src_mask,
                                     lam_d, M, k_max=16):
    """Full-refactorization greedy source selection — the per-step O(M·D³)
    host loop the incremental factor carry replaces. Every step re-solves
    the column-masked ridge of active ∪ {j} for all candidates j via
    ``jnp.linalg.inv`` in float64 and accepts the best iff it improves the
    LOO SSE. Ground truth for the incremental-carry property suite.

    Returns (sel (M,) 0/1 numpy, objective trajectory [bias-only LOO,
    then the accepted objective after each greedy step]).
    """
    AtA, Aty, A_rm = (np.asarray(v, np.float64) for v in (AtA, Aty, A_rm))
    y, rmask, lam_d = (np.asarray(v, np.float64) for v in (y, rmask, lam_d))
    src_mask = np.asarray(src_mask, np.float64)
    D = AtA.shape[0]
    C = D - M

    def loo_full(cm):
        cm2 = cm[:, None] * cm[None, :]
        Ginv = np.linalg.inv(AtA * cm2 + np.diag(lam_d))
        v = (Ginv @ (Aty * cm)) * cm
        resid = (A_rm @ v - y) * rmask
        h = np.sum(((A_rm * cm) @ Ginv) * (A_rm * cm), axis=-1)
        loo = resid / np.maximum(1.0 - h, 0.1)
        return float(np.sum(loo ** 2))

    sel = np.zeros(M)
    best = loo_full(np.concatenate([np.zeros(M), np.ones(C)]))
    traj = [best]
    for _ in range(min(k_max, M)):
        objs = np.full(M, np.inf)
        for j in range(M):
            if sel[j] or not src_mask[j]:
                continue
            cm = np.concatenate([sel * src_mask, np.ones(C)])
            cm[j] = 1.0
            objs[j] = loo_full(cm)
        j = int(np.argmin(objs))
        if not np.isfinite(objs[j]) or objs[j] >= best:
            break
        sel[j] = 1.0
        best = objs[j]
        traj.append(best)
    return sel, traj


def rglru_reference(a, b, h0=None):
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t.

    a, b: (B,S,W) float32; h0: (B,W) or None.
    """
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)

    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (a.astype(jnp.float32).transpose(1, 0, 2),
                          b.astype(jnp.float32).transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2)
