"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles in ref.py
(interpret mode on CPU — kernel bodies execute in Python)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.loo_trials import loo_trials, loo_trials_ref
from repro.kernels.ref import (loo_trials_inv_reference, mha_reference,
                               rglru_reference, ssd_reference)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,Sq,Skv,d,causal,window",
    [
        (2, 4, 2, 256, 256, 64, True, 0),     # GQA causal
        (1, 8, 8, 128, 384, 64, True, 0),     # MHA, kv longer (decode-ish)
        (2, 4, 1, 256, 256, 128, True, 64),   # MQA + sliding window
        (1, 2, 2, 192, 192, 64, False, 0),    # bidirectional, ragged blocks
        (1, 4, 4, 64, 64, 32, True, 0),       # small head dim
    ])
def test_flash_attention_sweep(B, H, KV, Sq, Skv, d, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, d), dtype)
    k = jax.random.normal(ks[1], (B, KV, Skv, d), dtype)
    v = jax.random.normal(ks[2], (B, KV, Skv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < TOL[dtype], f"err={err}"


def test_flash_attention_q_offset_decode():
    """Decode semantics: 1 query at position T attends to all T+1 keys."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, H, d, T = 2, 4, 64, 128
    q = jax.random.normal(ks[0], (B, H, 1, d))
    k = jax.random.normal(ks[1], (B, H, T, d))
    v = jax.random.normal(ks[2], (B, H, T, d))
    out = flash_attention(q, k, v, causal=True, q_offset=T - 1,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=True, q_offset=T - 1)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 256, 4, 64, 32, 64),
    (1, 128, 2, 32, 64, 128),
    (2, 512, 8, 64, 128, 128),
    (1, 256, 1, 128, 16, 32),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = (jax.random.normal(ks[3], (B, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, N)) * 0.5).astype(dtype)
    y, st = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm)
    scale = float(jnp.max(jnp.abs(yr.astype(jnp.float32)))) + 1e-9
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                - yr.astype(jnp.float32)))) / scale
    tol = 3e-5 if dtype == jnp.float32 else 5e-2
    assert err < tol, f"err={err}"
    sscale = float(jnp.max(jnp.abs(sr.astype(jnp.float32)))) + 1e-9
    serr = float(jnp.max(jnp.abs(st.astype(jnp.float32)
                                 - sr.astype(jnp.float32)))) / sscale
    assert serr < tol, f"state err={serr}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,W,chunk,bw", [
    (2, 256, 256, 64, 128),
    (1, 128, 128, 128, 128),
    (3, 512, 384, 128, 128),
    (1, 64, 512, 32, 256),
])
def test_rglru_scan_sweep(B, S, W, chunk, bw, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, W)) * 0.5).astype(dtype)
    h = rglru_scan(a, b, chunk=chunk, block_w=bw, interpret=True)
    hr = rglru_reference(a, b)
    err = float(jnp.max(jnp.abs(h.astype(jnp.float32)
                                - hr.astype(jnp.float32))))
    assert err < (1e-4 if dtype == jnp.float32 else 5e-2), f"err={err}"


def _bordering_inputs(R, M, C, seed, lam_scale=1.0):
    """Shared-factor quantities for a random masked ridge system, prepared
    exactly as greedytl._score_trials does (Cholesky of the active set,
    whitened rows, candidate borderings). ``lam_scale`` scales the ridge
    penalties."""
    from jax.scipy.linalg import solve_triangular
    rng = np.random.default_rng(seed)
    D = M + C
    A = rng.normal(size=(R, D)).astype(np.float32)
    y = rng.normal(size=R).astype(np.float32)
    rmask = (rng.random(R) < 0.8).astype(np.float32)
    sel = (rng.random(M) < 0.3).astype(np.float32)
    cmask = np.concatenate([sel, np.ones(C, np.float32)])
    lam_d = (lam_scale * (np.abs(rng.normal(0.5, 0.2, D)) + 1e-3)
             ).astype(np.float32)
    A_rm = A * rmask[:, None]
    AtA = A_rm.T @ A_rm
    Aty = A_rm.T @ (y * rmask)

    L = jnp.linalg.cholesky(AtA * (cmask[:, None] * cmask[None, :])
                            + jnp.diag(lam_d))
    Am = A_rm * cmask[None, :]
    Ut = solve_triangular(L, Am.T, lower=True).T
    z = solve_triangular(L, jnp.asarray(Aty * cmask), lower=True)
    Cc = solve_triangular(L, jnp.asarray(AtA[:, :M] * cmask[:, None]),
                          lower=True)
    dsq = np.diag(AtA)[:M] + lam_d[:M] - jnp.sum(Cc ** 2, axis=0)
    dinv = jax.lax.rsqrt(jnp.maximum(dsq, 1e-8))
    zj = (Aty[:M] - Cc.T @ z) * dinv
    shared = (Ut, Cc, jnp.asarray(A_rm[:, :M]), Ut @ z,
              jnp.sum(Ut ** 2, -1), jnp.asarray(y), jnp.asarray(rmask),
              zj, dinv)
    system = (AtA, Aty, A_rm, y, rmask, cmask, lam_d)
    valid = sel == 0
    return shared, system, valid


@pytest.mark.parametrize("R,M,C,block_r", [
    (1120, 16, 7, 256),     # production shape (cap=160)
    (224, 16, 7, 256),      # small cap, single padded tile
    (448, 8, 7, 64),        # multi-tile, narrow candidate set
    (1120, 32, 7, 128),     # wide candidate set (bench shape)
    (200, 16, 4, 128),      # ragged rows (R % 8 != 0)
])
def test_loo_trials_kernel_vs_ref(R, M, C, block_r):
    """Pallas interpret path == pure-jnp oracle on random systems."""
    shared, _, _ = _bordering_inputs(R, M, C, seed=R + M)
    out = loo_trials(*shared, block_r=block_r, interpret=True)
    ref = loo_trials_ref(*shared)
    err = float(jnp.max(jnp.abs(out - ref))) / (float(jnp.max(ref)) + 1e-9)
    assert err < 2e-6, f"rel err={err}"


@pytest.mark.parametrize("R", [1, 3, 5, 7, 9, 20])
@pytest.mark.parametrize("block_r", [4, 8, 100, 256])
def test_loo_trials_small_R_and_odd_tiles(R, block_r):
    """Regression: R < 8, R not a multiple of 8, and tuned/odd block_r
    values must all snap the row tile to a sublane multiple and pad the
    tail with rmask=0 rows — not crash or mis-reduce. (The autotuner can
    hand the kernel any block_r, and tiny fleets produce tiny R.)

    With R < D = 23 rows and the default penalties the ridge nearly
    interpolates: at R=1 the fit lands within 2% of y (leverage ~0.98), so
    ``fitted - y`` cancels and the f32 rounding of the D-term dot, which
    the kernel and XLA sum in different orders, is amplified ~50x relative
    to the objective (no row reduction is involved at R=1). The stronger
    ridge keeps leverage <= 0.7, so the residual is well conditioned and
    the comparison checks the tiling and padding it is meant to."""
    shared, _, _ = _bordering_inputs(R, 16, 7, seed=R * 31 + block_r,
                                     lam_scale=30.0)
    out = loo_trials(*shared, block_r=block_r, interpret=True)
    ref = loo_trials_ref(*shared)
    err = float(jnp.max(jnp.abs(out - ref))) / (float(jnp.max(ref)) + 1e-9)
    assert err < 2e-6, f"rel err={err}"


def test_loo_trials_rejects_nonpositive_block_r():
    shared, _, _ = _bordering_inputs(64, 16, 7, seed=0)
    with pytest.raises(ValueError):
        loo_trials(*shared, block_r=0, interpret=True)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loo_trials_matches_inverse_formulation(seed):
    """Cholesky-bordering objectives == the O(M D^3) inverse-based LOO the
    kernel replaced, for every valid (not-yet-selected) candidate."""
    shared, system, valid = _bordering_inputs(1120, 16, 7, seed)
    AtA, Aty, A_rm, y, rmask, cmask, lam_d = system
    ref = np.asarray(loo_trials_inv_reference(
        jnp.asarray(AtA), jnp.asarray(Aty), jnp.asarray(A_rm),
        jnp.asarray(y), jnp.asarray(rmask), jnp.asarray(cmask),
        jnp.asarray(lam_d), 16))
    fac = np.asarray(loo_trials_ref(*shared))
    rel = np.abs(fac - ref)[valid] / np.maximum(np.abs(ref[valid]), 1e-6)
    assert rel.max() < 1e-5, rel.max()


def test_models_agree_xla_vs_pallas():
    """End-to-end: loss with attention_impl='pallas' == 'xla' reference."""
    import dataclasses

    from repro.configs import get_config
    from repro.data.pipeline import make_lm_batch
    from repro.models import build_model

    for arch in ["llama3.2-3b", "mamba2-1.3b", "recurrentgemma-9b"]:
        cfg = get_config(arch).reduced()
        m_x = build_model(cfg)
        m_p = build_model(dataclasses.replace(cfg, attention_impl="pallas"))
        params = m_x.init(jax.random.PRNGKey(0))
        batch = make_lm_batch(cfg.vocab_size, 2, 128, d_model=cfg.d_model)
        lx, _ = jax.jit(m_x.loss_fn)(params, batch)
        lp, _ = jax.jit(m_p.loss_fn)(params, batch)
        assert abs(float(lx) - float(lp)) < 1e-3, arch
