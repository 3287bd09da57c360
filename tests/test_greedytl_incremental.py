"""Incremental-factor GreedyTL: property suite against the
full-refactorization oracle. DESIGN.md §11.

The carry contract: the greedy loop extends the active set's Cholesky
factor by the bordering column computed during trial scoring instead of
refactorizing, so on the same Stage-1 system it must select the sources
that a float64 loop refactorizing at every step selects
(``kernels/ref.greedy_select_refactor_reference``), with the same LOO
objective. The model follows from the selection through one final full
factorization of the selected set, so equal selections give equal models.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # deterministic shim, tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.greedytl import (_greedy_select, greedytl, greedytl_fleet,
                                 greedytl_fleet_stacked)
from repro.kernels.ref import greedy_select_refactor_reference

F, C, M_CAP = 54, 7, 16


# ---------------------------------------------------------------------------
# problem builders
# ---------------------------------------------------------------------------

def _pad_problem(x, y, n_src, seed, scale=0.5):
    rng = np.random.default_rng(seed)
    n = len(y)
    cap = max(32, n)
    xp = np.zeros((cap, F), np.float32)
    xp[:n] = x
    yp = np.zeros(cap, np.int32)
    yp[:n] = y
    mp = np.zeros(cap, np.float32)
    mp[:n] = 1
    src = np.zeros((M_CAP, F + 1, C), np.float32)
    sm = np.zeros(M_CAP, np.float32)
    for i in range(n_src):
        src[i] = rng.normal(0, scale, (F + 1, C))
        sm[i] = 1
    return tuple(jnp.asarray(v) for v in (xp, yp, mp, src, sm))


def _deep_problem(n=160, n_src=12, seed=0):
    """Greedy accepts many sources: each explains a disjoint feature block
    of the true boundary (same construction as the dispatch gate)."""
    r = np.random.default_rng(seed)
    src = np.zeros((M_CAP, F + 1, C), np.float32)
    sm = np.zeros(M_CAP, np.float32)
    w_total = np.zeros((F + 1, C), np.float32)
    for i, blk in enumerate(np.array_split(np.arange(F), n_src)):
        w = np.zeros((F + 1, C), np.float32)
        w[blk] = r.normal(0, 1.0, (len(blk), C))
        src[i] = w
        sm[i] = 1.0
        w_total += w
    x = r.normal(size=(n, F)).astype(np.float32)
    y = np.argmax(x @ w_total[:-1] + w_total[-1], axis=1).astype(np.int32)
    return tuple(jnp.asarray(v) for v in
                 (x, y, np.ones(n, np.float32), src, sm))


def _random_stacked_system(M, rows, seed, p_src=0.8, p_row=0.85):
    """Random stacked Gram system in the Stage-1 layout: D = M + C columns,
    bias block trailing, random row validity and source validity masks."""
    rng = np.random.default_rng(seed)
    D = M + C
    A = rng.normal(size=(rows, D)).astype(np.float32)
    y = rng.normal(size=rows).astype(np.float32)
    rmask = (rng.random(rows) < p_row).astype(np.float32)
    src_mask = (rng.random(M) < p_src).astype(np.float32)
    lam_d = (np.abs(rng.normal(0.8, 0.5, D)) + 1e-3).astype(np.float32)
    A_rm = A * rmask[:, None]
    return (A_rm.T @ A_rm, A_rm.T @ (y * rmask), A_rm, y, rmask, src_mask,
            lam_d)


def _stage1_system(x, y, mask, src_w, src_mask, lam_src=0.1, lam_bias=2.0):
    """GreedyTL's Stage-1 system for one local dataset, built in float64
    from the definition (unit-RMS source predictions and per-class biases
    over the n*C stacked one-vs-rest rows), in the layout of
    :func:`_random_stacked_system`."""
    x, mask, src_w = (np.asarray(v, np.float64) for v in (x, mask, src_w))
    y = np.asarray(y)
    n, M = len(y), src_w.shape[0]
    xm = x * mask[:, None]
    Yoh = (2.0 * np.eye(C)[y] - 1.0) * mask[:, None]
    H = (np.einsum("nf,mfc->mnc", xm, src_w[:, :-1])
         + src_w[:, None, -1]) * mask[None, :, None]
    s = np.sqrt(np.sum(H ** 2, axis=(1, 2))
                / (max(1.0, mask.sum()) * C)) + 1e-6
    A = np.concatenate([(H / s[:, None, None]).transpose(1, 2, 0)
                        .reshape(n * C, M), np.tile(np.eye(C), (n, 1))], 1)
    yr, rmask = Yoh.reshape(-1), np.repeat(mask, C)
    lam_d = np.concatenate([np.full(M, lam_src), np.full(C, lam_bias)]) + 1e-4
    A_rm = A * rmask[:, None]
    return (A_rm.T @ A_rm, A_rm.T @ (yr * rmask), A_rm, yr, rmask,
            np.asarray(src_mask, np.float64), lam_d)


def _assert_matches_oracle(sel, problem, k_max=16):
    """``sel``, a program's selection on ``problem`` (x, y, mask, src_w,
    src_mask), is the float64 refactorizing oracle's on the same Stage-1
    system, and the carried loop reaches the oracle's LOO objective."""
    system = _stage1_system(*problem)
    m = system[5].shape[0]
    sel_ref, traj = greedy_select_refactor_reference(*system, m, k_max=k_max)
    assert np.array_equal(np.asarray(sel), sel_ref)
    sel_inc, best = _greedy_select(
        *(jnp.asarray(v, jnp.float32) for v in system), M=m, C=C,
        k_max=k_max)
    assert np.array_equal(np.asarray(sel_inc), sel_ref)
    rel = abs(float(best) - traj[-1]) / max(abs(traj[-1]), 1e-6)
    assert rel < 1e-5, rel


# ---------------------------------------------------------------------------
# tentpole: incremental carry == full refactorization
# ---------------------------------------------------------------------------

@given(seed=st.integers(min_value=0, max_value=200),
       m=st.sampled_from([2, 8, M_CAP]),
       rows=st.sampled_from([64, 224, 400]),
       k_max=st.sampled_from([1, 3, 16]))
@settings(max_examples=15, deadline=None)
def test_incremental_selection_matches_refactor_on_random_systems(
        seed, m, rows, k_max):
    """Property: on random masked Gram systems the carried-factor loop and
    the refactorize-per-step loop accept the same sources and report the
    same objective (≤ 1e-5 rel), at every greedy depth bound."""
    system = _random_stacked_system(m, rows, seed)
    sel_inc, best_inc = _greedy_select(
        *(jnp.asarray(v) for v in system), M=m, C=C, k_max=k_max)
    sel_ref, traj = greedy_select_refactor_reference(*system, m,
                                                     k_max=k_max)
    assert np.array_equal(np.asarray(sel_inc), sel_ref)
    rel = abs(float(best_inc) - traj[-1]) / max(abs(traj[-1]), 1e-6)
    assert rel < 1e-5, rel


@given(seed=st.integers(min_value=0, max_value=60),
       m=st.sampled_from([4, 8]),
       rows=st.sampled_from([64, 160]))
@settings(max_examples=8, deadline=None)
def test_incremental_matches_float64_inverse_oracle(seed, m, rows):
    """Property: against the float64 inverse-based host oracle
    (kernels/ref.py), the incremental loop selects the same sources with
    the same objective trajectory — modulo genuine float ties, where the
    oracle's own objectives for both choices must agree ≤ 1e-4."""
    AtA, Aty, A_rm, y, rmask, src_mask, lam_d = _random_stacked_system(
        m, rows, seed)
    sel_inc, best_inc = _greedy_select(
        *(jnp.asarray(v) for v in
          (AtA, Aty, A_rm, y, rmask, src_mask, lam_d)), M=m, C=C, k_max=16)
    sel_inc = np.asarray(sel_inc)
    sel_ref, traj = greedy_select_refactor_reference(
        AtA, Aty, A_rm, y, rmask, src_mask, lam_d, m, k_max=16)
    if np.array_equal(sel_inc, sel_ref):
        rel = abs(float(best_inc) - traj[-1]) / max(abs(traj[-1]), 1e-6)
        assert rel < 1e-4, rel
    else:
        # f32-vs-f64 tie at the acceptance boundary: both final sets must
        # be indistinguishable under the oracle's own objective
        def oracle_obj(sel):
            s, t = greedy_select_refactor_reference(
                AtA, Aty, A_rm, y, rmask, sel * src_mask, lam_d, m,
                k_max=int(sel.sum()))
            return t[-1]
        o_inc, o_ref = oracle_obj(sel_inc), oracle_obj(sel_ref)
        assert abs(o_inc - o_ref) / max(abs(o_ref), 1e-6) < 1e-4


@pytest.mark.parametrize("k_max", [1, 2, 4, 8, 12, 16])
def test_depth_sweep_matches_refactor_path(k_max):
    """Greedy depths 1–16 (k_max-bounded on a deep-accepting problem): the
    entry point selects what the refactorizing oracle selects."""
    problem = _deep_problem()
    _, sel = greedytl(*problem, num_classes=C, k_max=k_max)
    assert int(np.asarray(sel).sum()) == min(k_max, 12)
    _assert_matches_oracle(sel, problem, k_max=k_max)


@given(n=st.integers(min_value=4, max_value=60),
       n_src=st.integers(min_value=0, max_value=8),
       seed=st.integers(min_value=0, max_value=100))
@settings(max_examples=10, deadline=None)
def test_random_problems_match_refactor_path(n, n_src, seed):
    """Random (possibly degenerate) local datasets and source pools: the
    oracle's selection and objective, through the public entry point."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, F)).astype(np.float32)
    y = rng.integers(0, C, n)
    problem = _pad_problem(x, y, n_src, seed)
    _, sel = greedytl(*problem, num_classes=C)
    _assert_matches_oracle(sel, problem)


def test_all_fleet_entry_points_match_refactor_oracle():
    """greedytl / greedytl_fleet / greedytl_fleet_stacked on the deep
    problem: every entry point stays bitwise equal across the lax.map
    variants and selects what the refactorizing oracle selects."""
    problem = _deep_problem()
    x, y, m, src, sm = problem
    L = 3
    xf, yf, mf = (jnp.stack([v] * L) for v in (x, y, m))
    srcs, sms = (jnp.stack([v] * L) for v in (src, sm))

    w1, s1 = greedytl(x, y, m, src, sm, num_classes=C)
    wf, sf = greedytl_fleet(xf, yf, mf, src, sm, num_classes=C)
    ws, ss = greedytl_fleet_stacked(xf, yf, mf, srcs, sms, num_classes=C)
    for i in range(L):
        assert np.array_equal(np.asarray(wf)[i], np.asarray(w1))
        assert np.array_equal(np.asarray(ws)[i], np.asarray(w1))
        assert np.array_equal(np.asarray(sf)[i], np.asarray(s1))
        assert np.array_equal(np.asarray(ss)[i], np.asarray(s1))
    _assert_matches_oracle(s1, problem)


def test_scan_engine_threads_incremental_carry():
    """Fourth entry point (scan/city engines, core/cityscan.py): the
    whole-scenario lax.scan program compiles once around the incremental
    while_loop and reproduces the fleet engine's F1 trajectory."""
    from repro.core.scenario import ScenarioConfig, run_scenario
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    kw = dict(windows=3, eval_every=1, algo="a2a")
    r_scan = run_scenario(ScenarioConfig(engine="scan", **kw), data)
    r_fleet = run_scenario(ScenarioConfig(engine="fleet", **kw), data)
    assert r_scan.f1_curve == r_fleet.f1_curve
    assert r_scan.ledger.total() == r_fleet.ledger.total()
