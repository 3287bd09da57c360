"""Scan-over-windows engine (repro.core.cityscan): fleet-engine parity,
city-mode smoke + determinism, shard-count invariance (subprocess, 8 fake
devices), EvalCache keying isolation, and the exact equivalence of the
confusion-count metric forms used by the streamed eval."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # deterministic shim, tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.metrics import (confusion_counts, f_measure,
                                f_measure_from_confusion, precision,
                                precision_from_confusion, recall,
                                recall_from_confusion)
from repro.core.scenario import (EvalCache, ScenarioConfig, run_scenario,
                                 _eval_cache)
from repro.data.synthetic_covtype import make_covtype_like

REPO = os.path.join(os.path.dirname(__file__), "..")
DATA = make_covtype_like(seed=0)
W = 5


# ---------------------------------------------------------------------------
# scan engine == fleet engine (the PR-1 parity oracle): the ledger is
# host-replayed so it must be *exactly* equal, and the streamed confusion
# eval reproduces the fleet engine's F1 values exactly on these configs
# ---------------------------------------------------------------------------

PARITY_CFGS = [
    ScenarioConfig(windows=W, eval_every=1, algo="a2a", tech="wifi", seed=1),
    ScenarioConfig(windows=W, eval_every=1, algo="star", tech="wifi", seed=1),
    ScenarioConfig(windows=W, eval_every=1, algo="star", tech="mesh:hops=2",
                   seed=2, aggregate=True),
    ScenarioConfig(windows=W, eval_every=2, algo="a2a", tech="4g", seed=3,
                   n_subsample=5),
]


@pytest.mark.parametrize("cfg", PARITY_CFGS,
                         ids=lambda c: f"{c.algo}_{c.tech}_s{c.seed}")
def test_scan_matches_fleet_engine(cfg):
    ref = run_scenario(dataclasses.replace(cfg, engine="fleet"), DATA)
    got = run_scenario(dataclasses.replace(cfg, engine="scan"), DATA)
    assert got.ledger.events == ref.ledger.events
    assert got.f1_curve == ref.f1_curve


# ---------------------------------------------------------------------------
# city engine: smoke, determinism, O(1) ledger events per window
# ---------------------------------------------------------------------------

CITY = ScenarioConfig(windows=3, eval_every=1, algo="star", engine="scan",
                      tech="wifi", fleet_size=40, obs_per_dc=4,
                      train_iters=5)


def test_city_engine_smoke():
    r = run_scenario(CITY, DATA)
    assert len(r.f1_curve) == CITY.windows
    assert all(0.0 < v <= 1.0 for v in r.f1_curve)
    assert r.f1_curve[-1] > 0.25          # it actually learns
    # analytic energy: exactly 4 ledger events per window (collection +
    # entropy index + center id + model gather), never O(L^2)
    assert len(r.ledger.events) == 4 * CITY.windows
    assert r.energy_collection > 0 and r.energy_learning > 0


def test_city_engine_deterministic():
    a = run_scenario(CITY, DATA)
    b = run_scenario(CITY, DATA)
    assert a.f1_curve == b.f1_curve
    assert a.ledger.events == b.ledger.events


def test_city_perwindow_reference_runs():
    from repro.core.cityscan import run_city_perwindow
    r = run_city_perwindow(CITY, DATA)
    assert len(r.f1_curve) == CITY.windows
    assert all(0.0 < v <= 1.0 for v in r.f1_curve)
    assert len(r.ledger.events) == 4 * CITY.windows


def test_city_mode_config_validation():
    with pytest.raises(ValueError, match="engine='scan'"):
        run_scenario(dataclasses.replace(CITY, engine="fleet",
                                         train_iters=200), DATA)
    with pytest.raises(ValueError, match="host-side collection"):
        run_scenario(dataclasses.replace(CITY, p_edge=0.5), DATA)
    with pytest.raises(ValueError, match=">= 2 DCs"):
        run_scenario(dataclasses.replace(CITY, fleet_size=1), DATA)


# ---------------------------------------------------------------------------
# shard-count invariance: sharded fleet rounds must match unsharded bitwise
# (one-hot psum + lexicographic election — DESIGN.md §10). The XLA fake-
# device flag must precede jax init, so the sweep owns its own process.
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax
    import numpy as np
    from repro.core.cityscan import M_CAP, run_city
    from repro.core.scenario import ScenarioConfig
    from repro.data.synthetic_covtype import make_covtype_like

    assert len(jax.devices()) == 8, jax.devices()
    data = make_covtype_like(seed=0)
    base = ScenarioConfig(windows=3, eval_every=1, algo="star",
                          engine="scan", tech="wifi", obs_per_dc=4,
                          train_iters=5)
    # padded caps 64 / 128 / 224: shard counts 2,4,8 all divide them (at
    # 8 shards of the 64-DC fleet each shard holds fewer DCs than M_CAP)
    for fleet_size, seed in ((40, 0), (100, 1), (200, 2)):
        cfg = dataclasses.replace(base, fleet_size=fleet_size, seed=seed)
        ref = run_city(cfg, data, max_shards=1)
        assert (ref.sources["pool"] >= M_CAP).any()    # the screen chose
        for shards in (2, 4, 8):
            got = run_city(cfg, data, max_shards=shards)
            assert got.f1_curve == ref.f1_curve, (fleet_size, shards)
            assert got.ledger.events == ref.ledger.events, \\
                (fleet_size, shards)
            assert np.array_equal(got.models, ref.models), \\
                (fleet_size, shards)
            for k in ("centre", "pool", "selected"):
                assert np.array_equal(got.sources[k], ref.sources[k]), \\
                    (fleet_size, shards, k)
    print("SHARD-INVARIANCE-OK")
""")


@pytest.mark.slow
def test_city_sharded_bitwise_matches_unsharded_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "SHARD-INVARIANCE-OK" in proc.stdout


# ---------------------------------------------------------------------------
# the fleet-wide source screen (DESIGN.md §10): the pool is the M_CAP best
# candidates by GreedyTL's own first-step objective, never the centre, a
# dead DC or padding; and the city run matches the plain reference
# ---------------------------------------------------------------------------

def _screen_case(fleet_size, K=14, seed=3, dead_every=0, alive_max=None):
    """Base models, the centre's data and the alive mask of one city
    window at ``fleet_size`` DCs (padded to ``fleet_cap``), trained for
    20 iterations on the CPU."""
    import jax
    import jax.numpy as jnp
    from repro.core import cityscan

    L = cityscan.city_fleet_pad(fleet_size)
    xtr, ytr = cityscan._train_arrays(DATA)
    gid = jnp.arange(L, dtype=jnp.int32)
    alive = np.arange(L) < fleet_size
    if dead_every:
        alive &= np.arange(L) % dead_every != 1
    if alive_max is not None:
        alive &= np.cumsum(alive) <= alive_max
    x, y, m = cityscan._draw_window(xtr, ytr, jax.random.PRNGKey(seed), 0,
                                    gid, jnp.asarray(alive, jnp.float32), K)
    base = jax.vmap(lambda a, b, c: cityscan._train_svm(
        a, b, c, num_classes=7, iters=20))(x, y, m)
    cg, _ = cityscan._elect(y, m, jnp.asarray(alive), gid, num_classes=7,
                            shards=1)
    cg = int(cg)
    return base, x[cg], y[cg], alive, gid, cg


def _screen(base, cx, cy, alive, gid, cg):
    import jax
    from repro.core import cityscan

    src, mask, pool = jax.jit(lambda *a: cityscan._screen(
        *a, num_classes=7, shards=1))(base, cx, cy, alive, gid, cg)
    return np.asarray(src), np.asarray(mask), np.asarray(pool)


def test_screen_pool_is_the_brute_force_ranking():
    """Every candidate's (1 + C)-unknown ridge written out and solved by
    Cholesky (``greedytl._loo_ridge``, no bordering identity) and ranked
    by (objective, gid): the screen's pool is that ranking's first M_CAP,
    in order, with each candidate's own base model."""
    import jax.numpy as jnp
    from repro.core import cityscan
    from repro.core.greedytl import _loo_ridge

    base, cx, cy, alive, gid, cg = _screen_case(64)
    src, mask, pool = _screen(base, cx, cy, alive, gid, cg)

    K, C = cx.shape[0], 7
    H = np.asarray(jnp.einsum("kf,lfc->lkc", cx, base[:, :-1])
                   + base[:, -1][:, None, :], np.float64)
    s = np.sqrt((H ** 2).sum(axis=(1, 2)) / (K * C)) + 1e-6
    Hn = H / s[:, None, None]
    y = (2.0 * np.eye(C)[np.asarray(cy)] - 1.0).reshape(-1)
    bias = np.tile(np.eye(C), (K, 1))
    lam = jnp.asarray(np.concatenate([[0.1], np.full(C, 2.0)]))
    objs = np.array([float(_loo_ridge(
        jnp.asarray(np.concatenate([Hn[j].reshape(-1, 1), bias], axis=1),
                    jnp.float32),
        jnp.asarray(y, jnp.float32), jnp.ones(K * C), jnp.ones(1 + C),
        lam)[0]) for j in range(len(Hn))])
    cand = np.flatnonzero(alive & (np.arange(len(alive)) != cg))
    order = cand[np.lexsort((cand, objs[cand]))]
    want = order[:cityscan.M_CAP]
    # the ranking is not decided by rounding: rank 16 and 17 lie apart
    assert objs[order[16]] - objs[order[15]] > 1e-4 * objs[order[15]]
    assert pool.tolist() == want.tolist()
    assert mask.tolist() == [1.0] * cityscan.M_CAP
    np.testing.assert_array_equal(src, np.asarray(base)[want])
    scores = np.asarray(cityscan._screen_scores(base, cx, cy,
                                                num_classes=C))
    np.testing.assert_allclose(scores[cand], objs[cand], rtol=2e-5)
    assert (want >= cityscan.M_CAP).any()     # not the first-ids rule


@pytest.mark.parametrize("fleet,dead_every,alive_max",
                         [(50, 3, None), (50, 0, 6), (5, 0, None)])
def test_screen_never_pools_the_centre_the_dead_or_padding(fleet, dead_every,
                                                          alive_max):
    """At 50 DCs padded to 64, with every third DC dead (the pool is
    full) or only 6 alive (5 candidates: 11 empty slots, gid -1, mask 0,
    zero models); and at 5 DCs padded to 8, fewer DCs than pool slots."""
    from repro.core import cityscan

    base, cx, cy, alive, gid, cg = _screen_case(fleet, dead_every=dead_every,
                                                alive_max=alive_max)
    src, mask, pool = _screen(base, cx, cy, alive, gid, cg)
    n = min(cityscan.M_CAP, int(alive.sum()) - 1)
    filled = pool[:n]
    assert (mask[:n] == 1.0).all() and (mask[n:] == 0.0).all()
    assert (pool[n:] == -1).all() and not src[n:].any()
    assert len(set(filled.tolist())) == n
    assert cg not in filled
    assert alive[filled].all()                 # no dead DC, no padding
    assert (filled < fleet).all()


def test_city_run_matches_the_plain_reference():
    """``run_city`` at 256 DCs against ``bench/references/city.py`` in
    float32, what the program computes on a CPU. The draws, the centres
    and the pools are equal: only float32 rounding separates the two, and
    no pool here is decided by a near tie. The global models agree to
    1e-4 relative (sums in another order, through 20 SVM steps and
    GreedyTL's solves; 5e-7 is typical), and the F1 to 1e-3 (one of the
    3845 test points on a decision boundary moves it about 3e-4)."""
    from bench.comparisons.city_model import WINDOWS
    from bench.references import city as reference

    cfg = ScenarioConfig(windows=WINDOWS, eval_every=1, algo="star",
                         engine="scan", tech="wifi", fleet_size=256,
                         obs_per_dc=14, train_iters=20)
    got = run_scenario(cfg, DATA)
    want = reference.answer(dataclasses.asdict(cfg), DATA, "float32")
    assert got.sources["centre"].tolist() == want["centre"]
    assert got.sources["pool"].tolist() == want["pool"]
    for a, b in zip(got.models, np.asarray(want["models"])):
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b)
    np.testing.assert_allclose(got.f1_curve, want["f1_curve"], atol=1e-3)
    assert got.energy_collection == pytest.approx(want["collection_mj"],
                                                  rel=1e-12)
    assert got.energy_learning == pytest.approx(want["learning_mj"],
                                                rel=1e-12)


def _greedy_case(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(14, 54)).astype(np.float32).astype(np.float64)
    y = rng.integers(0, 7, 14)
    src = 0.3 * rng.normal(size=(16, 55, 7))
    src[:, :54] += 0.5 * rng.normal(size=(1, 54, 7))
    return x, y, src


@pytest.mark.parametrize("precision", ["tpu_default", "float32", "control"])
def test_reference_outcomes_start_with_plain_greedytl(precision):
    """The city reference opens GreedyTL's near ties; its first outcome
    takes every decision as ``plain.greedytl`` does and is that model to
    the bit, with the chosen sources it stopped at. With ties closed it is
    the only one."""
    from bench.references import city, plain

    num = plain.NUMS[precision]
    for seed in range(6):
        x, y, src = _greedy_case(seed)
        mask = np.ones(16)
        mask[seed % 16] = 0.0
        want = plain.greedytl(x, y, np.ones(14), src, mask, classes=7,
                              num=num)
        got = city.greedytl_outcomes(x, y, src, mask, num)
        margin, model, chosen = got[0]
        np.testing.assert_array_equal(model, want)
        assert margin == 0.0 and seed % 16 not in chosen
        margins = [m for m, _, _ in got[1:]]
        assert margins == sorted(margins)
        assert all(0.0 <= m <= city.TIE for m in margins)
        closed = city.greedytl_outcomes(x, y, src, mask, num, ties=False)
        assert len(closed) == 1
        np.testing.assert_array_equal(closed[0][1], want)


def test_reference_outcomes_open_an_exact_tie_both_ways():
    """Two sources with the same model tie exactly at every step: where
    the reference chooses one, an outcome that chose the other
    instead is there."""
    from bench.references import city, plain

    x, y, src = _greedy_case(0)
    got0 = city.greedytl_outcomes(x, y, src, np.ones(16), plain.TPU_DEFAULT,
                                  ties=False)
    first = got0[0][2][0]                     # a source it chooses
    twin = (first + 1) % 16
    src[twin] = src[first]
    got = city.greedytl_outcomes(x, y, src, np.ones(16), plain.TPU_DEFAULT)
    chosen = [set(c) for _, _, c in got]
    assert any(first in c and twin not in c for c in chosen)
    assert any(twin in c and first not in c for c in chosen)


def test_comparison_reads_the_reference_path_that_chose_as_the_program():
    """A reference answer with one opened tie: a program that chose as the
    tied path did is read against that path; one that chose as the
    reference did is read against the reference's own models, even where
    the tied path's lie closer; a choice no path made is read against the
    reference's own."""
    from bench.comparisons import city_model

    w0, w1 = np.ones((2, 55, 7)), np.full((2, 55, 7), 2.0)
    ref = {"models": w0.tolist(), "pool": [[1, 2]] * 2,
           "selected": [[1], [1]], "centre": [0, 0], "f1_curve": [0.5, 0.5],
           "collection_mj": 1.0, "learning_mj": 1.0,
           "tied": [{"models": w1.tolist(), "pool": [[1, 3]] * 2,
                     "selected": [[1], [3]], "f1_curve": [0.4, 0.4]}]}

    def program(models, selected, pool):
        return dict(ref, models=models.tolist(), selected=selected,
                    pool=[pool] * 2, f1_curve=[0.4, 0.4])

    g = city_model.gaps(program(w1, [[1], [3]], [1, 3]), ref)
    assert g["model"] == [0.0, 0.0] and g["pool"] == [0.0, 0.0]
    assert g["f1"] == [0.0, 0.0]
    g = city_model.gaps(program(w1, [[1], [1]], [1, 3]), ref)
    assert g["model"] == [1.0, 1.0] and g["pool"] == [0.5, 0.5]
    g = city_model.gaps(program(w1, [[2], [2]], [1, 2]), ref)
    assert g["model"] == [1.0, 1.0] and g["pool"] == [0.0, 0.0]


def test_city_record_round_trips_models_and_sources():
    """The sweep layer's records and their JSON carry the global models
    and sources exactly; other engines' records gain no key."""
    from repro.core.experiment import SweepResult, get_preset

    res = get_preset("city", fleet_size=40, windows=2).run(DATA)
    back = SweepResult.from_json(res.to_json())
    assert back == res
    r = back.records[0].to_scenario_result()
    assert r.models.dtype == np.float32 and r.models.shape == (2, 55, 7)
    assert set(r.sources) == {"centre", "pool", "selected"}
    assert r.sources["pool"].shape == (2, 16)
    scan = get_preset("smoke", windows=2, n_seeds=1, engine="scan").run(DATA)
    assert "models" not in scan.to_json()
    assert scan.records[0].to_scenario_result().models is None


# ---------------------------------------------------------------------------
# EvalCache keying: (dataset, kind) entries must isolate — the scan
# engine's extra derivatives may never evict or shadow the fleet engine's
# test matrix, and re-running any engine must hit, not thrash
# ---------------------------------------------------------------------------

def test_evalcache_kind_keying_isolates_entries():
    import jax.numpy as jnp
    cache = EvalCache(maxsize=8)
    d1 = make_covtype_like(seed=11)
    d2 = make_covtype_like(seed=12)
    built = {}
    for i, data in enumerate((d1, d2)):
        for j, kind in enumerate(("test", "test_onehot", "train_x",
                                  "train_y")):
            built[(i, kind)] = cache.array(
                data, kind, lambda d, v=(i * 10 + j): jnp.full((3,), v))
    assert cache.misses == 8 and cache.hits == 0
    # second pass: every (dataset, kind) hits and returns the same buffer
    for i, data in enumerate((d1, d2)):
        for kind in ("test", "test_onehot", "train_x", "train_y"):
            again = cache.array(data, kind,
                                lambda d: pytest.fail("rebuilt on hit"))
            assert again is built[(i, kind)]
    assert cache.misses == 8 and cache.hits == 8


def test_evalcache_lru_bound_still_applies():
    import jax.numpy as jnp
    cache = EvalCache(maxsize=2)
    d = make_covtype_like(seed=13)
    for kind in ("a", "b", "c"):
        cache.array(d, kind, lambda _: jnp.zeros(1))
    assert len(cache) == 2                 # oldest kind evicted
    cache.array(d, "a", lambda _: jnp.zeros(1))
    assert cache.misses == 4               # 'a' was the evicted one


def test_scan_engine_reuses_fleet_test_matrix():
    """Cross-engine no-thrash regression: after a fleet run uploaded the
    test matrix, a scan run on the same dataset must only miss on its NEW
    kinds (the one-hot labels), hitting the shared 'test' entry."""
    data = make_covtype_like(seed=14)
    cfg = ScenarioConfig(windows=2, eval_every=1, algo="star", tech="wifi")
    run_scenario(cfg, data)                              # uploads 'test'
    h0, m0 = _eval_cache.hits, _eval_cache.misses
    run_scenario(dataclasses.replace(cfg, engine="scan"), data)
    assert _eval_cache.misses - m0 == 1                  # 'test_onehot' only
    assert _eval_cache.hits - h0 >= 1                    # 'test' reused


# ---------------------------------------------------------------------------
# streamed-eval metric forms: confusion-count forms are bitwise equal to
# the paper's label-array forms (integer/integer f64 divisions are exact)
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(pairs=st.lists(st.tuples(st.integers(min_value=0, max_value=6),
                                st.integers(min_value=0, max_value=6)),
                      min_size=1, max_size=200))
def test_confusion_forms_match_label_forms_bitwise(pairs):
    y_true = np.array([a for a, _ in pairs], np.int64)
    y_pred = np.array([b for _, b in pairs], np.int64)
    cm = confusion_counts(y_true, y_pred, 7)
    assert cm.sum() == len(pairs)
    assert precision_from_confusion(cm) == precision(y_true, y_pred)
    assert recall_from_confusion(cm) == recall(y_true, y_pred, 7)
    assert f_measure_from_confusion(cm) == f_measure(y_true, y_pred, 7)
