"""The scan engine's compact upload (repro.core.cityscan._pack_plan): the
observations go up once as a row table with placement arrays, and the
scan program builds the zero-padded sample blocks on the device. Those
blocks are bitwise the dense ``pad_local`` blocks the engine packed on the
host before, the packed dict holds no dense sample block, and the
scenario results still equal the fleet engine's."""
import dataclasses
from functools import partial

import jax
import numpy as np
import pytest

from repro.core.cityscan import (_WindowPlan, _pack_plan, _plan_scenario,
                                 _row_bucket, _scan_inputs,
                                 run_scenario_scan)
from repro.core.experiment import SweepResult, get_preset, records_from
from repro.core.fleet import fleet_cap
from repro.core.htl import DC, M_CAP
from repro.core.scenario import ScenarioConfig, run_scenario
from repro.core.svm import pad_local, sample_cap
from repro.data.synthetic_covtype import make_covtype_like

DATA = make_covtype_like(seed=0)
W = 5

# every case of the layout: A2A and star, an aggregated row, DCs holding
# more than ``cap`` observations (cap 16 against ~100 a window), and a
# churned fleet whose windows go single-DC and empty
PLANNED = {
    "a2a": ScenarioConfig(windows=W, algo="a2a", tech="wifi", seed=1),
    "star": ScenarioConfig(windows=W, algo="star", tech="4g", seed=2),
    "star_agg": ScenarioConfig(windows=W, algo="star", tech="wifi", seed=3,
                               aggregate=True),
    "a2a_over_cap": ScenarioConfig(windows=W, algo="a2a", tech="4g", seed=4,
                                   cap=16, n_subsample=5),
    "star_churn": ScenarioConfig(windows=10, algo="star", tech="4g", seed=5,
                                 battery_mj=5.0),
}


def _dc(name, n, seed):
    rng = np.random.default_rng(seed)
    return DC(name, rng.standard_normal((n, 6)).astype(np.float32),
              rng.integers(0, 7, n).astype(np.int32))


def _hand_plans(algo):
    """A multi-DC window with one DC over ``cap`` (cap 160), a single-DC
    window, an empty window, and another multi-DC window."""
    a, b, c = _dc("a", 200, 0), _dc("b", 3, 1), _dc("c", 40, 2)
    refine = ([_dc("ra", 9, 3), _dc("rb", 2, 4), _dc("rc", 30, 5)]
              if algo == "a2a" else [_dc("rc", 30, 5)])
    return [_WindowPlan([a, b, c], refine, n_pool=3),
            _WindowPlan([b], [], single=True),
            _WindowPlan([], []),
            _WindowPlan([c, a], refine[:2] if algo == "a2a" else refine[:1],
                        n_pool=2, prev_slot=2)]


def _cases():
    out = [(name, cfg, _plan_scenario(cfg, DATA)[0])
           for name, cfg in PLANNED.items()]
    for algo in ("a2a", "star"):
        cfg = ScenarioConfig(windows=4, algo=algo)
        out.append((f"hand_{algo}", cfg, _hand_plans(algo)))
    return out


CASES = _cases()


def _dense(cfg, plans):
    """The dense host blocks, zero-padded DC by DC with ``pad_local``: the
    reference for what the scan body reads."""
    W_ = cfg.windows
    L = fleet_cap(max([len(p.live) for p in plans] + [1]))
    cap = max([sample_cap(d.n, cfg.cap) for p in plans for d in p.live]
              + [sample_cap(1, cfg.cap)])
    rcap = max([sample_cap(d.n, cfg.cap) for p in plans for d in p.refine]
               + [sample_cap(1, cfg.cap)])
    F = next((d.x.shape[1] for p in plans for d in p.live), 1)
    rshape = (W_, L) if cfg.algo == "a2a" else (W_,)
    xb = np.zeros((W_, L, cap, F), np.float32)
    yb = np.zeros((W_, L, cap), np.int32)
    mb = np.zeros((W_, L, cap), np.float32)
    xr = np.zeros(rshape + (rcap, F), np.float32)
    yr = np.zeros(rshape + (rcap,), np.int32)
    mr = np.zeros(rshape + (rcap,), np.float32)
    for t, p in enumerate(plans):
        for i, d in enumerate(p.live):
            xb[t, i], yb[t, i], mb[t, i] = pad_local(d.x, d.y, cap)
        if p.single or not p.live:
            continue
        if cfg.algo == "a2a":
            for i, d in enumerate(p.refine):
                xr[t, i], yr[t, i], mr[t, i] = pad_local(d.x, d.y, rcap)
        else:
            xr[t], yr[t], mr[t] = pad_local(p.refine[0].x, p.refine[0].y,
                                            rcap)
    return {"xb": xb, "yb": yb, "mb": mb, "xr": xr, "yr": yr, "mr": mr}


def test_cases_cover_every_layout():
    plans = {name: p for name, _, p in CASES}
    assert any(not p.live for p in plans["star_churn"])
    assert any(p.single for p in plans["star_churn"])
    assert any(len(p.live) > 1 for p in plans["star_churn"])
    assert any(d.n > 16 for p in plans["a2a_over_cap"] for d in p.live)
    assert any(p.single for p in plans["hand_star"])


@pytest.mark.parametrize("name,cfg,plans", CASES, ids=[c[0] for c in CASES])
def test_device_blocks_equal_the_dense_blocks_bitwise(name, cfg, plans):
    packed = _pack_plan(cfg, plans)
    ref = _dense(cfg, plans)
    rest = dict(packed)
    x_rows = rest.pop("x_rows")
    # window by window, as the scan program builds them
    got = jax.jit(lambda x, r: jax.lax.map(partial(_scan_inputs, x), r))(
        x_rows, rest)
    for key, want in ref.items():
        have = np.asarray(got[key])
        assert have.dtype == want.dtype and have.shape == want.shape, key
        assert np.array_equal(have.view(np.uint8), want.view(np.uint8)), key
    for key in ("yb", "mb", "yr", "mr"):      # uploaded as packed
        assert np.array_equal(packed[key], ref[key]), key


@pytest.mark.parametrize("name,cfg,plans", CASES, ids=[c[0] for c in CASES])
def test_packed_bytes_are_the_rows_slots_and_placements(name, cfg, plans):
    packed = _pack_plan(cfg, plans)
    W_, L, _ = packed["yb"].shape
    F = packed["x_rows"].shape[1]
    rows = int(packed["mb"].sum() + packed["mr"].sum())
    slots = packed["mb"].size + packed["mr"].size
    # no dense sample block: only the table is F wide, and it holds the
    # placed rows up to their bucket
    assert packed["x_rows"].shape == (_row_bucket(rows), F)
    assert _row_bucket(rows) <= max(1024, 2 * rows)
    assert all(a.size <= slots for k, a in packed.items() if k != "x_rows")
    # besides the table and the label and mask blocks, only placement and
    # per-window arrays
    labels_masks = sum(packed[k].nbytes for k in ("yb", "mb", "yr", "mr"))
    assert labels_masks == slots * 8
    rest = [a for k, a in packed.items()
            if k not in ("x_rows", "yb", "mb", "yr", "mr")]
    assert all(a.size <= W_ * max(L, M_CAP) for a in rest)
    assert sum(a.nbytes for a in packed.values()) == (
        _row_bucket(rows) * F * 4 + slots * 8 + sum(a.nbytes for a in rest))
    assert int(packed["xb_count"].sum() + packed["xr_count"].sum()) == rows


def test_row_bucket():
    assert [_row_bucket(n) for n in (0, 1, 1024, 1025, 20000)] == \
        [1024, 1024, 1024, 2048, 32768]


def _row_json(label, result):
    """A result's sweep-record JSON, engine field normalized."""
    res = dataclasses.replace(
        result, cfg=dataclasses.replace(result.cfg, engine="fleet"))
    return SweepResult("row", records_from([label], [res])).to_json()


PAPER_ROWS = ("table3_a2a_wifi", "table4_star_4g_agg",
              "table9_a2a_n2_uniform")
RESULT_ROWS = (
    [(lbl, cfg) for lbl, cfg in get_preset("smoke", windows=W,
                                           n_seeds=1).configs()]
    + [(lbl, cfg) for lbl, cfg in get_preset("paper_tables", windows=W,
                                             n_seeds=1).configs()
       if lbl in PAPER_ROWS])


@pytest.mark.parametrize("label,cfg", RESULT_ROWS,
                         ids=[r[0] for r in RESULT_ROWS])
def test_scan_result_equals_the_fleet_engine(label, cfg):
    ref = run_scenario(dataclasses.replace(cfg, engine="fleet"), DATA)
    got = run_scenario_scan(dataclasses.replace(cfg, engine="scan"), DATA)
    assert _row_json(label, got) == _row_json(label, ref)
