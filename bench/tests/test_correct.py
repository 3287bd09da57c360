"""``correct``, in every cell of ``BENCHMARK.json``: a sound run passes
the cell's limits; the control (the reference one precision below the
configuration's, in the program's place) and every fault of the cell's
configuration (``bench/faults/<name>.py``, the timed path broken
underneath) fail them; and the faults restore the program.

The runs skip the harness's look for a chip and drive the rest on the
CPU at the configuration's ``cpu`` size (``shrink``)."""
import json
import os
import sys

import numpy as np
import pytest

from bench import check
from bench.run import run_cell
from bench.spec import ROOT, load_cell, load_module

CELLS = [w["name"] for w in json.load(open(os.path.join(
    ROOT, "BENCHMARK.json")))["workloads"]]
FAULTS = [(name, fault) for name in CELLS
          for fault in load_cell(name).faults().FAULTS]


def _run(cell, seed=2**31 + 99, seconds=1.0):
    return run_cell(cell, seed, seconds, False, chip=False)


def _f1_energy():
    return load_module(os.path.join(ROOT, "bench", "comparisons",
                                    "f1_energy.py"), "f1_energy")


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, shrink):
    cell = shrink(load_cell(name))
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert list(out["checks"]) == list(cell.comparison().COMPARED)


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name, seed, shrink):
    """The reference one precision below the configuration's (for the
    paper's scenarios: bfloat16, with float8 products) put where the
    program's answers go."""
    from bench.data import dataset
    from bench.scenarios import items

    cell = shrink(load_cell(name))
    ref, comparison = cell.reference(), cell.comparison()
    data = dataset(cell.config, seed)
    seq = items(cell.config)
    pairs = []
    for _ in range(3):
        s = seq.next()
        pairs.append((s, ref.answer(s.plain(), data, "control")))
    checks = check.judge(
        check.compare(pairs, ref, data, cell.config["reference_precision"],
                      comparison),
        cell.limits, comparison.COMPARED)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("name,fault", FAULTS)
def test_broken_path_is_not_correct(name, fault, shrink):
    cell = shrink(load_cell(name))
    with cell.faults().plant(fault):
        out = _run(cell)
    assert not out["correct"], out["checks"]


def _bindings():
    """Every name bound in the program's modules, with what it is bound
    to."""
    return {(m, k): v for m, mod in list(sys.modules.items())
            if m.split(".")[0] == "repro" and mod is not None
            for k, v in vars(mod).items()}


def _same(a, b):
    return a.keys() == b.keys() and all(a[k] is b[k] for k in a)


@pytest.mark.parametrize("name", CELLS)
def test_faults_restore_the_program(name):
    faults = load_cell(name).faults()
    for fault in faults.FAULTS:      # a first plant may import what it patches
        with faults.plant(fault):
            pass
    before = _bindings()
    for fault in faults.FAULTS:
        with faults.plant(fault):
            assert not _same(_bindings(), before), fault
        assert _same(_bindings(), before), fault


@pytest.mark.parametrize("name", CELLS)
def test_readings_write_every_number_of_every_variant(name, shrink):
    from bench.readings import readings

    cell = shrink(load_cell(name))
    comparison, faults = cell.comparison(), cell.faults()
    seed = 2**31 + 7
    rows = readings(cell, [seed], {seed}, {seed}, 1.0, chip=False)
    assert len(rows) == 2 * (2 + len(faults.FAULTS))
    assert {r["variant"] for r in rows} == {"program", "control",
                                            *faults.FAULTS}
    for r in rows:
        assert set(comparison.COMPARED) | set(comparison.PRINTED) <= set(r)


def test_missing_answers_are_not_correct():
    f1_energy = _f1_energy()
    assert all(v == check.FAR for v in f1_energy.numbers([]).values())
    nan = {"f1_curve": [float("nan")], "collection_mj": 1.0,
           "learning_mj": 1.0}
    ok = dict(nan, f1_curve=[0.5])
    assert f1_energy.gaps(nan, ok)["f1"] == [check.FAR]
    assert f1_energy.gaps(dict(ok, f1_curve=[0.5, 0.5]), ok)["f1"] == \
        [check.FAR]
    assert f1_energy.gaps(dict(ok, f1_curve=[]),
                          dict(ok, f1_curve=[]))["f1"] == [check.FAR]
    values = f1_energy.numbers([f1_energy.gaps(nan, ok)])
    assert values["f1_gap"] == values["f1_mean_gap"] == check.FAR


def test_numbers_by_hand():
    f1_energy = _f1_energy()
    a = {"f1_curve": [0.5, 0.6], "collection_mj": 2.0, "learning_mj": 4.0}
    b = {"f1_curve": [0.5, 0.7], "collection_mj": 2.0, "learning_mj": 5.0}
    c = {"f1_curve": [0.1, 0.6], "collection_mj": 1.0, "learning_mj": 4.0}
    values = f1_energy.numbers([f1_energy.gaps(a, b), f1_energy.gaps(a, c)])
    assert values["f1_gap"] == pytest.approx(0.4)
    assert values["f1_mean_gap"] == pytest.approx((0.0 + 0.1 + 0.4 + 0.0)
                                                  / 4)
    assert values["energy_gap"] == pytest.approx(1.0)


def test_a_number_without_a_limit_is_an_error():
    with pytest.raises(KeyError):
        check.judge({"f1_mean_gap": 0.0, "energy_gap": 0.0},
                    {"f1_mean_gap": 0.1}, ("f1_mean_gap", "energy_gap"))


def test_a_limit_that_nothing_compares_is_an_error():
    with pytest.raises(KeyError):
        check.judge({"f1_mean_gap": 0.0, "f1_gap": 0.0},
                    {"f1_mean_gap": 0.1, "f1_gap": 0.1}, ("f1_mean_gap",))


def test_control_rounding_saturates():
    from bench.references import plain

    x = np.array([1e6, -1e6, 0.3, 1e-12])
    y = plain.fp8(x)
    assert np.isfinite(y).all()
    assert y[0] == plain.E4M3_MAX and y[1] == -plain.E4M3_MAX
    assert abs(y[2] - 0.3) <= 0.3 * 2 ** -4
    assert plain.bf16(np.float64(1 + 2 ** -9)) == 1.0


def test_seed_makes_the_data_and_keeps_the_classes():
    from bench.data import dataset

    config = load_cell("paper-htl.closed").config
    a, b = dataset(config, 2**31 + 5), dataset(config, 2**31 + 5)
    c = dataset(config, 12)
    for u, v in zip(a, b):
        assert np.array_equal(u, v)
    assert np.array_equal(a.y_train, c.y_train)
    assert np.array_equal(a.y_test, c.y_test)
    assert not np.array_equal(a.x_train, c.x_train)
    # every row is one of the seed's own rows, with its own class
    from repro.data.synthetic_covtype import make_covtype_like
    raw = make_covtype_like(seed=12)
    for cls in range(7):
        got = np.sort(c.x_train[c.y_train == cls][:, 0])
        pool = np.concatenate([raw.x_train[raw.y_train == cls][:, 0],
                               raw.x_test[raw.y_test == cls][:, 0]])
        assert np.isin(got, pool).all()
