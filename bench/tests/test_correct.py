"""``correct``: a sound run passes the cell's limits; the control (the
reference one precision below the configuration's, in the program's
place) and the timed path broken underneath both fail them.

The runs skip the harness's look for a chip and drive the rest on the
CPU at a small size (``shrink``)."""
import numpy as np
import pytest

from bench import check, faults
from bench.run import run_cell
from bench.spec import load_cell

CELL = "paper-htl.closed"


def _run(cell, seed=2**31 + 99, seconds=1.0):
    return run_cell(cell, seed, seconds, False, chip=False)


def test_sound_run_is_correct(shrink):
    out = _run(shrink(load_cell(CELL)))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(check.COMPARED)


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_control_fails_the_limits(seed, shrink):
    """The reference in bfloat16, with float8 products, put where the
    program's answers go."""
    from bench.data import dataset
    from bench.scenarios import items

    cell = shrink(load_cell(CELL))
    ref = cell.reference()
    data = dataset(cell.config, seed)
    seq = items(cell.config)
    pairs = []
    for _ in range(3):
        s = seq.next()
        pairs.append((s, ref.answer(s.plain(), data, "control")))
    checks = check.judge(check.compare(pairs, ref, data,
                                       cell.config["reference_precision"]),
                         cell.limits)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_path_is_not_correct(fault, shrink):
    with faults.plant(fault):
        out = _run(shrink(load_cell(CELL)))
    assert not out["correct"], out["checks"]


def test_faults_restore_the_program():
    from repro.core import cityscan

    before = (cityscan._pack_plan, cityscan._dispatch_scan)
    for name in faults.FAULTS:
        with faults.plant(name):
            assert (cityscan._pack_plan,
                    cityscan._dispatch_scan) != before
        assert (cityscan._pack_plan, cityscan._dispatch_scan) == before


def test_missing_answers_are_not_correct():
    assert all(v == check.FAR for v in check.numbers([]).values())
    nan = {"f1_curve": [float("nan")], "collection_mj": 1.0,
           "learning_mj": 1.0}
    ok = dict(nan, f1_curve=[0.5])
    assert check.gaps(nan, ok)["f1"] == [check.FAR]
    assert check.gaps(dict(ok, f1_curve=[0.5, 0.5]), ok)["f1"] == \
        [check.FAR]
    assert check.gaps(dict(ok, f1_curve=[]), dict(ok, f1_curve=[]))["f1"] \
        == [check.FAR]
    values = check.numbers([check.gaps(nan, ok)])
    assert values["f1_gap"] == values["f1_mean_gap"] == check.FAR


def test_numbers_by_hand():
    a = {"f1_curve": [0.5, 0.6], "collection_mj": 2.0, "learning_mj": 4.0}
    b = {"f1_curve": [0.5, 0.7], "collection_mj": 2.0, "learning_mj": 5.0}
    c = {"f1_curve": [0.1, 0.6], "collection_mj": 1.0, "learning_mj": 4.0}
    values = check.numbers([check.gaps(a, b), check.gaps(a, c)])
    assert values["f1_gap"] == pytest.approx(0.4)
    assert values["f1_mean_gap"] == pytest.approx((0.0 + 0.1 + 0.4 + 0.0)
                                                  / 4)
    assert values["energy_gap"] == pytest.approx(1.0)


def test_a_number_without_a_limit_is_an_error():
    with pytest.raises(KeyError):
        check.judge({k: 0.0 for k in check.COMPARED}, {"f1_gap": 0.1})


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

    config = load_cell(CELL).config
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
