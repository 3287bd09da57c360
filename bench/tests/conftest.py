"""The harness's own tests, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive the harness at sizes a CPU holds; the chip's sizes are the
cells' own."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def root():
    return ROOT


def _shrink(cell):
    """The cell at a size the CPU holds: the configuration's ``cpu`` block
    (each of its groups laid over the group of that name), compared with
    the reference in float32, which is what the program computes on a CPU
    (its products are float32 there, not bfloat16 passes)."""
    for key, value in cell.config["cpu"].items():
        cell.config[key] = (dict(cell.config[key], **value)
                            if isinstance(value, dict) else value)
    cell.config["reference_precision"] = "float32"
    cell.params["compare"] = 2
    return cell


@pytest.fixture
def shrink():
    return _shrink
