"""Pallas TPU kernels for the perf-critical compute hot spots.

Each kernel: ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM tiling),
``ops.py`` (jit'd wrappers, interpret=True off-TPU), ``ref.py`` (pure-jnp
oracles swept by tests/test_kernels.py). ``svm_lanes.py`` (the city
fleet's base SVMs) is called from ``repro.core.svm._train_svm_lanes`` on a
TPU; its oracle is ``bench/references/plain.svm_train`` at the TPU's
default precision (tests/test_cityscan.py).
"""
from repro.kernels import ops, ref  # noqa: F401
