"""Pallas TPU kernels.

Each kernel: ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM tiling),
``ref.py`` (pure-jnp oracles swept by tests/test_kernels.py). ``ops.py``
wraps the LM path's kernels (flash attention, SSD, RG-LRU) with
interpret=True off-TPU. ``svm_lanes.py`` (the city fleet's base SVMs) is
called from ``repro.core.svm._train_svm_lanes`` on a TPU; its oracle is
``bench/references/plain.svm_train`` at the TPU's default precision
(tests/test_cityscan.py). ``loo_trials.py`` has no program caller:
GreedyTL scores its trials with ``repro.core.greedytl.loo_trials``.
"""
from repro.kernels import ops, ref  # noqa: F401
