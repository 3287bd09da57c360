"""The run's data, made from its ``--seed``.

``make_covtype_like(seed)`` gives every seed its own class structure and
rows. Where the configuration names a ``label_order_seed``, the rows are
then placed so that the class of every position follows one fixed order
drawn from that seed (every seed has the same number of rows of each
class, so each class's rows fill its positions in the seed's own order).
The fleets a scenario draws from its own seed then see the same classes
in every run: the same padded shapes and the same amount of work, on
other rows.
"""
from __future__ import annotations

import numpy as np


def dataset(config: dict, seed: int):
    from repro.data.synthetic_covtype import Dataset, make_covtype_like

    data = make_covtype_like(seed=int(seed))
    order = config.get("label_order_seed")
    if order is None:
        return data
    x = np.concatenate([data.x_test, data.x_train])
    y = np.concatenate([data.y_test, data.y_train])
    fixed = np.random.default_rng(int(order)).permutation(np.sort(y))
    placed = np.empty_like(x)
    for c in np.unique(y):
        placed[fixed == c] = x[y == c]
    n_test = len(data.y_test)
    return Dataset(placed[n_test:], fixed[n_test:], placed[:n_test],
                   fixed[:n_test])
