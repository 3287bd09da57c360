"""Faults planted in the city engine's timed path, for the harness's tests
and for the fault readings of ``bench/readings.py``. Each is a context
manager that patches ``repro.core.cityscan`` and restores it on exit; a
fault inside the cached city program clears that cache on entry and on
exit, so the program is traced again with the fault and again without.

* ``half_fleet``: every odd DC id dead from the first window, on the
  device only (the death schedule the program is launched with); the
  host's ledger is still charged for the whole fleet, so only the model
  and the pool can see it;
* ``first_ids``: the source pool filled by the old rule, the base models
  of the first ``M_CAP`` alive DC ids, instead of the fleet-wide screen;
* ``half_obs``: every other observation of each DC masked out, for base
  training and the election;
* ``altered``: an answer altered where it is produced: in what the
  program returns, the global model of the last compared window has its
  class columns rolled by one, and that window's confusion counts are
  rolled to match.
"""
from __future__ import annotations

import contextlib

import numpy as np

from bench.comparisons.city_model import WINDOWS


@contextlib.contextmanager
def _patched(obj, name, make):
    real = getattr(obj, name)
    setattr(obj, name, make(real))
    try:
        yield
    finally:
        setattr(obj, name, real)


@contextlib.contextmanager
def _in_program(name, make):
    from repro.core import cityscan

    cityscan._city_program.cache_clear()
    try:
        with _patched(cityscan, name, make):
            yield
    finally:
        cityscan._city_program.cache_clear()


def _half_fleet(real):
    def dispatch(*args):
        t_die = np.array(args[-1])
        t_die[1::2] = 0
        return real(*args[:-1], t_die)
    return dispatch


def _first_ids(real):
    def screen(base, cx, cy, alive, gid, cg, *, num_classes, shards):
        import jax
        import jax.numpy as jnp
        from repro.core.cityscan import FLEET_AXIS, M_CAP

        slot = jnp.arange(M_CAP, dtype=gid.dtype)
        oh = ((gid[:, None] == slot[None, :])
              & alive[:, None]).astype(jnp.float32)
        src = jnp.einsum("lm,lfc->mfc", oh, base)
        mask = jnp.sum(oh, axis=0)
        if shards > 1:
            src = jax.lax.psum(src, FLEET_AXIS)
            mask = jax.lax.psum(mask, FLEET_AXIS)
        return src, mask, jnp.where(mask > 0, slot, -1)
    return screen


def _half_obs(real):
    def draw(*args):
        x, y, m = real(*args)
        return x, y, m.at[:, 1::2].set(0.0)
    return draw


def _altered(real):
    def dispatch(*args):
        cms, centres, models, pools, sel = (np.array(a) for a in real(*args))
        t = WINDOWS - 1
        models[t] = np.roll(models[t], 1, axis=-1)
        cms[t] = np.roll(cms[t], 1, axis=-1)
        return cms, centres, models, pools, sel
    return dispatch


def plant(name: str):
    from repro.core import cityscan

    if name == "half_fleet":
        return _patched(cityscan, "_dispatch_city", _half_fleet)
    if name == "first_ids":
        return _in_program("_screen", _first_ids)
    if name == "half_obs":
        return _in_program("_draw_window", _half_obs)
    if name == "altered":
        return _patched(cityscan, "_dispatch_city", _altered)
    raise KeyError(name)


FAULTS = ("half_fleet", "first_ids", "half_obs", "altered")
