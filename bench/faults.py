"""Faults planted in the program's timed path, for the harness's tests and
for the fault readings of ``bench/readings.py``. Each is a context manager
that patches the scan engine and restores it on exit; none changes a
compiled program's shapes.

* ``unchanged``: the learning step returns its state unchanged, so the
  global model stays the zero model it starts from;
* ``half``: half of every window's batch left out (every other sample
  slot masked, for base training and for GreedyTL), the mean taken over
  the rest;
* ``altered``: an answer altered where it is produced: the last window's
  predicted classes shifted by one in the confusion counts the device
  returns.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, make):
    real = getattr(obj, name)
    setattr(obj, name, make(real))
    try:
        yield
    finally:
        setattr(obj, name, real)


def _pack(change):
    def make(real):
        def pack(cfg, plans):
            out = real(cfg, plans)
            change(out)
            return out
        return pack
    return make


def _unchanged(out):
    out["learn"][:] = False


def _half(out):
    out["mb"][..., 1::2] = 0.0
    out["mr"][..., 1::2] = 0.0


def _altered(real):
    def dispatch(*args):
        cms = np.array(real(*args))
        cms[-1] = np.roll(cms[-1], 1, axis=-1)
        return cms
    return dispatch


def plant(name: str):
    from repro.core import cityscan

    if name == "unchanged":
        return _patched(cityscan, "_pack_plan", _pack(_unchanged))
    if name == "half":
        return _patched(cityscan, "_pack_plan", _pack(_half))
    if name == "altered":
        return _patched(cityscan, "_dispatch_scan", _altered)
    raise KeyError(name)


FAULTS = ("unchanged", "half", "altered")
