#!/usr/bin/env python
"""The city trainer on a TPU, alone: its precision and its time a step.

    PYTHONPATH=src python scripts/city_svm_probe.py [--out FILE]

Precision: at 200 and 256 DCs (14 observations, 54 features, 7 classes,
200 steps, every 7th DC masked and every 7th from the 4th half masked),
the lane-major trainer (``_train_svm_lanes``, the Pallas kernel on a TPU)
and the vmapped ``_train_svm`` (the scan engine's trainer, at the default
precision of a float32 dot) against ``bench/references/plain.svm_train``
at ``tpu_default`` (bfloat16 product operands, float32 products and sums)
and at float32, as the relative Frobenius gap over the fleet.

Time: both trainers at the ``city-250k`` size (250016 DCs, 14
observations each), on random data made on the device, as milliseconds a
step (the median of three timed calls after a warm-up call, over the
steps of one call). Prints one JSON line, also written to ``--out``.
Needs a TPU; exits non-zero without one.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.references import plain  # noqa: E402
from repro.core import svm  # noqa: E402

C, F = 7, 54


def lanes(x, y, m, iters):
    return jax.jit(lambda a, b, c: svm._train_svm_lanes(
        a, b, c, num_classes=C, iters=iters))(x, y, m)


def vmapped(x, y, m, iters):
    """(K, F, L) in, (C, F+1, L) out, as the scan engine's trainer."""
    w = jax.jit(jax.vmap(lambda a, b, c: svm._train_svm(
        a, b, c, num_classes=C, iters=iters), in_axes=(2, 1, 1)))(x, y, m)
    return jnp.transpose(w, (2, 1, 0))


def case(fleet, K=14, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(fleet, K, F)).astype(np.float32)
    y = rng.integers(0, C, size=(fleet, K)).astype(np.int32)
    m = np.ones((fleet, K), np.float32)
    m[::7] = 0.0
    m[3::7, 1::2] = 0.0
    return x, y, m


def precision(iters=200) -> dict:
    out = {}
    for fleet in (200, 256):
        x, y, m = case(fleet)
        ref = {name: plain.svm_train(x, y, m, classes=C, iters=iters,
                                     num=plain.NUMS[name])
               for name in ("tpu_default", "float32")}
        lane_args = (x.transpose(1, 2, 0), y.T, m.T)
        for name, fn in (("lanes", lanes), ("vmapped", vmapped)):
            got = np.asarray(fn(*lane_args, iters), np.float64)
            got = got.transpose(2, 1, 0)
            for rname, r in ref.items():
                out[f"{name}_{fleet}_vs_{rname}"] = float(
                    np.linalg.norm(got - r) / np.linalg.norm(r))
            out[f"{name}_{fleet}_masked_zero"] = bool(not got[::7].any())
    return out


def step_ms(fn, L: int, K: int, iters: int) -> float:
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (K, F, L), jnp.float32)
    y = jax.random.randint(ky, (K, L), 0, C)
    m = jnp.ones((K, L), jnp.float32)
    call = jax.jit(lambda a, b, c: fn(a, b, c, iters))
    call(x, y, m).block_until_ready()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        call(x, y, m).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3 / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    res = {"device": dev.device_kind, **precision()}
    res["lanes_ms_per_step"] = step_ms(lanes, 250016, 14, 200)
    res["vmapped_ms_per_step"] = step_ms(vmapped, 250016, 14, 20)
    line = json.dumps(res)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
