"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described rather than attached. These tests compile the main path's
kernels and the city round at real sizes: what Mosaic or XLA would refuse
on the chip (unaligned blocks, unsupported ops, a program that does not
fit the device's memory) fails here at no chip time. Nothing runs, so
they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
AUTOTUNE = os.path.join(REPO, "results", "benchmarks", "kernel_autotune.json")
V5E_USABLE_HBM = 15.75e9          # of 16 GB, as XLA reports for one v5e
# the city-250k round with the DCs on the lane axis plans 1.62e9 bytes
# (1615564288); with the per-DC (55, 7) tiles of a vmapped trainer it
# planned 9.53e9. The bound leaves that plan a quarter of room.
CITY_CELL_ROUND_PLAN = 2.0e9
BLOCK_RS = (64, 128, 256, 512)


def _autotune_keys():
    with open(AUTOTUNE) as f:
        backends = json.load(f)["backends"]
    return sorted({key for entries in backends.values() for key in entries})


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on device 0 of a described v5e:2x2, with the
    persistent compile cache off (its entries for a described chip cannot
    be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                         # noqa: BLE001
            jax.config.update("jax_enable_compilation_cache", was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("block_r", BLOCK_RS)
@pytest.mark.parametrize("key", _autotune_keys())
def test_loo_trials_compiles_for_v5e(one_chip, key, block_r):
    """The GreedyTL trial scorer compiles through Mosaic at every bucketed
    shape the autotuner has recorded, at every tile it may choose."""
    from repro.kernels import loo_trials as kernel

    R, D, M = (int(part[1:]) for part in key.split("_"))
    s = functools.partial(_shape, one_chip)
    compiled = kernel.loo_trials.lower(
        s((R, D)), s((D, M)), s((R, M)), s((R,)), s((R,)), s((R,)),
        s((R,)), s((M,)), s((M,)), block_r=block_r,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_city_round_fits_one_v5e(one_chip):
    """One city round at 250,000 DCs (K=4, train_iters=6, the city preset's
    widths) fits one chip's memory."""
    _city_round_fits(one_chip, K=4, iters=6)


def test_city_cell_round_fits_one_v5e(one_chip):
    """The ``city-250k`` configuration's round (250,000 DCs, 14
    observations each, 200 SVM iterations) fits one chip's memory, and
    plans no more than the lane-major layout needs: the per-DC SVMs are
    one kernel, not a vmapped batch of padded (55, 7) tiles."""
    compiled, total = _city_round_fits(one_chip, K=14, iters=200)
    assert total <= CITY_CELL_ROUND_PLAN, f"{total / 1e9:.2f} GB planned"
    assert "svm_lanes" in compiled.as_text()


def _city_round_fits(one_chip, K: int, iters: int):
    """Compile the round as a TPU lowers it: the program asks
    ``jax.default_backend()`` for its TPU path, which a described chip
    does not answer, so the test answers it; GreedyTL's trial scorer is
    held to its jnp form, so that no autotuner runs here."""
    from repro.core.cityscan import _city_round_program, city_fleet_pad
    from repro.data.synthetic_covtype import NUM_CLASSES, make_covtype_like

    data = make_covtype_like(seed=0)
    L, F = city_fleet_pad(250_000), data.x_train.shape[1]
    n_test = data.x_test.shape[0]
    s = functools.partial(_shape, one_chip)
    args = (s((F + 1, NUM_CLASSES)), s((), jnp.bool_), s((L, K, F)),
            s((L, K), jnp.int32), s((L, K)), s((L,), jnp.bool_),
            s((L,), jnp.int32), s(()), s((n_test, F)),
            s((n_test, NUM_CLASSES)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setenv("REPRO_KERNEL_FORCE", "jnp")
        compiled = _city_round_program.__wrapped__(NUM_CLASSES, iters).lower(
            *args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes
             + mem.generated_code_size_in_bytes)
    assert total <= V5E_USABLE_HBM, f"{total / 1e9:.2f} GB per chip"
    return compiled, total


def _lm_kernel_case(name, sharding):
    """(jitted kernel, argument shapes) at one published width each."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rglru_scan import rglru_scan
    from repro.kernels.ssd_scan import ssd_scan

    s = functools.partial(_shape, sharding)
    bf, S = jnp.bfloat16, 2048
    if name == "flash_attention":      # llama3.2-3b: 24 heads, 8 kv, d=128
        return (jax.jit(functools.partial(flash_attention, causal=True)),
                (s((1, 24, S, 128), bf), s((1, 8, S, 128), bf),
                 s((1, 8, S, 128), bf)))
    if name == "ssd_scan":             # mamba2-1.3b: 64 heads, P=64, N=128
        return (jax.jit(functools.partial(ssd_scan, chunk=256)),
                (s((1, S, 64, 64), bf), s((1, S, 64), bf), s((64,)),
                 s((1, S, 128), bf), s((1, S, 128), bf)))
    if name == "rglru_scan":           # recurrentgemma-9b: lru_width 4096
        return (jax.jit(functools.partial(rglru_scan, chunk=128,
                                          block_w=128)),
                (s((1, S, 4096), bf), s((1, S, 4096), bf)))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["flash_attention", "ssd_scan",
                                  "rglru_scan"])
def test_lm_kernels_compile_for_v5e(one_chip, name):
    fn, args = _lm_kernel_case(name, one_chip)
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
