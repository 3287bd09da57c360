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

import jax
import jax.numpy as jnp
import pytest

V5E_USABLE_HBM = 15.75e9          # of 16 GB, as XLA reports for one v5e
# the city-250k round with the DCs on the lane axis plans 1.62e9 bytes
# (1615564288); with the per-DC (55, 7) tiles of a vmapped trainer it
# planned 9.53e9. The bound leaves that plan a quarter of room.
CITY_CELL_ROUND_PLAN = 2.0e9
BLOCK_RS = (64, 128, 256, 512)
# GreedyTL's trial sweep as (R, D, M): R stacked rows n*C rounded up to a
# power of two, D = C + min(k_max, M) carried columns, M candidate sources:
# the paper's fleets (R512, R2048), the city's 14-observation DCs (R128),
# and shallower (k_max 4, 8) or wider (M 32) source pools.
LOO_TRIAL_SHAPES = ("R128_D23_M16", "R2048_D11_M16", "R2048_D15_M16",
                    "R2048_D15_M8", "R2048_D23_M16", "R2048_D23_M32",
                    "R512_D23_M16")


def _trial_shape(key):
    return tuple(int(part[1:]) for part in key.split("_"))


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
@pytest.mark.parametrize("key", LOO_TRIAL_SHAPES)
def test_loo_trials_compiles_for_v5e(one_chip, key, block_r):
    """The Pallas trial scorer compiles through Mosaic at every recorded
    trial shape, at every row tile."""
    from repro.kernels import loo_trials as kernel

    R, D, M = _trial_shape(key)
    s = functools.partial(_shape, one_chip)
    compiled = kernel.loo_trials.lower(
        s((R, D)), s((D, M)), s((R, M)), s((R,)), s((R,)), s((R,)),
        s((R,)), s((M,)), s((M,)), block_r=block_r,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("key", LOO_TRIAL_SHAPES)
def test_greedytl_compiles_for_v5e(one_chip, key):
    """GreedyTL compiles for the chip at every recorded trial shape, with
    its trial scorer in plain XLA: the program holds no Mosaic kernel."""
    from repro.core.greedytl import greedytl
    from repro.data.synthetic_covtype import NUM_CLASSES as C
    from repro.data.synthetic_covtype import NUM_FEATURES as F

    R, D, M = _trial_shape(key)
    n, k_max = R // C, D - C
    assert 1 << (n * C - 1).bit_length() == R and C + min(k_max, M) == D
    s = functools.partial(_shape, one_chip)
    lowered = greedytl.__wrapped__.lower(
        s((n, F)), s((n,), jnp.int32), s((n,)), s((M, F + 1, C)), s((M,)),
        num_classes=C, k_max=k_max)
    assert "tpu_custom_call" not in lowered.as_text()
    lowered.compile()


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
    does not answer, so the test answers it."""
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
