"""The fused detection kernels compile for a TPU v5e chip.

Interpret mode runs the kernel bodies as plain jax, so it cannot see
what the TPU compiler refuses: dynamic slices, scalar stores to VMEM,
reductions over unsigned integers, more VMEM than a core has.  These
tests compile both kernels, and the live-scale variant the steady-state
detect launches, for a described (not attached) ``v5e:2x2`` topology at
real fleet sizes.  Nothing runs, so no result or time is checked here.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.detect_fused.kernel import ab_fused_kernel, ns_fused_kernel

FLEETS = (512, 2048, 8192)
# 256 vertices, and mamba2-130m's contracted train-step PSG (90 vertices)
# plus the gradient all-reduce chip_smoke.py adds to it
WIDTHS = (256, 91)
LANES = 128                     # the abnormal kernel's column tile


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        cache = jax.config.jax_enable_compilation_cache
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            from jax.experimental import topologies
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("V", WIDTHS)
@pytest.mark.parametrize("P", FLEETS)
@pytest.mark.parametrize("n_hist", [0, 2], ids=["stacked", "live"])
def test_ns_kernel_compiles_for_v5e(one_chip, P, V, n_hist):
    """Three scales: all stacked (n_hist=0), or two device-cached
    historical columns plus the live scale's rows (n_hist=2)."""
    S_d = 3 - n_hist
    text = _compile(
        lambda *a: ns_fused_kernel(*a, n_hist=n_hist),
        [(S_d, P, V), (S_d, P, V), (4, max(n_hist, 1), V), (3, 1), (3, V),
         (1, V), (1, 8)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("V", WIDTHS)
@pytest.mark.parametrize("P", FLEETS)
def test_ab_kernel_compiles_for_v5e(one_chip, P, V):
    """One shape serves the full and the degraded fleet: ops gathers the
    live rows before the launch and passes their mask in ``valid``."""
    V = -(-V // LANES) * LANES                 # ops pads to whole lanes
    text = _compile(lambda *a: ab_fused_kernel(*a, k=20),
                    [(P, V), (P, 1), (1, V), (1, 8)], one_chip)
    assert "tpu_custom_call" in text


def _lowered(fn, shapes, sharding, dtypes=None, **static):
    dtypes = dtypes or [jnp.float32] * len(shapes)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in zip(shapes, dtypes)]
    return fn.lower(*args, **static).as_text()


@pytest.mark.parametrize("program", ["scatter", "non_scalable", "abnormal"])
def test_detection_programs_have_stable_names(one_chip, program):
    """A device trace names an operation by its program's module, and a
    Pallas kernel's operation by the kernel's name: the device feed's
    row scatter and both fused kernels carry names of their own."""
    from repro.core.shard import _row_scatter
    P, V = 512, 128
    if program == "scatter":
        text = _lowered(_row_scatter(), [(P, V), (8,), (8, V)], one_chip,
                        [jnp.float32, jnp.int32, jnp.float32])
        module, kernel = "jit_scatter_rows", None
    elif program == "non_scalable":
        text = _lowered(ns_fused_kernel,
                        [(3, P, V), (3, P, V), (4, 1, V), (3, 1), (3, V),
                         (1, V), (1, 8)], one_chip, n_hist=0)
        module, kernel = "jit_ns_fused_kernel", "detect_non_scalable"
    else:
        text = _lowered(ab_fused_kernel, [(P, V), (P, 1), (1, V), (1, 8)],
                        one_chip, k=20)
        module, kernel = "jit_ab_fused_kernel", "detect_abnormal"
    assert text.splitlines()[0].startswith(f"module @{module} ")
    if kernel is not None:
        assert f'kernel_name = "{kernel}"' in text


def test_row_scatter_compiles_at_the_resident_shape(one_chip):
    """The device feed's one row scatter per matrix a refresh, at the
    8,192-process fleet's resident (P, V) buffer with 32 dirty rows (8
    hosts of 4), compiles and keeps the module name a trace reads."""
    from repro.core.shard import _row_scatter
    P, V, rows = 8192, 128, 32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in [((P, V), jnp.float32), ((rows,), jnp.int32),
                         ((rows, V), jnp.float32)]]
    lowered = _row_scatter().lower(*args)
    assert lowered.as_text().splitlines()[0].startswith(
        "module @jit_scatter_rows ")
    assert "scatter" in lowered.compile().as_text()
