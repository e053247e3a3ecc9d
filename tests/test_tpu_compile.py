"""Compiles for a described TPU v5e: the race kernel and the sweep program.

Nothing here runs on a chip.  ``get_topology_desc`` describes a v5e 2x2
slice and the TPU compiler compiles for one of its chips on the host,
so what the chip's compiler refuses — a primitive Mosaic cannot lower,
a block it cannot tile, a program that does not fit in HBM — fails here
instead of on the chip.  Interpret-mode tests (test_kernels.py) cannot
see any of that.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.vectorized as vz
from repro.core import Params
from repro.kernels import ops

#: TPU v5e HBM per chip
HBM_BYTES = 16 * 10 ** 9
#: replica rows of the on-chip smoke's Fig. 2a grid (16 points x 16384)
ROWS = 16 * 16384


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler otherwise writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache here could not be
    # read back without a chip
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_target(one_chip, monkeypatch):
    """The race dispatch reads the process backend (the CPU here); point
    it at the described chip for the compile."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    return one_chip


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if isinstance(x, jax.Array) else x, tree)


def _fits_and_has_kernel(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert "tpu_custom_call" in compiled.as_text()
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("k_exp,k_det", [
    (16, 3),        # single-job exponential race (vectorized.K_EXP + 3)
    (16 + 20, 4),   # + 20 fault-domain shock lanes and a campaign timer
    (16 * 3, 2 * 3),  # the multi-job race at J = 3
], ids=["exponential", "correlated", "multijob"])
def test_event_race_compiles(tpu_target, k_exp, k_det):
    def race(rates, resid, ut, up):
        return ops.event_race(rates, resid, ut, up, impl="pallas")

    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((ROWS, k_exp), f32, sharding=tpu_target),
            jax.ShapeDtypeStruct((ROWS, k_det), f32, sharding=tpu_target),
            jax.ShapeDtypeStruct((ROWS,), f32, sharding=tpu_target),
            jax.ShapeDtypeStruct((ROWS,), f32, sharding=tpu_target))
    _fits_and_has_kernel(jax.jit(race).lower(*args).compile())


def test_fig2a_sweep_program_compiles(tpu_target):
    """The whole chunked-scan program the on-chip smoke runs: the Fig. 2a
    grid (12 points, padded to 16) x 16384 replicas, Pallas race."""
    grid = [Params(job_length=32 * 1440.0, recovery_time=rt,
                   working_pool_size=pool)
            for rt in (10.0, 20.0, 30.0) for pool in (4112, 4128, 4160, 4192)]
    [(_, R_run, run, args, kw)] = vz.sweep_programs(grid, 16384, seed=0,
                                                    impl="pallas")
    assert args[2] * R_run == ROWS
    _fits_and_has_kernel(run.lower(*_shapes(args, tpu_target),
                                   **kw).compile())


def test_llama3_fault_domain_program_compiles(tpu_target):
    """The Llama 3 cluster's 1,544 fault domains race as 2 level lanes
    (K = 18), so the race kernel's block fits VMEM; one lane a domain
    (K = 1,560) needed 146.5M of the chip's 128M.  The cell's grid at
    1,024 replicas a point: 16,384 rows, the kernel's full block."""
    import json
    from pathlib import Path

    from bench import harness

    bench = Path(__file__).resolve().parents[1] / "bench"
    grid = [Params.from_dict(json.loads(json.dumps(p)))
            for p in harness.grid_points(
                harness.load_config("llama3_24k_fault_domains", bench),
                harness.load_traffic("llama3_pod_grid", bench))]
    [(_, R_run, run, args, kw)] = vz.sweep_programs(grid, 1024, seed=0,
                                                    impl="pallas")
    compiled = run.lower(*_shapes(args, tpu_target), **kw).compile()
    _fits_and_has_kernel(compiled)
    # the kernel's rates: K = 18 lanes over the 16,384 rows in 128 x 128
    assert "f32[18,128,128]" in compiled.as_text()
