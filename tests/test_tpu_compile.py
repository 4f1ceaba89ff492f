"""The main device path compiles for a TPU v5e chip, at the widths users run.

Nothing here runs on a chip: each test lowers a jitted program with shapes
placed on a *described* v5e device and asks the TPU compiler for an
executable, which raises whatever the chip's compiler would refuse
(Mosaic layouts, VMEM limits, unsupported ops) at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and pytest-xdist imports every
test file in every worker.  All compile tests stay in this one file so that
a single worker loads it.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

N_POOLS, POOL, SLAB, STREAM_REPS = 2304, 6, 1024, 2  # the trace-scale stream cell
PLAN_WORKERS, PLAN_REPS = 16, 2048  # the dynamic planner cell


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(np.shape(s), s.dtype, sharding=sharding), tree
    )


@pytest.fixture
def x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


def test_masked_cover_kernel_compiles(one_chip):
    """The Pallas cover kernel lowers through Mosaic (not interpret mode)."""
    from repro.kernels.cover import masked_cover_times

    draws = jax.ShapeDtypeStruct((16384, 16, 16), jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = masked_cover_times.lower(draws, scalar, scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stream_slab_compiles(one_chip, dtype, balanced):
    from repro.cluster.vectorized import STREAM_HIST_EDGES, _stream_slab, stream_acc_init

    n_classes = 6  # the heavy family's source jobs
    b, r = POOL // 2, 2
    args = (
        jnp.zeros((STREAM_REPS, SLAB, b, r), dtype),  # draws
        jnp.zeros(SLAB, dtype),  # scales
        jnp.zeros(SLAB, dtype),  # gaps
        jnp.zeros(SLAB, bool),  # mask
        jnp.zeros(SLAB, jnp.int32),  # classes
        jnp.zeros((STREAM_REPS, N_POOLS), dtype),  # rel_free
        jnp.zeros((STREAM_REPS, N_POOLS), dtype),  # load
        jax.eval_shape(lambda: stream_acc_init(STREAM_REPS, dtype, n_classes)),
        jnp.asarray(STREAM_HIST_EDGES, dtype),
    )
    _stream_slab.lower(
        *_on(args, one_chip),
        b=b,
        r=r,
        n_gangs=N_POOLS,
        cancel_redundant=True,
        balanced=balanced,
        collect=False,
        n_classes=n_classes,
    ).compile()


@pytest.mark.parametrize("balanced", [False, True], ids=["packed", "balanced"])
def test_stream_slab_compiles_f32(one_chip, balanced):
    _stream_slab_compiles(one_chip, jnp.float32, balanced)


def test_stream_slab_compiles_f64(one_chip, x64):
    _stream_slab_compiles(one_chip, jnp.float64, balanced=False)


def test_churned_epoch_scan_runner_compiles(one_chip, monkeypatch):
    """The legacy churned lane of the dynamic planner, at the bench's width.

    The host side prepares the real lane inputs; the compiled runner is
    replaced by a shape-only stand-in so nothing runs here, and the recorded
    signature is then compiled for the chip, where it must not warn either
    (a donated buffer no output can alias warns as it is lowered).
    """
    from repro.cluster import ChurnProcess, Scenario, epoch_scan
    from repro.core.service_time import Pareto

    seen = []
    build = epoch_scan._get_runner

    def shapes_only(cfg):
        runner = build(cfg)

        def call(*args):
            seen.append((runner, args))
            out = jax.eval_shape(runner, *args)
            return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), out)

        return call

    monkeypatch.setattr(epoch_scan, "_get_runner", shapes_only)
    sc = Scenario(
        churn=ChurnProcess(fail_rate=0.02, mean_downtime=2.0),
        speeds=tuple(np.random.default_rng(0).uniform(0.5, 2.0, PLAN_WORKERS)),
        jobs_per_stream=96,
    )
    epoch_scan.frontier_job_times_dynamic(
        Pareto(1.0, 1.8), PLAN_WORKERS, [1, 2, 4, 8, 16], PLAN_REPS, scenario=sc
    )
    ((runner, args),) = seen
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        runner.lower(*_on(args, one_chip)).compile()
