"""Compile the main paths for a described TPU v5e chip, no chip attached.

Interpret-mode tests cannot show what the chip's compiler refuses (the
closed-loop kernel once aborted the whole process in Mosaic's layout
inference). These tests lower and compile, at real sizes, for one chip
of a described ``v5e:2x2`` topology:

* the closed-loop Pallas kernel, summary and trace mode (block_b=128,
  T=2048);
* one `_flat_core` scan chunk of the phased / detector / faulted /
  guarded / mixed-policy campaign grid `chip_smoke.py` runs;
* the control-plane tick at the 16,384-row capacity bucket.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and it keeps it until it exits.
The persistent compilation cache is off around these compiles — a
described-chip executable is written to it but cannot be read back.
"""
import functools
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core import executor, sim  # noqa: E402
from repro.kernels.closed_loop import kernel as K  # noqa: E402
from repro.kernels.closed_loop import ref as R  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # no skip: where libtpu cannot describe the chip, these tests fail
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding, rows=None):
    """ShapeDtypeStructs of a pytree on ``sharding``; ``rows`` replaces
    every leaf's leading (run/tenant) axis."""
    def one(x):
        shape = jnp.shape(x)
        if rows is not None:
            shape = (rows,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, jnp.result_type(x),
                                    sharding=sharding)
    return jax.tree_util.tree_map(one, tree)


class _Captured(Exception):
    pass


def _capture_grid(monkeypatch, call):
    """Run ``call`` up to its `executor.run_grid` and return that call's
    (fn, batched rows, shared arguments) instead of executing it."""
    def fake(fn, batched, shared, n_runs, **kw):
        raise _Captured(fn, batched, shared)

    monkeypatch.setattr(executor, "run_grid", fake)
    with pytest.raises(_Captured) as got:
        call()
    return got.value.args


@pytest.mark.parametrize("collect", [False, True],
                         ids=["summary", "trace"])
def test_closed_loop_kernel_compiles(one_chip, collect):
    B, T = 4096, 2048
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    fn = functools.partial(K.closed_loop_pallas, collect=collect,
                           block_b=128, chunk_t=64)
    compiled = jax.jit(fn).lower(
        f32((K.N_PROF, B)), f32((K.N_GAIN, B)), f32((T, R.N_NOISE, B)),
        f32((4,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_phased_faulted_scan_chunk_compiles(one_chip, monkeypatch):
    kw = chip_smoke.scan_campaign_kwargs()
    fn, batched, shared = _capture_grid(
        monkeypatch, lambda: sim.sweep(seeds=range(2), **kw))
    compiled = jax.jit(fn).lower(
        _shapes(batched, one_chip, rows=kw["chunk_size"]),
        *_shapes(shared, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_plane_tick_compiles_at_16k_bucket(one_chip, monkeypatch):
    from benchmarks.plane_load import make_plane

    plane = make_plane(chip_smoke.PLANE_TENANTS)
    assert plane.capacity == 16_384
    fn, rows, shared = _capture_grid(monkeypatch, plane.tick)
    compiled = jax.jit(fn).lower(_shapes(rows, one_chip),
                                 *_shapes(shared, one_chip)).compile()
    assert compiled.memory_analysis() is not None
