"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with JAX; it compiles for a v5e that is
described (``get_topology_desc``) and not attached. That refuses what the
Pallas interpreter accepts (blocks off the tiling, gathers Mosaic cannot
lower) and programs that do not fit the chip's memory. Nothing runs, so
these tests say nothing about results or speed.

The topology is described only inside the module fixture: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.batch_query import (DeviceIndex, _host_layout, batch_query,
                                    batch_query_full, batch_query_full_mixed,
                                    window_sweep)
from repro.core.core_time import (_count_le_pallas, _pair_csr, _sweep_block,
                                  _tuv_rows, count_le_csr)
from repro.core.pecb_index import build_stratified_index
from repro.core.temporal_graph import bench_graph
from repro.kernels import label_prop, segmented_select

HBM_BYTES = 16 * 2**30          # one v5e chip
B = 256                         # the engine's largest bucket


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to a persistent cache cannot be read back
    # without the chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    # drop traces made for the described chip before later tests trace
    # the same functions for the CPU
    jax.clear_caches()


@pytest.fixture(scope="module")
def fb_like():
    g = bench_graph("fb_like")
    return g, build_stratified_index(g)


def _struct(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_index(index, sharding) -> DeviceIndex:
    """The DeviceIndex of ``index`` as shapes placed on the described chip."""
    meta, arrays = _host_layout(index)
    return DeviceIndex(**meta, **{k: _struct(v.shape, sharding)
                                  for k, v in arrays.items()})


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["batch_query", "batch_query_full",
                                     "batch_query_full_mixed",
                                     "window_sweep"])
def test_query_program_fits_one_chip(one_chip, fb_like, program):
    g, sx = fb_like
    q = _struct((B,), one_chip)
    if program == "batch_query":
        lowered = batch_query.lower(_device_index(sx, one_chip), q, q, q)
    elif program == "batch_query_full":
        lowered = batch_query_full.lower(_device_index(sx, one_chip), q, q, q)
    elif program == "batch_query_full_mixed":
        lowered = batch_query_full_mixed.lower(_device_index(sx, one_chip),
                                               q, q, q, q)
    else:
        # the engine sweeps against one stratum's mirror
        k = sx.supported_ks[len(sx.supported_ks) // 2]
        lowered = window_sweep.lower(_device_index(sx.slice_k(k), one_chip),
                                     _struct((), one_chip), q, q)
    # the launch's outputs end with its int32 pointer-jump round count
    rounds = jax.tree_util.tree_leaves(lowered.out_info)[-1]
    assert rounds.shape == () and rounds.dtype == jnp.int32
    compiled = lowered.compile()
    assert 0 < _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("count_fn", [count_le_csr, _count_le_pallas],
                         ids=["jnp", "pallas"])
def test_construction_sweep_compiles(one_chip, fb_like, monkeypatch,
                                     count_fn):
    g, sx = fb_like
    # the test process runs on the CPU, where kernels default to the
    # interpreter; compile the Mosaic kernel the chip would run
    monkeypatch.setattr(segmented_select, "interpret_mode", lambda i: False)
    csr = _pair_csr(g)
    inf = g.t_max + 1
    ksteps = int(np.ceil(np.log2(inf + 1))) + 1
    rows = _tuv_rows(csr, 1, g.t_max + 1, g.t_max)
    e = csr.src.shape[0]
    compiled = _sweep_block.lower(
        count_fn, sx.supported_ks[0], inf, ksteps,
        _struct(rows.shape, one_chip), _struct((e,), one_chip),
        _struct((e,), one_chip), _struct(csr.vptr.shape, one_chip),
        _struct((g.n,), one_chip)).compile()
    assert 0 < _total_bytes(compiled) < HBM_BYTES
    has_kernel = "tpu_custom_call" in compiled.as_text()
    assert has_kernel == (count_fn is _count_le_pallas)


def test_segmented_count_le_lowers_to_mosaic(one_chip):
    e, n = 120_000, 1899     # CollegeMsg-scale pair slots and vertices
    fn = jax.jit(lambda w, seg, thr: segmented_select.segmented_count_le(
        w, seg, thr, n, interpret=False))
    compiled = fn.lower(_struct((e,), one_chip), _struct((e,), one_chip),
                        _struct((n,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_label_prop_round_lowers_to_mosaic(one_chip):
    # Mosaic gathers within one (8, 128) vreg: rows of at most 128 nodes
    n = 128
    fn = jax.jit(lambda *a: label_prop.label_prop_round(*a, interpret=False))
    rows = _struct((B, n), one_chip)
    compiled = fn.lower(rows, rows, rows, rows,
                        _struct((B, n), one_chip, jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
