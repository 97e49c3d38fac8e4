"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles for a v5e device that is
described, not attached (``jax.experimental.topologies``), at the widths
``chip_smoke.py`` runs.  What the TPU compiler refuses — a Pallas block the
chip's tiling rejects, a primitive with no TPU lowering — fails here on the
CPU instead of on the chip.  Kernels are compiled directly with
``interpret=False``: the ``ops`` wrappers ask ``jax.default_backend()``,
which is the CPU here.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the worker that runs this file
keeps it until it exits.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import dmd
from repro.kernels import gram, ops, quant
from repro.sim import cfd

D = 2304          # one CFD slab of the 192x96 deployment: 6 rows x 192 x 2
QBLOCK = 256      # codec block (core.records.QBLOCK)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache: keep it off while this file runs
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("n", [8, 128])
def test_gram_pair_kernel_compiles_at_slab_width(sds, n):
    blocks = ops.get_block_config("gram_pair")
    fn = jax.jit(partial(gram.gram_pair_accumulate, interpret=False,
                         **blocks), donate_argnums=(2, 3))
    text = _compiled_text(fn, sds((n, D)), sds((n, D)), sds((D, D)),
                          sds((D, D)))
    assert "tpu_custom_call" in text


# 288 rows: a 32-record frame of 2304-float slabs (9 blocks each); 1024:
# a four-step grid.  Both failed while the scales were a 1-D block.
@pytest.mark.parametrize("rows", [288, 1024])
def test_quantize_kernel_compiles_multi_step_grid(sds, rows):
    fn = jax.jit(partial(quant.quantize, block_rows=QBLOCK, interpret=False))
    assert "tpu_custom_call" in _compiled_text(fn, sds((rows, QBLOCK)))


@pytest.mark.parametrize("rows", [288, 1024])
def test_dequantize_kernel_compiles_multi_step_grid(sds, rows):
    fn = jax.jit(partial(quant.dequantize, block_rows=QBLOCK,
                         interpret=False))
    text = _compiled_text(fn, sds((rows, QBLOCK), jnp.int8), sds((rows,)))
    assert "tpu_custom_call" in text


def test_batched_window_reduction_compiles(sds):
    """The device half of ``batched_window_dmd``: 16 co-fired panes of up
    to 32 snapshot rows of d=2304, unpadded."""
    _compiled_text(dmd._batched_operator(8), sds((16, 32, D)),
                   sds((16,), jnp.int32))


def test_exact_dmd_reduction_compiles(sds):
    _compiled_text(dmd._exact_operator, sds((D, 16)), rank=8)


def test_cfd_step_compiles(sds):
    cfg = cfd.CFDConfig(nx=192, nz=96, n_regions=16, pressure_iters=50)
    state = {k: sds((cfg.nz, cfg.nx)) for k in ("u", "w", "p", "mask")}
    _compiled_text(cfd.step, state, cfg)
