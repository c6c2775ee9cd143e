"""Compile the aggregation kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached: what Mosaic refuses (rank-1 blocks over a
longer array, unaligned tiles, dot layouts it cannot lower) fails here at
no chip time.  Interpret-mode parity lives in test_kernels.py,
test_server_step.py and test_robust_agg.py; these cases only prove that
``impl="pallas"`` lowers to a real kernel (``tpu_custom_call``) at the
shapes the engine uses — X=512 cohort rows of the default classifier's
packed D=22,026 — plus a client count below one sublane tile and the
4-chip client-mesh path.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the suite runs under several
workers that all import this file.
"""
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.fed_agg.kernel import fed_agg_pallas
from repro.kernels.fed_agg.ops import fed_agg_packed_sharded
from repro.kernels.robust_agg.kernel import residual_norms_pallas
from repro.kernels.robust_agg.ops import geometric_median

X, D = 512, 22026          # engine cohort rows x default classifier's D


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one — keep the cache out of it
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [X, 5])
def test_fed_agg_compiles(one_chip, c):
    compiled = fed_agg_pallas.lower(
        _sds((c, D), one_chip), _sds((c,), one_chip)).compile()
    _assert_kernel(compiled)


def test_fed_agg_compiles_for_adapter_rows(one_chip):
    """The deepseek-v2-lite cell's uploads: 16 rows of its LoRA adapters
    (D = 1,579,008 = 2,048 x 771)."""
    compiled = fed_agg_pallas.lower(
        _sds((16, 1579008), one_chip), _sds((16,), one_chip)).compile()
    _assert_kernel(compiled)


def test_residual_norms_compiles(one_chip):
    compiled = residual_norms_pallas.lower(
        _sds((X, D), one_chip), _sds((D,), one_chip)).compile()
    _assert_kernel(compiled)


def test_geometric_median_compiles(one_chip):
    compiled = jax.jit(partial(geometric_median, impl="pallas")).lower(
        _sds((X, D), one_chip), _sds((X,), one_chip)).compile()
    _assert_kernel(compiled)


def test_fed_agg_sharded_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("clients",),
                axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, P("clients", None))
    col = NamedSharding(mesh, P("clients"))
    compiled = jax.jit(partial(fed_agg_packed_sharded, mesh=mesh,
                               impl="pallas")).lower(
        _sds((X, D), rows), _sds((X,), col)).compile()
    _assert_kernel(compiled)
    assert "all-reduce" in compiled.as_text()
