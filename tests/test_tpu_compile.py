"""Compile-only checks of the serving kernels for a TPU v5e chip.

The chip is described, not attached: each case lowers and compiles one
kernel entry point at the deployment's widths (769-d corpus rows and cache
entries, 64-row waves, k_c = 1000) for one chip of a ``v5e:2x2`` topology
and checks that the Mosaic kernel is in the compiled program.  A tiling or
VMEM mistake that interpret mode cannot see fails here, with no chip time
spent.  The topology is described inside a module fixture, which skips
when this installation cannot describe it.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import cache_ops
from repro.kernels.knn.ops import knn_search

pytestmark = pytest.mark.kernels

N, D = 65536, 769                 # corpus rows, transformed width
S, CAPACITY, K_C, K = 64, 16000, 1000, 10


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_knn_search_compiles(one_chip, dtype):
    _assert_kernel(
        lambda d, i, s, q: knn_search(d, i, q, K_C, backend="compiled",
                                      scale=s),
        _shape(one_chip, (N, D), dtype), _shape(one_chip, (N,), jnp.int32),
        _shape(one_chip, (N,), jnp.float32),
        _shape(one_chip, (S, D), jnp.float32))


def _state(one_chip, store):
    cfg = cache_ops.CacheConfig(capacity=CAPACITY, dim=D, store_dtype=store)
    st = jax.eval_shape(lambda: cache_ops.init_batched_cache(cfg, S))
    return cfg, jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype), st)


def _wave_args(one_chip):
    return (_shape(one_chip, (S, D), jnp.float32),
            _shape(one_chip, (S,), jnp.float32),
            _shape(one_chip, (S, K_C, D), jnp.float32),
            _shape(one_chip, (S, K_C), jnp.int32),
            _shape(one_chip, (S,), jnp.bool_),
            _shape(one_chip, (S,), jnp.bool_))


@pytest.mark.parametrize("store", ["fp32", "int8"])
@pytest.mark.parametrize("op", ["probe", "query", "insert_query", "insert"])
def test_cache_wave_ops_compile(one_chip, store, op):
    cfg, st = _state(one_chip, store)
    psi = _shape(one_chip, (S, D), jnp.float32)
    if op == "probe":
        _assert_kernel(lambda s, p: cache_ops.probe_batched(
            s, p, cfg.epsilon, backend="compiled",
            max_queries=cfg.max_queries), st, psi)
    elif op == "query":
        _assert_kernel(lambda s, p: cache_ops.query_batched(
            s, p, K, backend="compiled"), st, psi)
    elif op == "insert_query":
        _assert_kernel(lambda s, p, r, e, i, do, rec:
                       cache_ops.insert_query_batched(
                           s, cfg, p, r, e, i, K, do=do, record=rec,
                           backend="compiled"), st, *_wave_args(one_chip))
    else:
        _assert_kernel(lambda s, p, r, e, i, do, rec:
                       cache_ops.insert_batched(
                           s, cfg, p, r, e, i, do=do, record=rec,
                           backend="compiled"), st, *_wave_args(one_chip))
