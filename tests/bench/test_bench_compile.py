"""Compile-only rehearsal of each benchmark configuration's kernels.

For one chip of a described ``v5e:2x2`` topology, at the shapes the
configuration runs: the corpus scan over the stored int8 payload at the
full wave bucket and ``k_c``, and the batched cache probe, query and fused
insert+query over the configuration's L1 state.  A tiling or memory fault
shows here with no chip time spent.  The topology is described inside a
module fixture, which skips when this installation cannot describe it.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.core import cache_ops, layout
from repro.core.metric_index import scan_topk

pytestmark = pytest.mark.kernels

CONFIGS = sorted((pathlib.Path(__file__).resolve().parents[2] / "bench"
                  / "configs").glob("*.json"))
CHUNK = 4096


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
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sizes(path):
    c = json.loads(path.read_text())
    n = c["n_docs"] + (-c["n_docs"]) % CHUNK          # DeviceShard's padding
    return c, n, layout.phys_dim(c["raw_dim"] + 1)


def _compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_scan_compiles(one_chip, path):
    c, n, d = _sizes(path)

    def shape(s, t):
        return jax.ShapeDtypeStruct(s, t, sharding=one_chip)
    _compiles(lambda x, i, s, q: scan_topk(x, i, q, c["k_c"], chunk=CHUNK,
                                           backend="compiled", scale=s),
              shape((n, d), jnp.int8), shape((n,), jnp.int32),
              shape((n,), jnp.float32), shape((c["l1_slots"], d), jnp.float32))


@pytest.mark.parametrize("op", ["probe", "query", "insert_query"])
@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_cache_ops_compile(one_chip, path, op):
    c, _, _ = _sizes(path)
    s, dim, k_c = c["l1_slots"], c["raw_dim"] + 1, c["k_c"]
    cfg = cache_ops.CacheConfig(capacity=c["l1_capacity"], dim=dim,
                                epsilon=c["epsilon"], store_dtype="fp32")
    st = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: cache_ops.init_batched_cache(cfg, s)))

    def shape(sh, t):
        return jax.ShapeDtypeStruct(sh, t, sharding=one_chip)
    psi = shape((s, dim), jnp.float32)
    if op == "probe":
        _compiles(lambda a, p: cache_ops.probe_batched(
            a, p, cfg.epsilon, backend="compiled",
            max_queries=cfg.max_queries), st, psi)
    elif op == "query":
        _compiles(lambda a, p: cache_ops.query_batched(
            a, p, c["k"], backend="compiled"), st, psi)
    else:
        _compiles(lambda a, p, r, e, i, do, rec: cache_ops.insert_query_batched(
            a, cfg, p, r, e, i, c["k"], do=do, record=rec,
            backend="compiled"), st, psi, shape((s,), jnp.float32),
            shape((s, k_c, dim), jnp.float32), shape((s, k_c), jnp.int32),
            shape((s,), jnp.bool_), shape((s,), jnp.bool_))
