"""repro.dist: logical sharding API, spec derivation, and the sharded
back-end retrieval layer (bit-identity with exact_nn on a multi-device mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.configs import registry
from repro.core import embedding as emb
from repro.core.metric_index import MetricIndex, exact_nn
from repro.dist import retrieval as dr
from repro.dist import sharding as shd
from repro.dist.api import (active_mesh, constrain, data_axes, fit_spec,
                            sharding_rules)
from repro.models import transformer as tf

jax.config.update("jax_platform_name", "cpu")


def test_multi_device_topology():
    """conftest forces 8 host devices; everything below depends on it."""
    assert jax.device_count() >= 2


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def _corpus(n, dim, seed=0, n_dup=0, n_queries=5):
    """Transformed corpus + queries; first n_dup docs duplicated mid-corpus
    so top-k tie-breaking is actually exercised."""
    rng = np.random.default_rng(seed)
    phi = rng.standard_normal((n, dim)).astype(np.float32)
    if n_dup:
        phi[n // 2:n // 2 + n_dup] = phi[:n_dup]
    docs, _ = emb.transform_documents(jnp.asarray(phi))
    q = emb.transform_queries(jnp.asarray(
        rng.standard_normal((n_queries, dim)).astype(np.float32)))
    return docs, jnp.arange(n, dtype=jnp.int32), q


# ----------------------------------------------------------------- dist.api

def test_constrain_identity_without_context():
    x = jnp.ones((4, 8))
    assert constrain(x, "act_bsd") is x
    assert active_mesh() is None


def test_sharding_rules_context_applies_and_fits():
    mesh = _mesh((2, 4), ("data", "model"))
    assert data_axes(mesh) == ("data",)
    rules = {"act_bsd": P("data", None, "model")}
    with sharding_rules(mesh, rules):
        assert active_mesh() is mesh
        y = jax.jit(lambda a: constrain(a, "act_bsd"))(jnp.zeros((4, 8, 16)))
        # batch split 2-way, last dim 4-way
        assert y.addressable_shards[0].data.shape == (2, 8, 4)
        # non-divisible dims: offending axes dropped, no error
        z = jax.jit(lambda a: constrain(a, "act_bsd"))(jnp.zeros((3, 8, 6)))
        assert z.shape == (3, 8, 6)
        # unknown rule name: identity
        w = jnp.zeros((5,))
        assert constrain(w, "no_such_rule") is w
    assert active_mesh() is None


def test_fit_spec_pads_and_drops():
    mesh = _mesh((2, 4), ("data", "model"))
    assert tuple(fit_spec(P("data"), (6, 7), mesh)) == ("data", None)
    assert tuple(fit_spec(P("data", "model"), (6, 7), mesh)) == ("data", None)
    assert fit_spec(P("data", None, None), (6,), mesh) is None


# ------------------------------------------------------------ dist.sharding

def test_param_specs_full_rank_and_divisible():
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = registry.get("star-encoder").full_config()
    shapes = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg))
    specs = shd.param_specs(shapes, mesh)
    assert jax.tree.structure(specs) == jax.tree.structure(
        shapes, is_leaf=lambda x: hasattr(x, "shape"))
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    n_sharded = 0
    for leaf, spec in zip(flat_shapes, flat_specs):
        assert isinstance(spec, P) and len(tuple(spec)) == leaf.ndim
        # every assignment must already fit (param_specs guarantees this)
        assert tuple(fit_spec(spec, leaf.shape, mesh)) == tuple(spec)
        n_sharded += any(e is not None for e in tuple(spec))
    assert n_sharded > 0    # the big matrices actually shard


def test_param_specs_moe_expert_parallel():
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = registry.get("deepseek-v3-671b").smoke_config()
    shapes = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg))
    specs = shd.param_specs(shapes, mesh, min_shard_size=1)
    for gname, group in specs.items():
        if "moe" not in gname:
            continue
        wi = tuple(group["ffn"]["wi"])      # (layers, E, d, 2ff)
        assert wi[1] == "model" or wi[1] is None  # expert dim, if divisible
        if cfg.moe.n_experts % 4 == 0:
            assert wi[1] == "model"


def test_lm_activation_rules_cover_all_constrain_names():
    mesh = _mesh((2, 4), ("data", "model"))
    for arch in ("gemma2-9b", "deepseek-v3-671b"):
        cfg = registry.get(arch).full_config()
        for kind in ("train", "decode"):
            rules = shd.lm_activation_rules(mesh, cfg, kind)
            for name in ("act_bsd", "act_bsf", "act_bshd", "act_bskd",
                         "attn_scores", "kv_cache", "mla_cache",
                         "mla_cache_r", "logits", "moe_buf", "moe_hidden",
                         "moe_out", "act_bfd"):
                assert name in rules and isinstance(rules[name], P)

    class Dummy:     # the recsys stub from launch/cells
        n_heads = 1
        n_kv_heads = 1
        attention = "gqa"

    rules = shd.lm_activation_rules(mesh, Dummy(), "train")
    assert tuple(rules["act_bshd"])[2] is None   # 1 head cannot split 4 ways


def test_forward_under_sharding_rules_matches_unsharded():
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = registry.get("star-encoder").smoke_config()
    params = tf.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    ref = tf.forward(params, tokens, cfg, remat="none")[0]
    rules = shd.lm_activation_rules(mesh, cfg, "train")
    with sharding_rules(mesh, rules):
        out = jax.jit(
            lambda p, t: tf.forward(p, t, cfg, remat="none")[0])(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4)


def test_cells_build_on_host_mesh():
    """The launch layer's cell builders run end-to-end on the dist API
    (eval_shape only — no compile, no allocation)."""
    from repro.launch.cells import build_lm_cell, build_recsys_cell
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    cfg = registry.get("star-encoder").smoke_config()
    cell = build_lm_cell("star-encoder", "train_4k", mesh, cfg_override=cfg)
    assert cell.kind == "train" and cell.rules and cell.in_shardings
    cell = build_lm_cell("star-encoder", "decode_32k", mesh, cfg_override=cfg)
    assert cell.kind == "decode"
    cell = build_recsys_cell("sasrec", "retrieval_cand", mesh)
    assert cell.kind == "retrieval" and callable(cell.fn)


# ----------------------------------------------------------- dist.retrieval

@pytest.mark.parametrize("n", [4096, 5003])
def test_sharded_nn_bit_identical_to_exact(n):
    docs, ids, q = _corpus(n, 32, n_dup=16)
    ref = exact_nn(docs, ids, q, 25)
    meshes = [None,                                     # flat all-device mesh
              _mesh((8,), ("shard",)),
              _mesh((2, 4), ("data", "model"))]         # multi-axis corpus
    for mesh in meshes:
        res = dr.sharded_nn(docs, ids, q, 25, mesh=mesh, chunk=512)
        np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
        np.testing.assert_allclose(np.asarray(res.scores),
                                   np.asarray(ref.scores), rtol=1e-6)
        assert (np.diff(np.asarray(res.distances), axis=1) >= -1e-6).all()


def test_sharded_nn_k_larger_than_shard():
    # k exceeds the per-device slice: merge must still be exact
    docs, ids, q = _corpus(300, 16, seed=3)
    ref = exact_nn(docs, ids, q, 120)
    res = dr.sharded_nn(docs, ids, q, 120, chunk=64)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))


def test_metric_index_sharded_path_matches_local():
    rng = np.random.default_rng(7)
    raw = rng.standard_normal((3000, 48)).astype(np.float32)
    local = MetricIndex(jnp.asarray(raw), chunk=256)
    shard = MetricIndex(jnp.asarray(raw), chunk=256, sharded=True)
    q = local.transform_queries(jnp.asarray(
        rng.standard_normal((4, 48)).astype(np.float32)))
    a = local.search(q, 30)
    b = shard.search(q, 30)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    # 1-D query convenience path
    c = shard.search(q[0], 10)
    assert c.ids.shape == (1, 10)


def test_batched_scorer_masks_and_matches_reference():
    mesh = _mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(11)
    table = jnp.asarray(rng.standard_normal((512, 64)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
    scorer = dr.make_batched_scorer(mesh, k=10, table_axes=("model",),
                                    batch_axes=("data",))
    scores, idx = jax.jit(lambda a, b: scorer(a, b, n_valid=300))(q, table)
    ref = np.asarray(q @ table.T)[:, :300]
    ref_idx = np.argsort(-ref, axis=1)[:, :10]
    np.testing.assert_array_equal(np.asarray(idx), ref_idx)
    assert int(np.asarray(idx).max()) < 300


def test_device_shards_front_the_router():
    from repro.serve.router import ShardedRouter
    rng = np.random.default_rng(5)
    raw = rng.standard_normal((2000, 32)).astype(np.float32)
    index = MetricIndex(jnp.asarray(raw))
    shards = dr.make_device_shards(index.doc_emb, index.doc_ids)
    assert len(shards) >= 2
    assert len({s.device for s in shards}) == len(shards)   # distinct devices
    router = ShardedRouter(shards, deadline_s=30)
    q = np.asarray(index.transform_queries(jnp.asarray(
        rng.standard_normal((3, 32)).astype(np.float32))))
    ans, degraded = router.search(q, 15)
    assert not degraded
    ref = index.search(jnp.asarray(q), 15)
    np.testing.assert_array_equal(ans.ids, np.asarray(ref.ids))


def test_router_over_devices_constructor():
    from repro.serve.router import ShardedRouter
    rng = np.random.default_rng(9)
    raw = rng.standard_normal((500, 16)).astype(np.float32)
    index = MetricIndex(jnp.asarray(raw))
    router = ShardedRouter.over_devices(index.doc_emb, index.doc_ids,
                                        deadline_s=30)
    q = np.asarray(index.transform_queries(jnp.asarray(
        rng.standard_normal((2, 16)).astype(np.float32))))
    ans, degraded = router.search(q, 10)
    assert not degraded and ans.ids.shape == (2, 10)
    np.testing.assert_array_equal(
        ans.ids, np.asarray(index.search(jnp.asarray(q), 10).ids))


def test_device_shards_place_each_slice_on_its_own_device():
    """Each shard's payload, ids and scales live on its own device only,
    stored lane-aligned with sentinel-padded rows; the shards together
    answer like the single-device index."""
    from repro.serve.router import ShardedRouter
    rng = np.random.default_rng(11)
    raw = rng.standard_normal((3000, 40)).astype(np.float32)
    index = MetricIndex(jnp.asarray(raw))
    docs = np.asarray(index.dequantized())[:index.n_docs]
    devices = jax.devices()[:4]
    shards = dr.make_device_shards(docs, devices=devices, dtype="int8")
    for sh, dev in zip(shards, devices):
        for x in (sh.docs, sh.doc_ids, sh.scale):
            assert x.devices() == {dev}
        assert sh.docs.dtype == jnp.int8 and sh.docs.shape[1] % 128 == 0
        assert sh.docs.shape[0] % sh.chunk == 0
        pad = np.asarray(sh.doc_ids) < 0
        assert (np.asarray(sh.scale)[pad] == 1).all()
        assert not np.asarray(sh.docs)[pad].any()
    router = ShardedRouter(shards, deadline_s=30)
    q = np.asarray(index.transform_queries(jnp.asarray(
        rng.standard_normal((3, 40)).astype(np.float32))))
    ans, degraded = router.search(q, 12)
    ref = MetricIndex(jnp.asarray(docs), transformed=True, dtype="int8")
    assert not degraded
    np.testing.assert_array_equal(ans.ids,
                                  np.asarray(ref.search(jnp.asarray(q), 12).ids))
    router.close()


def test_shard_corpus_lays_host_rows_over_the_mesh():
    rng = np.random.default_rng(12)
    docs = rng.standard_normal((1000, 24)).astype(np.float32)
    ids = np.arange(1000, dtype=np.int32)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("shard",))
    d, i, s, _, chunk = dr.shard_corpus(docs, ids, mesh=mesh, chunk=64)
    assert s is None and d.shape[0] % (4 * chunk) == 0
    assert len(d.sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(d)[:1000], docs)
    assert (np.asarray(i)[1000:] == -1).all()
