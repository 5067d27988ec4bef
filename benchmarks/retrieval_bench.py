"""Back-end retrieval benchmark: exact_nn vs chunked_nn vs sharded retrieval
at 1M synthetic docs — the perf trajectory anchor for the distributed index.

Writes ``BENCH_retrieval.json`` and returns rows for the harness CSV.

``benchmarks.run`` calls ``run`` in its own process, over whatever devices
that process has (one process per chip: a child started after the parent
touched JAX could not get the TPU).  Run as its own entry point
(``python -m benchmarks.retrieval_bench``) on a CPU host, it first forces
8 virtual host devices so the sharded rows have a mesh to shard over.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.core import embedding as emb
from repro.core.metric_index import chunked_nn, exact_nn
from repro.dist import retrieval as dr
from repro.launch.hostdevices import ensure_host_devices

N_DOCS = 1 << 20
DIM = 64
N_QUERIES = 16
K = 100
CHUNK = 4096


def _make_corpus(n=N_DOCS, dim=DIM, nq=N_QUERIES, seed=0):
    rng = np.random.default_rng(seed)
    docs, _ = emb.transform_documents(
        jnp.asarray(rng.standard_normal((n, dim), ).astype(np.float32)))
    queries = emb.transform_queries(
        jnp.asarray(rng.standard_normal((nq, dim)).astype(np.float32)))
    ids = jnp.arange(n, dtype=jnp.int32)
    return docs, ids, queries


def run(out_path: str = "BENCH_retrieval.json") -> dict:
    docs, ids, queries = _make_corpus()
    n_dev = jax.device_count()

    t_exact, ref = timed(lambda: exact_nn(docs, ids, queries, K))
    t_chunk, res_c = timed(
        lambda: chunked_nn(docs, ids, queries, K, chunk=CHUNK))
    t_shard, res_s = timed(
        lambda: dr.sharded_nn(docs, ids, queries, K, chunk=CHUNK))

    identical = bool(
        np.array_equal(np.asarray(ref.ids), np.asarray(res_c.ids))
        and np.array_equal(np.asarray(ref.ids), np.asarray(res_s.ids)))

    record = {
        "n_docs": N_DOCS, "dim": DIM, "n_queries": N_QUERIES, "k": K,
        "chunk": CHUNK, "n_devices": n_dev,
        "exact_us": 1e6 * t_exact,
        "chunked_us": 1e6 * t_chunk,
        "sharded_us": 1e6 * t_shard,
        "sharded_speedup_vs_chunked": t_chunk / max(t_shard, 1e-12),
        "rankings_identical": identical,
        "timestamp": time.time(),
    }
    # merge-update: keep other sections (e.g. kernel_bench's "kernels" rows)
    from benchmarks.kernel_bench import merge_json
    merge_json(out_path, record)
    return record


if __name__ == "__main__":
    ensure_host_devices(8)
    print(json.dumps(run()))
