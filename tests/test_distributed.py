"""Multi-device tests (8 fake CPU devices via a subprocess — the main test
process must keep seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_serve_matches_oracle():
    r = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from repro.launch.mesh import make_host_mesh
        from repro.corpus import make_corpus, make_query_trace
        from repro.core import GeoSearchEngine, QueryBudgets
        from repro.core.distributed import (
            MortonPartitioner, shard_corpus_np, make_serve_fn,
        )

        corpus = make_corpus(n_docs=512, n_terms=100, seed=0)
        budgets = QueryBudgets(max_candidates=512, max_tiles=256, k_sweeps=4,
                               sweep_budget=256, top_k=10)
        mesh = make_host_mesh((4, 2), ("data", "model"))
        sharded = shard_corpus_np(corpus.doc_terms, corpus.doc_rects,
                                  corpus.doc_amps, corpus.pagerank,
                                  corpus.n_terms, 4, MortonPartitioner(),
                                  grid=32)
        serve = make_serve_fn(mesh, budgets, doc_axes=("data",), grid=32,
                              n_terms=corpus.n_terms)
        q = make_query_trace(corpus, n_queries=16, seed=1)
        with mesh:
            ids, scores = serve(sharded, q)
        eng = GeoSearchEngine.build(corpus.doc_terms, corpus.doc_rects,
                                    corpus.doc_amps, corpus.n_terms,
                                    pagerank=corpus.pagerank, grid=32,
                                    budgets=budgets)
        want = eng.oracle(q)
        w = np.asarray(want.ids); g = np.asarray(ids)
        hits = sum(len(set(w[b][w[b]>=0]) & set(g[b][g[b]>=0])) for b in range(16))
        tot = int(sum((w[b]>=0).sum() for b in range(16)))
        print(json.dumps({"recall": hits/max(tot,1), "shape": list(g.shape)}))
    """))
    assert r["recall"] >= 0.9
    assert r["shape"] == [16, 10]


def test_distributed_lm_train_step_matches_single_device():
    """SPMD data+tensor-parallel train step must be numerically close to the
    single-device step (same init, same batch)."""
    r = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.models.transformer import TransformerConfig, loss_fn
        from repro.train.loop import make_train_step
        from repro.train.optimizer import OptimizerConfig, init_opt_state
        from repro.sharding.specs import use_sharding

        cfg = TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                                d_ff=128, vocab=256, attn_chunk=16,
                                compute_dtype=jnp.float32)
        opt = OptimizerConfig(lr=1e-3, warmup_steps=1)
        params = cfg.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (8, 32), 0, 256)
        batch = {"tokens": toks, "labels": toks}

        # single device
        step1 = make_train_step(lambda p, b: loss_fn(cfg, p, b), opt, donate=False)
        p1, _, m1 = step1(params, init_opt_state(opt, params), batch)

        # 4x2 mesh
        mesh = make_host_mesh((4, 2), ("data", "model"))
        with use_sharding(mesh), mesh:
            stepN = make_train_step(lambda p, b: loss_fn(cfg, p, b), opt, donate=False)
            pN, _, mN = stepN(params, init_opt_state(opt, params), batch)
        dl = abs(float(m1["loss"]) - float(mN["loss"]))
        dw = max(float(jnp.abs(a - b).max())
                 for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pN)))
        print(json.dumps({"dloss": dl, "dparams": dw}))
    """))
    assert r["dloss"] < 1e-4
    assert r["dparams"] < 1e-4


def test_compressed_psum_matches_mean():
    """int8 compressed gradient all-reduce ≈ exact mean across shards."""
    r = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.train.compression import psum_compressed

        mesh = make_host_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(0, 1, (8, 512)).astype(np.float32))

        def body(g):
            g = g[0]
            mean, err = psum_compressed({"g": g}, {"g": jnp.zeros_like(g)}, ("data",))
            return mean["g"][None], err["g"][None]

        f = shard_map(body, mesh=mesh, in_specs=P("data"),
                      out_specs=(P("data"), P("data")), check_rep=False)
        with mesh:
            mean, err = f(g)
        want = np.asarray(g).mean(axis=0)
        got = np.asarray(mean)[0]
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        print(json.dumps({"rel_err": float(rel)}))
    """))
    assert r["rel_err"] < 0.05  # int8 grid error, corrected over steps by EF


def test_zero1_moment_sharding():
    """ZeRO-1: optimizer moments are sharded over data; params replicated."""
    r = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.optimizer import OptimizerConfig, zero1_sharding

        mesh = make_host_mesh((4, 2), ("data", "model"))
        spec = P(None, "model")
        sh = zero1_sharding(mesh, spec, (64, 32))
        print(json.dumps({"spec": str(sh.spec)}))
    """))
    assert "data" in r["spec"] and "model" in r["spec"]


def test_mesh_executor_serving_stack():
    """The serving stack (cache + batcher) over the shard_map MeshExecutor:
    with full budgets, mesh-served results must match the exact oracle."""
    r = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from repro.launch.mesh import make_host_mesh
        from repro.corpus import make_corpus, make_query_trace, make_zipf_trace
        from repro.core import GeoSearchEngine, QueryBudgets
        from repro.serving import (
            GeoServer, LRUCache, MeshExecutor, ShapeBucketedBatcher,
        )

        corpus = make_corpus(n_docs=512, n_terms=100, seed=0)
        budgets = QueryBudgets(max_candidates=1024, max_tiles=2048, k_sweeps=8,
                               sweep_budget=1024, top_k=10)
        mesh = make_host_mesh((8, 1), ("data", "model"))
        from repro.core.distributed import MortonPartitioner
        mx = MeshExecutor.build(
            corpus.doc_terms, corpus.doc_rects, corpus.doc_amps,
            corpus.n_terms, pagerank=corpus.pagerank, mesh=mesh,
            partitioner=MortonPartitioner(), grid=32, budgets=budgets)
        eng = GeoSearchEngine.build(
            corpus.doc_terms, corpus.doc_rects, corpus.doc_amps,
            corpus.n_terms, pagerank=corpus.pagerank, grid=32,
            budgets=budgets)
        q = make_query_trace(corpus, n_queries=16, seed=1)
        got = mx.run(q)
        want = eng.oracle(q)  # exact ground truth
        g, w = np.asarray(got.ids), np.asarray(want.ids)
        hits = sum(len(set(w[b][w[b]>=0]) & set(g[b][g[b]>=0]))
                   for b in range(16))
        tot = int(sum((w[b]>=0).sum() for b in range(16)))

        # and the full serve loop on top of the mesh executor
        server = GeoServer(mx, cache=LRUCache(256),
                           batcher=ShapeBucketedBatcher(max_batch=8))
        rep = server.run_trace(make_zipf_trace(corpus, n_queries=64,
                                               pool_size=16, seed=2))
        print(json.dumps({"recall": hits/max(tot,1),
                          "served": rep.n_queries,
                          "hit_rate": rep.hit_rate}))
    """))
    assert r["recall"] >= 0.99
    assert r["served"] == 64
    assert r["hit_rate"] >= 0.30


def test_mesh_executor_stacks_doc_plane():
    """A compressed MeshExecutor stacks each shard's doc-id plane: shape
    [S, NBp, 128], each shard's live slots are exactly its documents of
    each term, every other slot (past blk_len, padded blocks) is the pad,
    and the mesh's answers still match the exact oracle."""
    r = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.corpus import make_corpus, make_query_trace
        from repro.core import GeoSearchEngine, QueryBudgets
        from repro.core.distributed import MortonPartitioner
        from repro.core.text_index import DOC_PLANE_PAD, POSTING_BLOCK
        from repro.serving import MeshExecutor

        corpus = make_corpus(n_docs=512, n_terms=100, seed=0)
        budgets = QueryBudgets(max_candidates=1024, max_tiles=2048, k_sweeps=8,
                               sweep_budget=1024, top_k=10)
        mesh = make_host_mesh((4, 1), ("data", "model"))
        mx = MeshExecutor.build(
            corpus.doc_terms, corpus.doc_rects, corpus.doc_amps,
            corpus.n_terms, pagerank=corpus.pagerank, mesh=mesh,
            partitioner=MortonPartitioner(), grid=32, budgets=budgets,
            compress="f16")
        idx = mx._index
        plane = np.asarray(idx.doc_plane)
        blk_len = np.asarray(idx.blk_len)
        bto = np.asarray(idx.blk_term_off)
        gid = np.asarray(idx.doc_offset)
        S, NBp = idx.blk_first.shape
        live = np.arange(POSTING_BLOCK)[None, None, :] < blk_len[:, :NBp, None]
        ok = bool((plane[~live] == DOC_PLANE_PAD).all())
        for s in range(S):
            for t in range(corpus.n_terms):
                rows = plane[s, bto[s, t]:bto[s, t + 1]]
                lv = live[s, bto[s, t]:bto[s, t + 1]]
                got = np.sort(gid[s][rows[lv]])
                want = [g for g in range(len(corpus.doc_terms))
                        if t in corpus.doc_terms[g] and g in set(gid[s])]
                ok = ok and np.array_equal(got, np.asarray(want))
        eng = GeoSearchEngine.build(
            corpus.doc_terms, corpus.doc_rects, corpus.doc_amps,
            corpus.n_terms, pagerank=corpus.pagerank, grid=32,
            budgets=budgets, compress="f16")
        q = make_query_trace(corpus, n_queries=16, seed=1)
        g, w = np.asarray(mx.run(q).ids), np.asarray(eng.oracle(q).ids)
        hits = sum(len(set(w[b][w[b]>=0]) & set(g[b][g[b]>=0]))
                   for b in range(16))
        tot = int(sum((w[b]>=0).sum() for b in range(16)))
        print(json.dumps({"shape": list(plane.shape), "dtype": str(plane.dtype),
                          "nbp": int(NBp), "S": int(S), "rows_ok": ok,
                          "recall": hits / max(tot, 1)}))
    """))
    assert r["shape"] == [r["S"], r["nbp"], 128] and r["S"] == 4
    assert r["dtype"] == "int32" and r["nbp"] > 0
    assert r["rows_ok"]
    assert r["recall"] >= 0.99
