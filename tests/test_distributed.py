"""Multi-device behaviour: the sharded search executor and engine.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest session keeps the default single CPU device.
"""

import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_in_child(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 8
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_topk_matches_exact():
    _run_in_child("""
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharded_search import (sharded_topk,
                                                      shard_rows, replicate)
        from repro.kernels import ops
        mesh = make_host_mesh(data=8, model=1)
        rng = np.random.default_rng(0)
        base = rng.standard_normal((4096, 32)).astype(np.float32)
        queries = rng.standard_normal((16, 32)).astype(np.float32)
        b = shard_rows(mesh, jnp.asarray(base))
        q = replicate(mesh, jnp.asarray(queries))
        with mesh:
            d, i = sharded_topk(mesh, q, b, 10)
        rv, ri = ops.topk_numpy(queries, base, 10)
        np.testing.assert_allclose(np.asarray(d), rv, atol=1e-3, rtol=1e-4)
        # index sets must match (ties aside, distances already checked)
        for r in range(16):
            assert len(set(np.asarray(i)[r].tolist())
                       & set(ri[r].tolist())) >= 9
        print("sharded_topk ok")
    """)


def test_sharded_topk_with_pattern_mask():
    """The VectorMaton distributed path: V_p as a validity mask."""
    _run_in_child("""
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharded_search import (sharded_topk,
                                                      shard_rows, replicate)
        from repro.kernels import ops
        mesh = make_host_mesh(data=8, model=1)
        rng = np.random.default_rng(1)
        base = rng.standard_normal((2048, 16)).astype(np.float32)
        queries = rng.standard_normal((4, 16)).astype(np.float32)
        mask = rng.random(2048) < 0.3
        with mesh:
            d, i = sharded_topk(mesh, replicate(mesh, jnp.asarray(queries)),
                                shard_rows(mesh, jnp.asarray(base)), 5,
                                valid_mask=shard_rows(
                                    mesh, jnp.asarray(mask)))
        ids = np.where(mask)[0]
        rv, ri = ops.topk_numpy(queries, base[ids], 5)
        np.testing.assert_allclose(np.asarray(d), rv, atol=1e-3, rtol=1e-4)
        got = np.asarray(i)
        assert all(mask[x] for x in got.ravel() if x >= 0)
        print("masked sharded_topk ok")
    """)


def test_sharded_topk_non_divisible_n_and_sentinels():
    """Satellite regressions: arbitrary N on any mesh (203 % 8 != 0), and
    when fewer than k rows qualify the unfilled slots are the same
    (+inf, -1) sentinels ops.topk_numpy pads with — never a pad row or a
    finite-looking id."""
    _run_in_child("""
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.sharded_search import sharded_topk, replicate
        from repro.kernels import ops
        mesh = make_host_mesh(data=8, model=1)
        rng = np.random.default_rng(5)
        base = rng.standard_normal((203, 16)).astype(np.float32)
        queries = rng.standard_normal((6, 16)).astype(np.float32)
        d, i = sharded_topk(mesh, replicate(mesh, jnp.asarray(queries)),
                            jnp.asarray(base), 10)
        rv, ri = ops.topk_numpy(queries, base, 10)
        np.testing.assert_allclose(np.asarray(d), rv, atol=1e-3, rtol=1e-4)
        assert np.asarray(i).max() < 203, "pad row won"
        # fewer than k qualifying rows -> sentinel padding, oracle-shaped
        mask = np.zeros(203, dtype=bool)
        mask[[3, 77, 202]] = True
        d, i = sharded_topk(mesh, replicate(mesh, jnp.asarray(queries)),
                            jnp.asarray(base), 10,
                            valid_mask=jnp.asarray(mask))
        d, i = np.asarray(d), np.asarray(i)
        rv, ri = ops.topk_numpy(queries, base[[3, 77, 202]], 10)
        assert (i[:, 3:] == -1).all() and np.isinf(d[:, 3:]).all()
        np.testing.assert_allclose(d[:, :3], rv[:, :3], atol=1e-3,
                                   rtol=1e-4)
        assert all(mask[x] for x in i.ravel() if x >= 0)
        print("non-divisible + sentinels ok")
    """)


def test_sharded_plan_descriptor_churn_exact():
    """Tentpole acceptance: the descriptor executor on a non-divisible N
    over 8 shards is bit-identical to the brute-force oracle mid-delta
    (inserts past the shard watermark) and post-compaction, rejects
    stale-generation plans, ships ZERO dense mask bytes on the warm path,
    runs ONE shard_map sweep per wave, and matches the legacy dense-mask
    parity oracle bit-for-bit."""
    _run_in_child("""
        from repro.core.vectormaton import VectorMaton, VectorMatonConfig
        from repro.core.predicate import parse_predicate
        from repro.distributed.sharded_search import sharded_plan_topk
        from repro.launch.mesh import make_host_mesh
        from repro.kernels import ops

        mesh = make_host_mesh(data=8, model=1)
        rng = np.random.default_rng(13)
        n, dim = 203, 16
        seqs = ["".join(rng.choice(list("abcd"),
                                   size=rng.integers(5, 14)))
                for _ in range(n)]
        vecs = rng.standard_normal((n, dim)).astype(np.float32)
        vm = VectorMaton(vecs, seqs,
                         VectorMatonConfig(T=10 ** 9, auto_compact=False))

        def brute(ptext, q, k, all_seqs, deleted):
            pred = parse_predicate(ptext)
            ids = np.asarray([j for j, s in enumerate(all_seqs)
                              if j not in deleted and pred.matches(s)],
                             dtype=np.int64)
            if not len(ids):
                return []
            dd = ((q[None, :] - vm.vectors[ids]) ** 2).sum(-1)
            return ids[np.argsort(dd, kind="stable")[:k]].tolist()

        # shard the PRE-churn table: watermark = 203, then churn past it
        # (every sharded call below passes the watermark, so the delta
        # inserts overflow to the host-merge path on all 8 shards)
        rt = vm.snapshot()
        rt.to_device_sharded(mesh, n=n)
        all_seqs = list(seqs)
        for j in range(9):
            s = "".join(rng.choice(list("abcd"), size=8))
            vm.insert(rng.standard_normal(dim).astype(np.float32), s)
            all_seqs.append(s)
        vm.delete(5)
        vm.delete(n + 2)            # one resident, one delta tombstone
        deleted = {5, n + 2}

        preds = ["a", "ab", "ab AND cd", "NOT ab", "LIKE '%a%b%'",
                 "a OR cd"]
        queries = rng.standard_normal((len(preds), dim)).astype(
            np.float32)
        rt = vm.snapshot()
        plan = vm.plan(preds, rt)
        t0 = dict(rt.traffic)
        res = sharded_plan_topk(mesh, n, rt, queries, plan, 5)
        for r, p in enumerate(preds):
            want = brute(p, queries[r], 5, all_seqs, deleted)
            assert res[r][1].tolist() == want, (p, res[r][1], want)
        assert rt.traffic["shard_mask_bytes"] == t0["shard_mask_bytes"], \
            "descriptor path uploaded a dense mask"

        # warm wave: cached tails, one sweep launch, zero mask bytes
        ops.reset_launch_stats()
        t1 = dict(rt.traffic)
        res2 = sharded_plan_topk(mesh, n, rt, queries, plan, 5)
        st = ops.launch_stats()
        # one shard_map sweep regardless of scan dtype (sq8 or fp32)
        assert (st.get("sharded_sweep", 0)
                + st.get("sq8_sharded_sweep", 0)) == 1, st
        assert rt.traffic["shard_tail_bytes"] == t1["shard_tail_bytes"]
        assert rt.traffic["shard_mask_bytes"] == t1["shard_mask_bytes"]

        # parity: legacy dense-mask path is bit-identical
        rt.shard_descriptors = False
        res3 = sharded_plan_topk(mesh, n, rt, queries, plan, 5)
        rt.shard_descriptors = True
        for (da, ia), (db, ib) in zip(res2, res3):
            assert np.array_equal(ia, ib)
            np.testing.assert_allclose(da, db, atol=1e-4)
        assert rt.traffic["shard_mask_bytes"] > 0   # the oracle DOES ship

        # post-compaction: fresh generation, fresh shard residency
        vm.compact()
        rt2 = vm.snapshot()
        plan2 = vm.plan(preds, rt2)
        res4 = sharded_plan_topk(mesh, None, rt2, queries, plan2, 5)
        for r, p in enumerate(preds):
            want = brute(p, queries[r], 5, all_seqs, deleted)
            assert res4[r][1].tolist() == want, (p, res4[r][1], want)

        # stale-generation rejection across the compaction swap
        try:
            sharded_plan_topk(mesh, None, rt2, queries, plan, 5)
            raise AssertionError("stale plan accepted")
        except ValueError as e:
            assert "generation" in str(e)
        print("sharded descriptor churn ok")
    """)


def test_sync_tombstones_delete_on_sharded_mesh():
    """A delete reaches the row-sharded tombstone bitmap through
    ``sync_tombstones`` — one eager scatter into an array sharded over
    the serving mesh — and the sweep's answers equal brute force over
    the live set."""
    _run_in_child("""
        from jax.sharding import PartitionSpec as P
        from repro.core.vectormaton import VectorMaton, VectorMatonConfig
        from repro.distributed.sharded_search import sharded_plan_topk
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(data=8, model=1)
        rng = np.random.default_rng(3)
        n, dim, preds = 300, 8, ["a", "ab"]
        seqs = ["".join(rng.choice(list("ab"), size=6)) for _ in range(n)]
        vecs = rng.standard_normal((n, dim)).astype(np.float32)
        vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9))
        rt = vm.snapshot()
        sh = rt.to_device_sharded(mesh)
        q = rng.standard_normal((len(preds), dim)).astype(np.float32)
        plan = vm.plan(preds, rt)
        before = sharded_plan_topk(mesh, None, rt, q, plan, 5)
        gone = {int(before[0][1][0]), int(before[1][1][1]), n - 1}
        for g in gone:
            vm.delete(g)
        sh.sync_tombstones(rt.deleted)
        assert set(np.nonzero(np.asarray(sh.deleted))[0].tolist()) == gone
        assert sh.deleted.sharding.spec == P("data")
        after = sharded_plan_topk(mesh, None, rt, q, plan, 5)
        for r, p in enumerate(preds):
            ids = np.asarray([j for j, s in enumerate(seqs)
                              if p in s and j not in gone])
            dd = ((q[r][None] - vecs[ids]) ** 2).sum(-1)
            want = ids[np.argsort(dd, kind="stable")[:5]].tolist()
            assert after[r][1].tolist() == want, (p, after[r][1], want)
        print("sync_tombstones ok")
    """)


def test_sharded_engine_matches_single_chip():
    """RetrievalEngine(mesh=...) routes waves through the sharded
    executor; answers match the single-chip engine exactly on a raw-only
    index."""
    _run_in_child("""
        from repro.core.vectormaton import VectorMatonConfig
        from repro.launch.mesh import make_host_mesh
        from repro.serve.engine import Request, RetrievalEngine
        mesh = make_host_mesh(data=8, model=1)
        rng = np.random.default_rng(21)
        n, dim = 150, 16
        seqs = ["".join(rng.choice(list("abcd"),
                                   size=rng.integers(5, 14)))
                for _ in range(n)]
        vecs = rng.standard_normal((n, dim)).astype(np.float32)
        sharded = RetrievalEngine(vecs, seqs,
                                  VectorMatonConfig(T=10 ** 9), mesh=mesh)
        plain = RetrievalEngine(vecs, seqs, VectorMatonConfig(T=10 ** 9))
        preds = ["a", "ab", "ab OR cd", "NOT ab", "ab", "a"]
        reqs = [Request(vector=rng.standard_normal(dim).astype(
                    np.float32), pattern=p, k=5) for p in preds]
        a = sharded.serve_batch(reqs)
        b = plain.serve_batch(reqs)
        for x, y in zip(a, b):
            assert x.ids.tolist() == y.ids.tolist(), (x.ids, y.ids)
        single = sharded.serve(reqs[0])
        assert single.ids.tolist() == a[0].ids.tolist()
        print("sharded engine ok")
    """)

