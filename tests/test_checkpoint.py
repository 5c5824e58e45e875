"""Checkpoint manager: atomic commit, async save, resume, retention."""

import os

import jax
import numpy as np

from repro.distributed.checkpoint import CheckpointManager


def _tree(step):
    return {"params": {"w": np.full((4, 4), float(step)),
                       "b": np.arange(3.0)},
            "opt": {"m": [np.ones(2) * step, np.zeros(1)]},
            "meta": {"step": np.asarray(step)}}


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _tree(5))
    out = mgr.restore(5)
    np.testing.assert_array_equal(out["params"]["w"], np.full((4, 4), 5.0))
    assert isinstance(out["opt"]["m"], list)
    np.testing.assert_array_equal(out["opt"]["m"][0], np.ones(2) * 5)


def test_resume_latest_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]       # retention
    assert mgr.latest_step() == 4
    out = mgr.restore()
    np.testing.assert_array_equal(out["params"]["w"], np.full((4, 4), 4.0))


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, _tree(7), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 7


def test_atomic_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(1))
    names = os.listdir(tmp_path)
    assert all(not n.endswith(".tmp") for n in names)


def test_reshard_on_load(tmp_path):
    """Restore with explicit shardings — the elastic-restart path."""
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": np.arange(8.0)})
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    out = mgr.restore(1, sharding_tree={"w": sh})
    assert out["w"].sharding == sh
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8.0))


def test_sharded_checkpoint_restore_onto_smaller_mesh():
    """VectorMaton checkpoint under a sharded mesh, restored onto a
    DIFFERENT mesh shape (8-way data-parallel -> 4-way via
    ElasticPlan.remesh over a shrunken device set): attribute schema,
    attributes, and the automaton's pseudo-states must round-trip, and
    hybrid predicate answers must stay oracle-exact post-restore —
    the reshard-on-rejoin path of the replication layer."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
        import jax
        import numpy as np
        from repro.core.predicate import parse_predicate
        from repro.core.vectormaton import VectorMatonConfig
        from repro.distributed.elastic import ElasticPlan
        from repro.launch.mesh import make_host_mesh
        from repro.serve.engine import RetrievalEngine

        rng = np.random.default_rng(5)
        n, dim = 257, 16
        genres = ["rock", "jazz", "pop"]
        seqs = ["".join(rng.choice(list("abcd"),
                                   size=rng.integers(5, 14)))
                for _ in range(n)]
        attrs = [{"genre": genres[int(rng.integers(0, 3))],
                  "price": float(np.round(rng.uniform(0, 20), 2))}
                 for _ in range(n)]
        vecs = rng.standard_normal((n, dim)).astype(np.float32)
        cfg = VectorMatonConfig(T=10 ** 9, auto_compact=False,
                                schema={"genre": "tag",
                                        "price": "numeric"})

        mesh8 = make_host_mesh(data=8, model=1)
        eng = RetrievalEngine(vecs, seqs, cfg, mesh=mesh8,
                              attributes=attrs)
        # churn: post-freeze inserts grow the automaton's pseudo-states
        for j in range(7):
            eng.insert(rng.standard_normal(dim).astype(np.float32),
                       "".join(rng.choice(list("abcd"), size=8)),
                       attributes={"genre": genres[j % 3],
                                   "price": float(j)})
        eng.delete(3)

        path = os.path.join(tempfile.mkdtemp(), "ckpt")
        eng.checkpoint(path, extra_meta={"lsn": 8})

        # the node comes back with 5 of its 8 devices: the elastic plan
        # keeps tp=1 and shrinks dp to the largest pow2 (4)
        mesh4 = ElasticPlan(tp_degree=1, old_data=8).remesh(
            jax.devices()[:5])
        assert mesh4.devices.shape == (4, 1)
        eng2 = RetrievalEngine.restore(path, mesh=mesh4)

        assert eng2.index.config.schema == cfg.schema
        assert eng2.index.attributes == eng.index.attributes
        from repro.distributed.checkpoint import load_checkpoint_meta
        assert load_checkpoint_meta(path)["lsn"] == 8

        def brute(vm, ptext, q, k):
            pred = parse_predicate(ptext)
            ids = [j for j in range(len(vm.sequences))
                   if j not in vm.deleted
                   and pred.matches(vm.sequences[j], vm.attributes[j])]
            if not ids:
                return []
            dd = ((q[None, :] - vm.vectors[ids]) ** 2).sum(-1)
            order = np.argsort(dd, kind="stable")[:k]
            return [ids[int(o)] for o in order]

        preds = ["genre = 'rock'",
                 "price >= 3 AND price <= 12",
                 "ab AND genre = 'jazz'",
                 "LIKE '%a%b%' AND price < 10",
                 "NOT genre = 'rock' AND a",
                 "genre = 'pop' OR cd"]
        queries = rng.standard_normal((len(preds), dim)).astype(
            np.float32)
        res = eng2.query_batch(queries, preds, 5)
        for r, p in enumerate(preds):
            want = brute(eng2.index, p, queries[r], 5)
            assert res[r][1].tolist() == want, (p, res[r][1].tolist(),
                                                want)
        # and the restored engine keeps absorbing writes on the new mesh
        eng2.insert(rng.standard_normal(dim).astype(np.float32), "abab",
                    attributes={"genre": "rock", "price": 1.0})
        res2 = eng2.query_batch(queries[:1], [preds[0]], 5)
        want2 = brute(eng2.index, preds[0], queries[0], 5)
        assert res2[0][1].tolist() == want2
        print("resharded restore OK")
    """)
    import os as _os
    repo_src = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "src")
    env = dict(_os.environ)
    env["PYTHONPATH"] = repo_src + _os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "resharded restore OK" in out.stdout
