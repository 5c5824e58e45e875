"""Kernel tuning policy + compiled-vs-interpret parity (DESIGN.md §6).

Three claims are locked down here:

  * every kernel entry point gives the same answer through the Pallas
    interpret path and the XLA-compiled jnp twin (``REPRO_IMPL``), so
    switching the executor default off-TPU cannot change results;
  * bf16 accumulation trades a bounded relative error for bandwidth —
    the bound is asserted, not assumed;
  * the SQ8 default is *exact*: rerank + certificate + escalation makes
    its top-k equal the fp32 scan's bit-for-bit on ids, including under
    the adaptive streak fallback and the unsupported-shape fallback.

Plus units for the shared tile-selection rule and the env overrides, and
determinism/selectivity checks for the real-scale corpus generator.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vectormaton import VectorMaton, VectorMatonConfig
from repro.data import corpora
from repro.kernels import ops, tuning
from repro.kernels.quant import SQ8_MAX_K, topk_sq8_rerank
from repro.kernels.tuning import (MAX_BLOCK_N, MAX_BLOCK_Q, VMEM_BUDGET,
                                  _working_set, select_tiles)

ON_TPU = jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
# tile selection units
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("q,n,d,itemsize,k", [
    (8, 100, 16, 4, 8), (256, 4096, 128, 4, 16), (64, 100000, 768, 4, 64),
    (512, 65536, 128, 1, 128), (32, 2048, 4096, 4, 8),
    (128, 8192, 256, 2, 16),
])
def test_select_tiles_invariants(q, n, d, itemsize, k):
    bq, bn = select_tiles(q, n, d, itemsize=itemsize, k=k)
    assert bq % 128 == 0 and bn % 128 == 0
    assert 128 <= bq <= MAX_BLOCK_Q and 128 <= bn <= MAX_BLOCK_N
    assert _working_set(bq, bn, d, itemsize, k) <= VMEM_BUDGET


def test_select_tiles_scales_with_operand_size():
    """Bigger dim / itemsize -> smaller candidate tile; int8 buys room."""
    _, bn_small = select_tiles(128, 100000, 64, itemsize=4, k=16)
    _, bn_big = select_tiles(128, 100000, 2048, itemsize=4, k=16)
    assert bn_big < bn_small
    _, bn_huge = select_tiles(128, 100000, 8192, itemsize=4, k=16)
    assert bn_huge == 128                     # budget pins the floor
    _, bn_i8 = select_tiles(128, 100000, 2048, itemsize=1, k=16)
    assert bn_i8 > bn_big                     # int8 tiles are 4x cheaper


def test_select_tiles_never_overgrows_the_problem():
    """A tile past N (or Q) buys nothing: tiny problems keep (128, 128)."""
    assert select_tiles(4, 100, 32) == (128, 128)
    bq, _ = select_tiles(4, 100000, 32, k=8)
    assert bq == 128                          # q=4 never grows block_q


def test_select_tiles_divisor_constraint():
    """Fixed padded extents (descriptor layout) force block_n to divide."""
    _, bn = select_tiles(128, 384, 16, k=8, divisor_n=384)
    assert 384 % bn == 0 and bn == 128        # 256 does not divide 384
    _, bn2 = select_tiles(128, 1024, 16, k=8, divisor_n=1024)
    assert 1024 % bn2 == 0 and bn2 > 128      # room to grow when it divides


# --------------------------------------------------------------------- #
# env-override policy
# --------------------------------------------------------------------- #

def test_default_interpret_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    if not ON_TPU:
        assert tuning.default_interpret() is True
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        assert tuning.default_interpret() is True
        monkeypatch.setenv("REPRO_INTERPRET", "false")
        assert tuning.default_interpret() is False
    # on a TPU the kernels always compile; asking for the interpreter
    # there is an error, not a silent slow path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert tuning.default_interpret() is False
    monkeypatch.delenv("REPRO_INTERPRET")
    assert tuning.default_interpret() is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret mode on a TPU"):
        tuning.default_interpret()


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    """Entry points keep JAX's compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed, git-ignored
    ``<checkout>/.jax_cache``."""
    from repro.launch.compile_cache import CHECKOUT, place_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert (CHECKOUT / "chip_smoke.py").is_file()
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()


def test_default_impl_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_IMPL", "pallas")
    assert tuning.default_impl() == "pallas"
    monkeypatch.setenv("REPRO_IMPL", "xla")
    assert tuning.default_impl() == "xla"
    monkeypatch.setenv("REPRO_IMPL", "garbage")   # unknown -> autodetect
    monkeypatch.delenv("REPRO_IMPL", raising=False)
    if not ON_TPU:
        assert tuning.default_impl() == "xla"     # compiled path off-TPU


# --------------------------------------------------------------------- #
# compiled (XLA) vs Pallas-interpret parity, per entry point
# --------------------------------------------------------------------- #

def _data(q, n, d, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((q, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((n, d)), jnp.float32))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_topk_parity_pallas_vs_xla(metric):
    x, y = _data(6, 96, 24)
    v_p, i_p = ops.topk(x, y, 5, metric=metric, interpret=True)
    v_x, i_x = ops.topk_xla(x, y, 5, metric=metric)
    assert np.array_equal(np.asarray(i_p), np.asarray(i_x))
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_x),
                               atol=2e-4, rtol=1e-4)


def test_topk_segmented_parity_pallas_vs_xla():
    """Same ids AND same (+inf, -1) padding semantics for unmatched /
    undersized / empty segments through both tops."""
    x, y = _data(6, 96, 16, seed=1)
    qseg = jnp.asarray([0, 1, 2, 0, -1, 3], jnp.int32)   # seg 3 is empty
    cseg = jnp.asarray(np.random.default_rng(2).integers(0, 3, 96),
                       jnp.int32)
    v_p, i_p = ops.topk_segmented(x, y, qseg, cseg, 4, interpret=True)
    v_x, i_x = ops.topk_segmented_xla(x, y, qseg, cseg, 4)
    assert np.array_equal(np.asarray(i_p), np.asarray(i_x))
    fin = np.isfinite(np.asarray(v_p))
    assert np.array_equal(fin, np.isfinite(np.asarray(v_x)))
    np.testing.assert_allclose(np.asarray(v_p)[fin], np.asarray(v_x)[fin],
                               atol=2e-4, rtol=1e-4)
    assert np.all(np.asarray(i_p)[4] == -1)              # qseg -1 row


DIM = 16
PREDS = ["a", "ab", "abc", "ba", "a OR cd", "dd", "a AND NOT b"]


@pytest.fixture(scope="module")
def small_corpus():
    rng = np.random.default_rng(7)
    n = 230
    seqs = ["".join(rng.choice(list("abcd"), size=rng.integers(5, 15)))
            for _ in range(n)]
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    return vecs, seqs


def _run_executor(small_corpus, monkeypatch, impl, **cfg):
    vecs, seqs = small_corpus
    monkeypatch.setenv("REPRO_IMPL", impl)
    vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9, backend="jax",
                                                   **cfg))
    q = np.random.default_rng(3).standard_normal(
        (len(PREDS), DIM)).astype(np.float32)
    return vm.query_batch(q, PREDS, 6)


def test_descriptor_executor_parity_pallas_vs_xla(small_corpus, monkeypatch):
    """The full device executor (descriptor scans + beams + merge) returns
    identical ids under impl=pallas(interpret) and impl=xla."""
    res_p = _run_executor(small_corpus, monkeypatch, "pallas")
    res_x = _run_executor(small_corpus, monkeypatch, "xla")
    for r, ((dp, ip), (dx, ix)) in enumerate(zip(res_p, res_x)):
        assert np.array_equal(ip, ix), (PREDS[r], ip, ix)
        np.testing.assert_allclose(dp, dx, atol=2e-4, rtol=1e-4)


def test_sq8_executor_parity_pallas_vs_xla(small_corpus, monkeypatch):
    """The SQ8 default (quantized scan + rerank + certificate) is also
    impl-agnostic end to end."""
    res_p = _run_executor(small_corpus, monkeypatch, "pallas",
                          quantize="sq8")
    res_x = _run_executor(small_corpus, monkeypatch, "xla", quantize="sq8")
    for r, ((dp, ip), (dx, ix)) in enumerate(zip(res_p, res_x)):
        assert np.array_equal(ip, ix), (PREDS[r], ip, ix)
        np.testing.assert_allclose(dp, dx, atol=2e-4, rtol=1e-4)


# --------------------------------------------------------------------- #
# bf16 accumulation: bounded relative error, not bounded hope
# --------------------------------------------------------------------- #

def test_bf16_pairwise_tolerance():
    x, y = _data(8, 256, 128, seed=4)
    d32 = np.asarray(ops.pairwise_sqdist(x, y, interpret=True))
    d16 = np.asarray(ops.pairwise_sqdist(x, y, interpret=True,
                                         accum="bf16"))
    # bf16 keeps ~8 mantissa bits: relative error stays within ~2%
    rel = np.abs(d16 - d32) / np.maximum(np.abs(d32), 1.0)
    assert float(rel.max()) < 2e-2, float(rel.max())


def test_bf16_topk_overlap():
    x, y = _data(8, 512, 128, seed=5)
    _, i32 = ops.topk(x, y, 10, interpret=True)
    _, i16 = ops.topk(x, y, 10, interpret=True, accum="bf16")
    overlap = np.mean([len(set(np.asarray(i32)[r].tolist())
                           & set(np.asarray(i16)[r].tolist())) / 10
                       for r in range(8)])
    assert overlap >= 0.8, overlap


# --------------------------------------------------------------------- #
# SQ8 exactness at the rerank tail
# --------------------------------------------------------------------- #

def test_sq8_rerank_equals_fp32_topk():
    """With an overfetch pool comfortably larger than k, the rerank tail
    returns the fp32 top-k exactly: same ids, and distances that ARE the
    fp32 distances (recomputed in numpy) — quantization never leaks into
    the returned values."""
    rng = np.random.default_rng(6)
    n, d, k = 300, 32, 4
    y = rng.standard_normal((n, d)).astype(np.float32)
    x = y[:6] + 0.05 * rng.standard_normal((6, d)).astype(np.float32)
    v, i = topk_sq8_rerank(jnp.asarray(x), jnp.asarray(y), k, overfetch=16)
    rv, ri = ops.topk_numpy(x, y, k)
    assert np.array_equal(np.asarray(i), ri)
    for r in range(6):
        for c in range(k):
            diff = x[r] - y[np.asarray(i)[r, c]]
            assert abs(float(diff @ diff) - float(np.asarray(v)[r, c])) \
                < 1e-4


def test_sq8_default_executor_exact(small_corpus, monkeypatch):
    """Acceptance: quantize='sq8' as the DEFAULT scan returns ids equal to
    the fp32 executor on every request (certificate or escalation, never
    silent approximation)."""
    res_q8 = _run_executor(small_corpus, monkeypatch, "xla",
                           quantize="sq8")
    res_fp = _run_executor(small_corpus, monkeypatch, "xla")
    for r, ((dq, iq), (df, if_)) in enumerate(zip(res_q8, res_fp)):
        assert np.array_equal(iq, if_), (PREDS[r], iq, if_)
        np.testing.assert_allclose(dq, df, atol=2e-4, rtol=1e-4)


def test_sq8_unsupported_k_falls_back_warn_once(small_corpus):
    """k > SQ8_MAX_K is outside the quantized scan's overfetch budget:
    the executor warns ONCE, counts a fallback, and the fp32 path keeps
    the answer exact."""
    vecs, seqs = small_corpus
    k = SQ8_MAX_K + 1
    vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9, backend="jax",
                                                   quantize="sq8"))
    vm_fp = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9,
                                                      backend="jax"))
    q = np.random.default_rng(8).standard_normal((2, DIM)).astype(
        np.float32)
    with pytest.warns(RuntimeWarning, match="sq8"):
        res = vm.query_batch(q, ["a", "b"], k)
    assert vm.runtime.sq8_stats["fallbacks"] >= 1
    res_fp = vm_fp.query_batch(q, ["a", "b"], k)
    for (dq, iq), (df, if_) in zip(res, res_fp):
        assert np.array_equal(iq, if_)
    with warnings.catch_warnings():            # second batch: silent
        warnings.simplefilter("error")
        vm.query_batch(q, ["a", "b"], k)


def test_sq8_adaptive_streak_flips_to_fp32(small_corpus):
    """Near-duplicate vectors make the worst-case certificate hopeless:
    every batch escalates, and after SQ8_MAX_STREAK consecutive
    escalations of its launch shape the runtime stops paying for the
    quantized scan there and runs fp32 directly (counted as fallbacks) —
    still exact throughout."""
    rng = np.random.default_rng(9)
    _, seqs = small_corpus
    n = len(seqs)
    base = 10.0 * rng.standard_normal(DIM).astype(np.float32)
    vecs = base + 1e-4 * rng.standard_normal((n, DIM)).astype(np.float32)
    vm = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9, backend="jax",
                                                   quantize="sq8"))
    vm_fp = VectorMaton(vecs, seqs, VectorMatonConfig(T=10 ** 9,
                                                      backend="jax"))
    rt = vm.runtime
    res = res_fp = None
    for b in range(rt.SQ8_MAX_STREAK + 2):
        q = rng.standard_normal((1, DIM)).astype(np.float32)
        res = vm.query_batch(q, ["a"], 6)
        res_fp = vm_fp.query_batch(q, ["a"], 6)
        assert np.array_equal(res[0][1], res_fp[0][1]), b
    assert rt.sq8_stats["escalations"] == rt.SQ8_MAX_STREAK
    assert rt.sq8_stats["fallbacks"] >= 2      # post-streak batches
    assert max(rt._sq8_bad_streak.values()) >= rt.SQ8_MAX_STREAK
    # and the approximate operating point skips the certificate entirely
    rt.sq8_escalate = False
    rt._sq8_bad_streak.clear()
    before = dict(rt.sq8_stats)
    vm.query_batch(rng.standard_normal((1, DIM)).astype(np.float32),
                   ["a"], 6)
    assert rt.sq8_stats["escalations"] == before["escalations"]
    assert rt.sq8_stats["certified"] == before["certified"]


# --------------------------------------------------------------------- #
# real-scale corpus generator
# --------------------------------------------------------------------- #

def test_scale_corpus_streaming_matches_materialized():
    n, dim = 3 * corpora.SCALE_BLOCK // 2, 32   # spans a partial block
    vecs, seqs = corpora.make_scale_corpus(n, dim, seed=11)
    assert vecs.shape == (n, dim) and len(seqs) == n
    streamed = np.concatenate(
        [blk for _, blk in corpora.stream_scale_vectors(n, dim, seed=11)])
    assert np.array_equal(streamed, vecs)
    vecs2, seqs2 = corpora.make_scale_corpus(n, dim, seed=11)
    assert np.array_equal(vecs2, vecs) and seqs2 == seqs
    vecs3, _ = corpora.make_scale_corpus(n, dim, seed=12)
    assert not np.array_equal(vecs3, vecs)      # seed actually matters


def test_scale_corpus_selectivities():
    """Tag membership hits its design selectivities, including the joint
    patterns — the avalanche mix must decorrelate tags (a plain Knuth
    hash gave pattern 'bc' selectivity 0)."""
    n = 16384
    _, seqs = corpora.make_scale_corpus(n, 8, seed=0)
    frac = {p: sum(p in s for s in seqs) / n for p in ("a", "b", "bc")}
    assert abs(frac["a"] - 0.50) < 0.02
    assert abs(frac["b"] - 0.25) < 0.02
    assert 0.01 < frac["bc"] < 0.05             # ~= 0.25 * 0.10
    # every sequence ends with the terminal sentinel
    assert all(s.endswith("z") for s in seqs)
