"""Compile the served path's device programs for a TPU v5e chip that is
described, not attached (``jax.experimental.topologies``), at the widths
of ``chip_smoke.py`` phase A: a 1,048,576 × 128 fp32 table, 128 query
rows, k=10 and about 2M flat scan candidates.

What the chip's compiler refuses (a primitive Mosaic cannot lower, a
block not aligned to the tiling, too much VMEM, a program that does not
fit HBM) fails here with no chip.  Nothing runs, so nothing here checks
results: the interpret-mode and XLA-twin tests do that.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library at a time, and
under pytest-xdist every worker imports every test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.tuning import select_tiles

N = 1_048_576            # phase A table rows (SIFT1M shape)
D = 128
QP = 128                 # query rows of one 64-request wave, bucketed
K = 10
KP = 16                  # k rounded to 8, as the scan program runs it
KQ = 40                  # SQ8 over-fetch k·4, rounded to 8
N_DESC = 2 ** 21         # bucketed descriptor region of one wave
TR = TS = 1024           # resident / shipped delta tails after the writes
DP = 64                  # bucketed descriptor count
CSR = 4 * N              # resident CSR base_ids
KEY = (QP, N_DESC, TR, TS, DP, D)   # the packed batch's bucket key


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _desc_args(sh):
    """Shapes of the two buffers ``ops.pad_descriptor_batch`` packs a
    descriptor batch into: query and shipped rows, planning integers."""
    return (_sds((QP + TS, D), jnp.float32, sh),
            _sds((QP + 3 * DP + 2 * TR + 2 * TS,), jnp.int32, sh))


def _resident(sh):
    return (_sds((N, D), jnp.float32, sh), _sds((CSR,), jnp.int32, sh),
            _sds((N,), jnp.bool_, sh))


def test_descriptor_scan_pallas_compiles(one_chip):
    from repro.kernels.distance_topk import distance_topk_descriptors
    n_flat = N_DESC + TR + TS
    bq, bn = select_tiles(QP, n_flat, D, k=KP, divisor_n=n_flat)
    compiled = distance_topk_descriptors.lower(
        *_resident(one_chip), *_desc_args(one_chip), k=K, n_desc=N_DESC,
        packed=KEY, block_q=min(bq, QP), block_n=bn, interpret=False,
        impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [D, 100], ids=["sift", "glove"])
def test_descriptor_scan_inplace_pallas_compiles(one_chip, d):
    """The in-place form of a label wave (no tails, descriptors read
    from the table where they lie), at a width on and off the lanes."""
    from repro.kernels.distance_topk import distance_topk_descriptors
    n_desc, dp = 2 ** 18, 8
    bq, bn = select_tiles(QP, n_desc, d, k=KP, divisor_n=n_desc)
    compiled = distance_topk_descriptors.lower(
        _sds((N, d), jnp.float32, one_chip), _sds((N,), jnp.int32, one_chip),
        _sds((N,), jnp.bool_, one_chip),
        _sds((QP, d), jnp.float32, one_chip),
        _sds((QP + 3 * dp,), jnp.int32, one_chip), k=K, n_desc=n_desc,
        packed=(QP, n_desc, 0, 0, dp, d), block_q=min(bq, QP), block_n=bn,
        interpret=False, impl="pallas", inplace=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sq8_descriptor_scan_pallas_compiles(one_chip):
    from repro.kernels.quant import _sq8_topk_descriptors
    vecs, base_ids, deleted = _resident(one_chip)
    quant = (_sds((N, D), jnp.int8, one_chip),
             *(_sds((N, 1), jnp.float32, one_chip) for _ in range(3)))
    compiled = _sq8_topk_descriptors.lower(
        vecs, *quant, base_ids, deleted, *_desc_args(one_chip), K, KQ,
        packed=KEY, interpret=False, impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_filtered_beam_compiles(one_chip):
    from repro.core.hnsw_jax import hnsw_search_fused_filtered
    g, n_max, width, masks, pairs = 16, 8192, 32, 2, 64
    i32 = jnp.int32
    compiled = hnsw_search_fused_filtered.lower(
        _sds((N, D), jnp.float32, one_chip),
        _sds((g, n_max), i32, one_chip),
        _sds((g, n_max, width), i32, one_chip), _sds((g,), i32, one_chip),
        _sds((masks, N), jnp.bool_, one_chip),
        _sds((pairs,), i32, one_chip), _sds((pairs,), i32, one_chip),
        _sds((pairs, D), jnp.float32, one_chip), k=K, ef=64).compile()
    assert compiled.as_text()


def test_device_merge_compiles(one_chip):
    rows, width, reqs, parts = 512, 16, 64, 4
    compiled = ops.merge_topk_device.lower(
        _sds((rows, width), jnp.float32, one_chip),
        _sds((rows, width), jnp.int32, one_chip),
        _sds((reqs, parts), jnp.int32, one_chip),
        _sds((N,), jnp.bool_, one_chip), K).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fp32", "sq8"])
def test_sharded_sweep_compiles_on_four_chips(topo, quantized):
    """The sweep ``RetrievalEngine(mesh=...)`` launches, on a 4-chip
    ``data`` mesh: per-shard descriptor expansion, gathers, the dense
    segmented sweep (int8 + rerank + certificate when quantized) and the
    cross-shard all-gather top-k fold."""
    from repro.distributed.sharded_search import _sweep_fn, _sweep_fn_sq8
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shards, local_n = 4, N // 4
    n_desc, t_pad, l_pad = 2 ** 19, 256, 2 ** 20
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))
    i32 = jnp.int32
    head = (_sds((QP, D), jnp.float32, rep), _sds((QP,), i32, rep),
            _sds((shards, DP), i32, rows), _sds((shards, DP), i32, rows),
            _sds((DP,), i32, rep), _sds((shards, t_pad), i32, rows),
            _sds((t_pad,), i32, rep))
    tail = (_sds((N, D), jnp.float32, rows),
            _sds((N,), jnp.bool_, NamedSharding(mesh, P("data"))),
            _sds((shards, l_pad), i32, rows))
    if quantized:
        quant = (_sds((N, D), jnp.int8, rows),
                 *(_sds((N, 1), jnp.float32, rows) for _ in range(3)))
        fn = _sweep_fn_sq8(mesh, "data", n_desc, K, KQ, "l2", local_n)
        args = head + quant + tail
    else:
        fn = _sweep_fn(mesh, "data", n_desc, K, "l2", local_n)
        args = head + tail
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" in text
