"""Shared kernel-tuning policy: tile-size selection + interpret mode.

Every kernel wrapper (``pairwise.py``, ``distance_topk.py``, ``quant.py``
via ``ops.py``) draws its ``(block_q, block_n)`` tile shape and its
``interpret`` default from this module, so the whole kernel layer agrees
on one policy instead of three hardcoded ones (DESIGN.md §6).

Tile selection (``select_tiles``): start from the hardware-aligned
minimum (128, 128) — the MXU consumes 128×128 operands and 128 is the
f32/bf16/int8 lane multiple — and grow the streamed candidate axis
first (fewer grid steps over N, better MXU utilisation per step), then
the query axis, while the per-step working set

    block_q·d·itemsize  (query tile)
  + block_n·d·itemsize  (candidate tile)
  + block_q·block_n·4   (distance tile, f32)
  + block_q·(block_n + 2k)·8  (top-k fold: tile copy, positions, carry)

fits half the ~16 MiB per-core VMEM — the other half is headroom for
the pipeline's double buffering.  Growth never exceeds what the logical
problem needs (a tile past N buys nothing) and, for callers whose
padded layout is fixed (the descriptor path concatenates pre-bucketed
regions), never violates divisibility of the padded extent.

Interpret mode (``default_interpret``): Pallas compiles only on TPU; on
CPU the kernels run in interpret mode as the validation path, and the
XLA-compiled jnp twins (``ops.topk_xla`` etc.) are the throughput path.
Off the TPU ``REPRO_INTERPRET=1|0`` overrides the autodetect either way;
on a TPU the kernels always compile, and asking for interpret mode there
is an error.
"""

from __future__ import annotations

import os
from typing import Tuple

_LANE = 128
VMEM_BUDGET = 8 * 1024 * 1024          # bytes: half of ~16 MiB/core VMEM
MAX_BLOCK_Q = 256
MAX_BLOCK_N = 1024
# SQ8 eligibility: int8 candidate tiles + the (Q, k·overfetch, d) fp32
# rerank gather stay inside the budget up to this dim; past it the
# executor falls back to the fp32 scan path (see quant.sq8_supported).
SQ8_DIM_CAP = 4096

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def default_interpret() -> bool:
    """One interpret-mode policy for every kernel entry point.

    On a TPU, where Pallas lowers natively, False — and ``REPRO_INTERPRET``
    set to a true value raises instead of quietly running the kernels in
    the interpreter on the chip.  Elsewhere the env override wins
    (``1``/``true`` forces interpret, ``0``/``false`` forces compiled),
    and interpret mode is the default.
    """
    import jax
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if jax.default_backend() == "tpu":
        if env in _TRUE:
            raise RuntimeError(
                f"REPRO_INTERPRET={env!r} asks for Pallas interpret mode on "
                "a TPU, where the kernels compile; unset it")
        return False
    if env in _FALSE:
        return False
    return True


def default_impl() -> str:
    """Which top-k core the executor launches: the Pallas kernels
    (``"pallas"`` — native on TPU, interpret-mode validation elsewhere)
    or their XLA-compiled jnp twins (``"xla"`` — the compiled throughput
    path off-TPU).  ``REPRO_IMPL=pallas|xla`` overrides the autodetect;
    assembly, gathers, and gid mapping are shared between the two, so
    they differ only in the top-k schedule."""
    env = os.environ.get("REPRO_IMPL", "").strip().lower()
    if env in ("pallas", "xla"):
        return env
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _working_set(bq: int, bn: int, d: int, itemsize: int, k: int) -> int:
    return ((bq + bn) * d * itemsize      # operand tiles
            + bq * bn * 4                 # distance tile (f32)
            + bq * (bn + 2 * max(k, 1)) * 8)   # top-k fold working set


def select_tiles(q: int, n: int, d: int, *, itemsize: int = 4, k: int = 0,
                 divisor_n: int | None = None) -> Tuple[int, int]:
    """Pick ``(block_q, block_n)`` for a (Q, d) × (N, d) kernel.

    ``itemsize``: bytes per operand element (4 f32, 2 bf16, 1 int8);
    ``k``: top-k scratch width (0 for pairwise); ``divisor_n``: when the
    caller's padded N extent is fixed (descriptor region layout),
    ``block_n`` must divide it — growth stops at the largest power-of-two
    multiple of 128 that does.  Callers without that constraint pad N up
    to the returned ``block_n`` multiple afterwards.
    """
    d = max(int(d), 1)
    bq, bn = _LANE, _LANE

    def n_ok(c: int) -> bool:
        if c > MAX_BLOCK_N or not _working_set(bq, c, d, itemsize,
                                               k) <= VMEM_BUDGET:
            return False
        if divisor_n is not None:
            return divisor_n % c == 0
        return bn < n                      # a tile past N buys nothing

    while bn * 2 <= MAX_BLOCK_N and n_ok(bn * 2):
        bn *= 2
    while (bq * 2 <= MAX_BLOCK_Q and bq < q
           and _working_set(bq * 2, bn, d, itemsize, k) <= VMEM_BUDGET):
        bq *= 2
    return bq, bn


__all__ = ["default_interpret", "default_impl", "select_tiles",
           "VMEM_BUDGET",
           "MAX_BLOCK_Q", "MAX_BLOCK_N", "SQ8_DIM_CAP"]
