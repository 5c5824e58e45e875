"""The configurations' data, made from ``--seed``.

A configuration's rows and queries come from its corpus module,
``bench/corpora/<name>.py``, named by the configuration's ``"corpus"``
key (``source``).  Such a module defines

- ``rows(n, seed, cfg)``: float32 ``(n, cfg["dim"])`` vectors and a list
  of ``n`` sequence strings, one per row;
- ``queries(count, seed, cfg, stream)``: float32 ``(count, dim)`` query
  vectors, on a seed stream of their own per ``stream``.

A configuration without the key gets ``corpora/labels.py``: ACORN's
label rule over the generators here.

Vectors: a copy of the scale-corpus rule of ``repro.data.corpora``
(``stream_scale_vectors``), kept here so that a change to the program's
data module cannot move the yardstick: seeded clustered Gaussians (256
centres, sigma 0.5).  Labels: the rule ACORN (Patel et al., SIGMOD 2024,
arXiv:2403.04871) applies to SIFT1M and Paper, which carry no
attributes: each row one label drawn uniformly from a fixed set (12
values there), and each query an equality filter on one label drawn
uniformly.  A row's sequence is its label, a single letter, so a
CONTAINS of that letter is the equality filter.
"""

from __future__ import annotations

import numpy as np

from bench import load_module

DEFAULT = "labels"
_KNUTH = np.uint64(2654435761)
_PHI32 = np.uint64(0x9E3779B9)
_MASK32 = np.uint64(0xFFFFFFFF)
BLOCK = 8192
N_CENTRES = 256


def source(cfg: dict):
    """The configuration's corpus module."""
    return load_module("corpora", cfg.get("corpus", DEFAULT))


def rows(cfg: dict, n: int, seed: int):
    """The configuration's ``n`` rows from the seed: (vectors, sequences);
    a corpus module that gives other shapes raises ``ValueError``."""
    vecs, seqs = source(cfg).rows(n, seed, cfg)
    if (vecs.dtype != np.float32 or vecs.shape != (n, cfg["dim"])
            or len(seqs) != n):
        raise ValueError(f"corpus {cfg.get('corpus', DEFAULT)!r} gave "
                         f"{vecs.dtype} {vecs.shape} vectors and "
                         f"{len(seqs)} sequences for {n} x {cfg['dim']}")
    return vecs, seqs


def labels(n: int, seed: int, count: int) -> np.ndarray:
    """Per row, the index of its label, uniform over ``count`` values, on
    a seed stream apart from the vectors'; more than 256 values, which
    the codes' ``uint8`` cannot hold, raise ``ValueError``."""
    if not 0 < count <= 256:
        raise ValueError(f"{count} labels: a label code is one byte, "
                         "so 1 to 256 labels")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x1AB]))
    return rng.integers(0, count, n).astype(np.uint8)


def sequences(codes: np.ndarray, vocabulary) -> list:
    """Each row's sequence: its label's string."""
    return [vocabulary[c] for c in codes.tolist()]


def centres(dim: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC5]))
    return rng.standard_normal((N_CENTRES, dim)).astype(np.float32)


def vectors(n: int, dim: int, seed: int, normalize: bool) -> np.ndarray:
    """Row i lies at centre ``hash(i) mod 256`` plus N(0, 0.5^2) noise;
    block b of 8192 rows draws its noise from ``(seed, b)`` alone."""
    c = centres(dim, seed)
    out = np.empty((n, dim), np.float32)
    for start in range(0, n, BLOCK):
        stop = min(n, start + BLOCK)
        ids = np.arange(start, stop, dtype=np.uint64)
        assign = ((ids * _KNUTH + 7 * _PHI32) & _MASK32) % N_CENTRES
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, 1 + start // BLOCK]))
        noise = rng.standard_normal((stop - start, dim)).astype(np.float32)
        out[start:stop] = c[assign.astype(np.int64)] + 0.5 * noise
    if normalize:
        out /= np.linalg.norm(out, axis=1, keepdims=True)
    return out


def queries(count: int, dim: int, seed: int, normalize: bool,
            stream: int = 0) -> np.ndarray:
    """Fresh draws from the same generator, on a seed stream apart from
    the base rows (theirs are ``[seed, block]``): a random centre plus
    N(0, 0.5^2) noise."""
    c = centres(dim, seed)
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, stream, 0x51]))
    pick = rng.integers(0, N_CENTRES, count)
    q = c[pick] + 0.5 * rng.standard_normal((count, dim)).astype(np.float32)
    if normalize:
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    return q.astype(np.float32)
