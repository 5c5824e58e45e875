"""An evaluator of the request predicate text, written apart from the
program's parser so that the reference shares nothing with it.

Grammar (the subset the traffic files use): a bare word or ``CONTAINS
'lit'`` is a substring test; ``LIKE 'pat'`` matches the whole sequence
with ``%`` for any run and ``_`` for one symbol; ``NOT`` binds tighter
than ``AND``, which binds tighter than ``OR``; parentheses group.
"""

from __future__ import annotations

import re

import numpy as np

_TOKEN = re.compile(r"\s*(?:(\()|(\))|'((?:[^']|'')*)'|([A-Za-z0-9_]+))")
_KEYWORDS = {"AND", "OR", "NOT", "LIKE", "CONTAINS"}


def _tokens(text: str) -> list:
    out, pos = [], 0
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m:
            raise ValueError(f"cannot read predicate {text!r} at {pos}")
        pos = m.end()
        if m.group(1) or m.group(2):
            out.append(("op", m.group(1) or m.group(2)))
        elif m.group(3) is not None:
            out.append(("lit", m.group(3).replace("''", "'")))
        elif m.group(4).upper() in _KEYWORDS:
            out.append(("kw", m.group(4).upper()))
        else:
            out.append(("lit", m.group(4)))
    return out


def _like(pattern: str):
    rx = "".join(".*" if c == "%" else "." if c == "_" else re.escape(c)
                 for c in pattern)
    return re.compile(rx, re.S)


def compile_text(text: str):
    """Predicate text -> a function of one sequence string."""
    toks = _tokens(text)
    pos = 0

    def peek():
        return toks[pos] if pos < len(toks) else (None, None)

    def take():
        nonlocal pos
        pos += 1
        return toks[pos - 1]

    # chained closures, not any()/all() over a generator: a per-row
    # corpus evaluates each predicate once per row
    def disj():
        fn = conj()
        while peek() == ("kw", "OR"):
            take()
            fn = (lambda a, b: lambda s: a(s) or b(s))(fn, conj())
        return fn

    def conj():
        fn = unary()
        while peek() == ("kw", "AND"):
            take()
            fn = (lambda a, b: lambda s: a(s) and b(s))(fn, unary())
        return fn

    def unary():
        kind, val = take()
        if (kind, val) == ("kw", "NOT"):
            inner = unary()
            return lambda s: not inner(s)
        if (kind, val) == ("op", "("):
            inner = disj()
            if take() != ("op", ")"):
                raise ValueError(f"unbalanced parentheses in {text!r}")
            return inner
        if (kind, val) == ("kw", "LIKE"):
            k2, lit = take()
            if k2 != "lit":
                raise ValueError(f"LIKE wants a literal in {text!r}")
            rx = _like(lit)
            return lambda s: rx.fullmatch(s) is not None
        if (kind, val) == ("kw", "CONTAINS"):
            kind, val = take()
        if kind != "lit":
            raise ValueError(f"unexpected {val!r} in {text!r}")
        return lambda s, lit=val: lit in s

    fn = disj()
    if pos != len(toks):
        raise ValueError(f"trailing tokens in {text!r}")
    return fn


def members(texts, sequences) -> dict:
    """``{text: sorted int64 ids of the rows whose sequence satisfies
    it}`` for each predicate text, over one sequence string per row.
    Each predicate is evaluated once per distinct sequence and mapped
    back to the rows, so a corpus of a few labels costs a few
    evaluations and a corpus of rows of their own one per row."""
    index: dict = {}
    codes = np.fromiter((index.setdefault(s, len(index)) for s in sequences),
                        np.int64, len(sequences))
    distinct = list(index)
    out = {}
    for text in texts:
        hit = np.fromiter(map(compile_text(text), distinct), bool,
                          len(distinct))
        out[text] = np.flatnonzero(hit[codes]).astype(np.int64, copy=False)
    return out
