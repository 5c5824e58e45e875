"""Property tests for the ESAM — the paper's Lemmas as executable claims."""

import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis "
                           "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.esam import (ESAM, naive_equivalence_classes,
                             naive_matching_ids)

seqs_strategy = st.lists(
    st.text(alphabet="abc", min_size=1, max_size=14),
    min_size=1, max_size=6)


@settings(max_examples=120, deadline=None)
@given(seqs_strategy)
def test_states_are_equivalence_classes(seqs):
    """ESAM states == poslist-equivalence classes (Definition 3), so the
    state count equals #classes + root (Lemma 1 exactness)."""
    a = ESAM()
    a.add_sequences(seqs)
    classes = naive_equivalence_classes(seqs)
    assert a.num_states == len(classes) + 1


@settings(max_examples=120, deadline=None)
@given(seqs_strategy, st.text(alphabet="abc", min_size=1, max_size=6))
def test_pattern_ids_exact(seqs, pattern):
    """V_p from the automaton == brute-force substring scan."""
    a = ESAM()
    a.add_sequences(seqs)
    got = np.sort(a.ids_for_pattern(pattern))
    want = naive_matching_ids(seqs, pattern)
    assert np.array_equal(got, want)


@settings(max_examples=80, deadline=None)
@given(seqs_strategy)
def test_linear_state_bound(seqs):
    """Lemma 1: states = O(m); classical SAM bound: <= 2m + 1."""
    a = ESAM()
    a.add_sequences(seqs)
    m = sum(len(s) for s in seqs)
    assert a.num_states <= 2 * m + 1


@settings(max_examples=80, deadline=None)
@given(seqs_strategy)
def test_transition_monotonicity(seqs):
    """§4.1: for any transition i->j, V_j ⊆ V_i (the DAG monotonicity that
    index reuse relies on)."""
    a = ESAM()
    a.add_sequences(seqs)
    a.finalize()
    for u in range(a.num_states):
        su = set(a.state_ids(u).tolist())
        for v in a.trans[u].values():
            assert set(a.state_ids(v).tolist()) <= su


@settings(max_examples=60, deadline=None)
@given(seqs_strategy)
def test_topological_order_valid(seqs):
    a = ESAM()
    a.add_sequences(seqs)
    # states by maxlen, as chain_layout orders them: topological, since
    # a transition appends a symbol to every pattern of its source
    order = np.argsort(np.asarray(a.maxlen), kind="stable")
    pos = {int(u): i for i, u in enumerate(order)}
    for u in range(a.num_states):
        for v in a.trans[u].values():
            assert pos[u] < pos[v]


@settings(max_examples=60, deadline=None)
@given(seqs_strategy)
def test_serialization_roundtrip(seqs):
    a = ESAM()
    a.add_sequences(seqs)
    a.finalize()
    b = ESAM.from_arrays(a.to_arrays())
    assert b.num_states == a.num_states
    for s in seqs:
        for i in range(len(s)):
            p = s[i:i + 3]
            assert np.array_equal(np.sort(a.ids_for_pattern(p)),
                                  np.sort(b.ids_for_pattern(p)))


def test_total_id_entries_bound():
    """Lemma 2: Σ|V| = O(m^1.5) — check the constant stays sane on a
    repetitive corpus (worst-ish case: many shared substrings)."""
    rng = np.random.default_rng(0)
    seqs = ["".join(rng.choice(list("ab"), size=40)) for _ in range(50)]
    a = ESAM()
    a.add_sequences(seqs)
    m = sum(len(s) for s in seqs)
    assert a.total_id_entries() <= 2 * m ** 1.5


def test_empty_pattern_is_unconstrained():
    a = ESAM()
    a.add_sequences(["abc", "bcd"])
    assert a.walk("") == 0
    assert set(a.ids_for_pattern("").tolist()) == {0, 1}
