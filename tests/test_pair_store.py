"""The accumulator's sorted pair store and the ``PairList`` it returns.

``ClusterAccumulator.pairs`` folds only the pairs evaluated since the
last read into frozen sorted columns; what it returns must equal the
list the verified-sim cache sorts to, whoever wrote the cache (``feed``
or ``merge_cluster_rounds`` through ``sim_cache``)."""
import numpy as np
import pytest

from repro.core.candidates import ShardedEdgeSource
from repro.core.engine import (
    ClusterAccumulator, PairList, merge_cluster_rounds,
)
from repro.core.verify import SignatureVerifier


def _reference(evaluated):
    return [(a, b, s) for (a, b), s in sorted(evaluated.items())]


def _assert_same(pairs, ref):
    got = list(pairs)
    assert got == ref
    assert all(type(a) is int and type(b) is int and type(s) is float
               for a, b, s in got)


@pytest.mark.parametrize("read_between", [True, False])
@pytest.mark.parametrize("batch", ["run", "band"])
def test_store_equals_sorted_cache_across_feeds_and_refine(batch,
                                                           read_between):
    rng = np.random.RandomState(11)
    n = 48
    # A few near-duplicate families so that some pairs clear the edge.
    base = rng.randint(0, 1000, size=(6, 100)).astype(np.uint32)
    sig = base[rng.randint(0, 6, size=n)]
    flip = rng.rand(n, 100) < 0.1
    sig = np.where(flip, rng.randint(0, 1000, size=(n, 100)),
                   sig).astype(np.uint32)
    verifier = SignatureVerifier(sig)
    acc = ClusterAccumulator(n, verifier, 0.75, 0.40, batch=batch)
    seen = []
    for _ in range(3):
        # Feeds walk ids in no particular order, so each feed's new pairs
        # interleave with the ones already sorted.
        edges = rng.randint(0, n, size=(40, 2)).astype(np.int64)
        edges = edges[edges[:, 0] != edges[:, 1]]
        acc.feed(ShardedEdgeSource(edges, num_docs=n))
        if read_between:
            ref = _reference(acc.evaluated)
            _assert_same(acc.pairs, ref)
            seen.append((acc.pairs, ref))
    before = len(acc.evaluated)
    merge_cluster_rounds(acc.uf, verifier, 0.75, roots=range(n),
                         sim_cache=acc.evaluated)
    assert len(acc.evaluated) > before  # refine wrote new keys
    _assert_same(acc.pairs, _reference(acc.evaluated))
    assert acc.n_sorted == len(acc.evaluated)
    # Lists returned earlier keep what they held.
    for pairs, ref in seen:
        _assert_same(pairs, ref)


def _pl(triples):
    ab = np.array([t[:2] for t in triples], np.int64).reshape(-1, 2)
    return PairList(ab, np.array([t[2] for t in triples], np.float64))


TRIPLES = [(0, 3, 0.5), (0, 7, 0.25), (2, 3, 0.875), (5, 9, 0.125)]
ONE_BIT = TRIPLES[:2] + [(2, 3, float(np.nextafter(0.875, 1.0)))] \
    + TRIPLES[3:]


@pytest.mark.parametrize("check", [
    lambda p: len(p) == 4 and len(PairList.empty()) == 0,
    lambda p: bool(p) and not PairList.empty(),
    lambda p: p[0] == (0, 3, 0.5) and p[-1] == (5, 9, 0.125),
    lambda p: isinstance(p[1:3], PairList) and p[1:3] == TRIPLES[1:3],
    lambda p: p[::-1] == TRIPLES[::-1],
    lambda p: p == TRIPLES and TRIPLES == p,
    lambda p: p == tuple(TRIPLES) and p != TRIPLES[:3],
    lambda p: p != ONE_BIT and not p == ONE_BIT,
    lambda p: p == _pl(TRIPLES) and p != _pl(ONE_BIT),
    lambda p: (2, 3, 0.875) in p and list(p) == TRIPLES,
], ids=["len", "bool", "index", "slice", "step_slice", "eq_list",
        "eq_tuple", "one_sim_bit", "eq_pairlist", "contains_iter"])
def test_pairlist_sequence_contract(check):
    assert check(_pl(TRIPLES))


def test_pairlist_index_out_of_range():
    with pytest.raises(IndexError):
        _pl(TRIPLES)[4]


def test_pairlist_merge_sorts_and_leaves_old_columns():
    old = _pl(TRIPLES)
    new = old.merged(np.array([[4, 1], [0, 5], [9, 9]], np.int64),
                     np.array([0.1, 0.2, 0.3]))
    assert new == sorted(TRIPLES + [(4, 1, 0.1), (0, 5, 0.2), (9, 9, 0.3)])
    assert old == TRIPLES
    for col in (old.ab, old.sim, new.ab, new.sim, new[1:].ab):
        assert not col.flags.writeable
        with pytest.raises(ValueError):
            col[0] = 0


def test_pairlist_rejects_ids_beyond_the_sort_key():
    with pytest.raises(ValueError):
        PairList.empty().merged(np.array([[0, 1 << 31]], np.int64),
                                np.array([0.5]))
