"""Data pipeline: corpus generation."""
from repro.data import inject_near_duplicates, make_i2b2_like


def test_corpus_shape():
    notes = make_i2b2_like(100, seed=0)
    assert len(notes) == 100
    lens = [len(n.split()) for n in notes]
    assert min(lens) > 50   # "a few hundred words" (paper §7.1)
    assert len(set(notes)) == 100


def test_injection_provenance():
    notes = make_i2b2_like(50, seed=1)
    out, prov = inject_near_duplicates(notes, 20, seed=2)
    assert len(out) == 70 and len(prov) == 20
    for dup_idx, src_idx, frac in prov:
        a, b = out[dup_idx].split(), out[src_idx].split()
        same = sum(x == y for x, y in zip(a, b)) / max(len(a), 1)
        assert same >= 1 - frac - 0.02
