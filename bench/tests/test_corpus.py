"""The benchmark's corpus generator writes what the program's does."""

import pytest

from bench import corpus
from repro.data import synthetic


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_write_corpus_bytes_match_program(tmp_path, seed):
    ours = corpus.write_corpus(tmp_path / "bench", 150_000, n_files=3, seed=seed)
    theirs = synthetic.write_corpus(tmp_path / "repro", 150_000, n_files=3, seed=seed)
    assert [p.name for p in ours] == [p.name for p in theirs]
    for a, b in zip(ours, theirs):
        assert a.read_bytes() == b.read_bytes()


def test_abstract_texts_are_distinct_and_seeded():
    counts = [60, 61, 220, 60, 100]
    a = corpus.abstract_texts(5, counts)
    assert a == corpus.abstract_texts(5, counts)
    assert len(set(a)) == len(a)
    assert a != corpus.abstract_texts(6, counts)
