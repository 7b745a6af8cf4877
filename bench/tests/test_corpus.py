"""The block generator: the same seed gives the same rows, in any order."""

import numpy as np
import pytest

from harness.corpus import Corpus

PARAMS = {"n_clusters": 8, "cluster_spread": 2.0, "cluster_std": 0.35,
          "sigma": 0.25, "alpha": 0.2, "block_rows": 256}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_rows_and_pool(seed):
    a = Corpus(seed, 1024, 64, PARAMS)
    b = Corpus(seed, 1024, 64, PARAMS)
    # block 3 alone equals block 3 of the whole corpus, built the other way
    np.testing.assert_array_equal(np.asarray(a.block(3)),
                                  np.asarray(b.rows())[768:1024])
    qa, sa, oa = a.pool(40, 0.5)
    qb, sb, ob = b.pool(40, 0.5)
    np.testing.assert_array_equal(qa, qb)
    np.testing.assert_array_equal(sa, sb)
    np.testing.assert_array_equal(oa, ob)
    assert sorted(oa.tolist()) == list(range(40))


def test_other_seeds_other_rows():
    rows = [np.asarray(Corpus(s, 512, 32, PARAMS).block(0))
            for s in (1, 2, 2**31 + 1)]
    assert not np.array_equal(rows[0], rows[1])
    assert not np.array_equal(rows[0], rows[2])


def test_copies_lie_near_their_sources():
    c = Corpus(5, 1024, 64, PARAMS)
    q, src, _ = c.pool(40, 0.5)
    rows = np.asarray(c.rows())
    assert (src[20:] == -1).all() and (src[:20] >= 0).all()
    d = ((q[:20, None, :] - rows[None, :, :]) ** 2).sum(-1)
    assert (d.argmin(1) == src[:20]).all()


def test_block_rows_must_divide():
    with pytest.raises(ValueError):
        Corpus(0, 1000, 8, PARAMS)
