"""The corpus comes from the configuration's fixed key, the queries from
the seed."""
import numpy as np

import _benchpaths  # noqa: F401
from benchlib import data

RECIPE = {"generator": "random_walk", "key": 240409637, "shards": 2,
          "chunk": 50}


def test_corpus_is_fixed_by_the_recipe_key():
    a = data.make_corpus(RECIPE, 32, 200)
    b = data.make_corpus(RECIPE, 32, 200)
    assert [x.shape for x in a] == [(100, 32), (100, 32)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.allclose(a[0], a[1])
    other = data.make_corpus(dict(RECIPE, key=RECIPE["key"] + 1), 32, 200)
    assert not np.allclose(a[0], other[0])
    # z-normalised rows
    np.testing.assert_allclose(a[0].mean(axis=1), 0.0, atol=1e-5)
    np.testing.assert_allclose(a[0].std(axis=1), 1.0, atol=1e-4)


def test_queries_follow_the_seed():
    corpus = np.concatenate(data.make_corpus(RECIPE, 32, 200))
    big = 2**35 + 11
    q1 = data.make_queries(corpus, 20, 0.05, big)
    np.testing.assert_array_equal(q1, data.make_queries(corpus, 20, 0.05,
                                                        big))
    assert not np.allclose(q1, data.make_queries(corpus, 20, 0.05, 3))
    # each query lies near one corpus row and is none of them
    d2 = ((q1[:, None, :] - corpus[None]) ** 2).sum(-1)
    assert np.all(d2.min(axis=1) > 0.0)
    assert np.all(d2.min(axis=1) < 0.05 * 32 * 4)
