import numpy as np

import gen


def test_same_seed_same_schedule_and_rows():
    big = 2**31 + 977
    a, b = gen.arrivals(big, 500, 3.0), gen.arrivals(big, 500, 3.0)
    assert np.array_equal(a, b)
    base = gen.corpus(gen.sub_key(big, gen.CORPUS), n=1024, d=8,
                      n_clusters=16, metric="angular")
    base2 = gen.corpus(gen.sub_key(big, gen.CORPUS), n=1024, d=8,
                       n_clusters=16, metric="angular")
    assert np.array_equal(np.asarray(base), np.asarray(base2))
    q1 = gen.query_rows(gen.sub_key(big, gen.QUERIES), base, n=64,
                        metric="angular")
    q2 = gen.query_rows(gen.sub_key(big, gen.QUERIES), base, n=64,
                        metric="angular")
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(q1), axis=1), 1,
                               rtol=1e-5)


def test_seeds_share_gaps_in_another_order():
    a, b = gen.arrivals(1, 400, 5.0), gen.arrivals(2**33 + 1, 400, 5.0)
    assert len(a) == len(b) == 2000
    assert not np.array_equal(a, b)
    # the last gap runs to the window's end
    ga, gb = np.diff(np.append(a, 5.0)), np.diff(np.append(b, 5.0))
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=0, atol=1e-9)
    assert a[0] == 0 and a[-1] < 5.0 and np.all(np.diff(a) > 0)


def test_high_seed_bits_matter():
    k1 = np.asarray(gen.root_key(5))
    k2 = np.asarray(gen.root_key(5 + 2**32))
    assert not np.array_equal(k1, k2)


def test_weights_and_sample():
    w = gen.scorer_weights(gen.sub_key(3, gen.WEIGHTS), R=2, d=8, H=16, B=32)
    assert w["w1"].shape == (2, 8, 16) and w["w2"].shape == (2, 16, 32)
    s = gen.sample(3, 100, 10)
    assert len(set(s)) == 10 and np.array_equal(s, gen.sample(3, 100, 10))
