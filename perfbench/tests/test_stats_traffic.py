import numpy as np
import pytest

from perfbench.harness import peaks, stats, traffic as T

from conftest import load_traffic


@pytest.mark.parametrize("vals,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05), (list(range(1, 101)), 99, 99.01),
    ([7], 99, 7.0), ([3, 1, 2], 0, 1.0), ([3, 1, 2], 100, 3.0)])
def test_percentile_matches_numpy(vals, q, want):
    assert stats.percentile(vals, q) == pytest.approx(want)
    assert stats.percentile(vals, q) == pytest.approx(np.percentile(vals, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 95) is None


def test_quartile_spread_is_the_contracts():
    import statistics
    vals = [100, 101, 99, 102, 98, 100.5]
    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx((q3 - q1) / q2)


def test_lengths_are_clipped_and_centred():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.8,
            "min": 32, "max": 1024}
    n = T.lengths(spec, 384)
    assert n.min() >= 32 and n.max() <= 1024 and len(n) == 384
    assert (n == 1024).sum() >= 1 and (n == 32).sum() >= 1   # both clips bite
    assert abs(np.median(n) - 192) <= 2


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 1])
def test_same_seed_same_requests(seed):
    tr = load_traffic("tiny_closed.json")
    a, b = T.request_stream(tr, 256, seed), T.request_stream(tr, 256, seed)
    for _ in range(40):                  # more than one pass over the pool
        (pa, ka), (pb, kb) = next(a), next(b)
        assert ka == kb and np.array_equal(pa, pb)
        assert pa.dtype == np.int32 and 0 <= pa.min() and pa.max() < 256


def test_every_seed_gets_the_same_lengths_in_another_order():
    tr = load_traffic("tiny_closed.json")

    def first_pass(seed):
        s = T.request_stream(tr, 256, seed)
        got = [next(s) for _ in range(tr["pool"])]
        return [len(p) for p, _ in got], [k for _, k in got]

    p1, o1 = first_pass(1)
    p2, o2 = first_pass(2)
    assert sorted(p1) == sorted(p2) and sorted(o1) == sorted(o2)
    assert p1 != p2


def test_no_two_prompts_share_a_prefix():
    s = T.request_stream(load_traffic("tiny_closed.json"), 256, 3)
    heads = [tuple(next(s)[0][:4]) for _ in range(32)]
    assert len(set(heads)) == len(heads)


@pytest.mark.parametrize("seed", [1, 2, 2 ** 33])
def test_arrivals(seed):
    tr = load_traffic("tiny_open.json")          # 6 rps, 2 every 1 s
    a = T.arrival_times(tr, 10.0, seed)
    assert a == sorted(a) and a[0] >= 0 and a[-1] < 10.0
    assert a == T.arrival_times(tr, 10.0, seed)
    assert abs(len(a) - 60) <= 3                 # the offered rate, bursts in
    for k in range(1, 10):                       # bursts are simultaneous
        assert sum(1 for t in a if t == float(k)) == 2
    other = T.arrival_times(tr, 10.0, seed + 1)
    assert other != a and abs(len(other) - len(a)) <= 1


def test_train_batches_rows_differ_and_repeat_by_seed():
    tr = load_traffic("tiny_train.json")
    a, b = T.train_batches(tr, 5), T.train_batches(tr, 5)
    x, y = next(a), next(b)
    assert np.array_equal(x, y) and x.shape == (2, 32) and x.dtype == np.int64
    assert x.max() < tr["corpus_ids"] and not np.array_equal(x[0], x[1])
    assert not np.array_equal(next(a), x)


def test_unknown_device_is_an_error_and_the_v5e_row_is_the_published_one():
    pk = peaks.peaks_for("TPU v5 lite")
    assert (pk.flops, pk.hbm_bw) == (197e12, 819e9) and "Google" in pk.source
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9")
