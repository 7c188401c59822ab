"""The arrival schedule and the batches are fixed by the seed, and every seed
offers the same load and the same batches, in another order."""
import numpy as np
import pytest

from chipbench import traffic


def test_schedule_is_deterministic_and_seeds_share_one_set_of_gaps():
    a = traffic.arrival_offsets(800.0, 20.0, 2**31 + 11)
    np.testing.assert_array_equal(a, traffic.arrival_offsets(800.0, 20.0, 2**31 + 11))
    c = traffic.arrival_offsets(800.0, 20.0, 5)
    assert len(a) == len(c) == 16000
    assert not np.array_equal(a, c)
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0) and a[-1] < 20.0
    np.testing.assert_array_equal(np.sort(traffic.gaps(800.0, 20.0, 2**31 + 11)),
                                  np.sort(traffic.gaps(800.0, 20.0, 5)))


def test_request_rows_are_blocks_in_the_seeds_order():
    np.testing.assert_array_equal(traffic.request_rows(2, 4, 10, np.array([1, 0])), [4, 5, 6, 7])
    np.testing.assert_array_equal(traffic.request_rows(1, 8, 10, np.array([0])), [0, 1, 2, 3, 4, 5, 6, 7])


@pytest.mark.parametrize("rows", [1, 128])
def test_every_seed_sends_the_same_batches(rows):
    def batches(seed):
        order = traffic.block_order(10_000, rows, seed)
        return [tuple(traffic.request_rows(i, rows, 10_000, order)) for i in range(len(order))]

    a, b = batches(2**31 + 3), batches(17)
    assert a != b and sorted(a) == sorted(b)
    assert all(len(set(x)) == rows for x in a)


@pytest.mark.parametrize("bad", [{"loop": "sideways"},
                                 {"loop": "closed", "clients": 1},
                                 {"loop": "closed", "clients": 1, "rows_per_request": 200,
                                  "buckets": [128], "sample_requests": 1, "why": "x"},
                                 {"loop": "open", "rate_per_s": 10, "rows_per_request": 1,
                                  "buckets": [1], "sample_requests": 1, "gap_seed": 0,
                                  "why": "x"}])
def test_malformed_mix_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)
