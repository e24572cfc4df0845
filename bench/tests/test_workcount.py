import pytest

from bench import workcount


def test_lu_counts_follow_the_shape():
    assert workcount.lu_flops(1, 3) == pytest.approx(18.0)
    assert workcount.lu_flops(32, 1024) == pytest.approx(32 * 2 * 1024**3 / 3)
    assert workcount.lu_bytes(2, 1024) == 2 * 3 * 1024 * 1024 * 4
    assert workcount.lu_bytes(1, 8, itemsize=8) == 3 * 64 * 8


@pytest.mark.parametrize("batch,n,bound", [(1, 64, "bytes"), (32, 1024, "bytes"),
                                           (1, 8192, "flops")])
def test_least_time_names_its_bound(batch, n, bound):
    peak = workcount.peaks("TPU v5 lite")
    t, which = workcount.lu_least_seconds(batch, n, peak)
    assert which == bound
    assert t == pytest.approx(max(workcount.lu_flops(batch, n) / 197e12,
                                  workcount.lu_bytes(batch, n) / 819e9))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        workcount.peaks("cpu")
