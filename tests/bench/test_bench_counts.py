"""Bytes and compares of one propose call, against values worked by hand."""

import pytest

from perfbench.lib import counts


def test_host_pool_counts_by_hand():
    # N=1024 candidates x D=60 knobs of float64 = 491520 B; 7000 nodes x 36 B
    # = 252000 B; 1024 aggregates x 8 B = 8192 B; 64 picks x 12 B = 768 B
    c = counts.propose_counts(N=1024, D=60, S=12, T=10, nodes=7000, depth=12,
                              k=64)
    assert c["bytes"] == 491520 + 252000 + 8192 + 768
    assert c["compares"] == 1024 * 12 * 10 * 12


def test_least_time_is_bound_by_bytes_on_v5e():
    peak = counts.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9 and "TPU v5e" in peak["source"]
    c = counts.propose_counts(N=131072, D=60, S=12, T=10, nodes=7000, depth=12,
                              k=64)
    least = counts.least_seconds(c, peak)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(c["bytes"] / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
