"""The work counts against values worked out by hand, and the peaks."""

import pytest

from harness import work


def test_hist_pass_level0_by_hand():
    # 1,000 rows, 28 features, 256 bins: a byte a code, g and h in, node out
    w = work.hist_pass(1000, 28, 256, 0)
    assert w["adds"] == 2 * 1000 * 28 == 56000
    assert w["bytes"] == 1000 * (28 + 4 + 4 + 4) + 1 * 28 * 256 * 2 * 4 == 97344


def test_hist_pass_deeper_reads_the_node_id_too():
    w = work.hist_pass(1000, 28, 256, 3)
    assert w["bytes"] == 1000 * (28 + 4 + 4 + 4 + 4) + 8 * 28 * 256 * 8 == 502752


def test_wide_bins_take_two_bytes_a_code():
    assert work.hist_pass(10, 3, 1024, 0)["bytes"] == 10 * (6 + 12) + 3 * 1024 * 8


def test_leaf_pass_and_round_by_hand():
    assert work.leaf_pass(1000, 28, 256) == {"adds": 1000, "bytes": 40000}
    r = work.round_work(1000, 28, 256, 2)
    l0, l1 = work.hist_pass(1000, 28, 256, 0), work.hist_pass(1000, 28, 256, 1)
    assert r["bytes"] == l0["bytes"] + l1["bytes"] + 40000
    assert r["adds"] == 2 * 56000 + 1000


def test_the_quarter_cell_needs_under_a_millisecond_of_hbm():
    w = work.hist_passes(2_625_000, 28, 256, 6)
    assert w["bytes"] == 2_625_000 * (6 * 40 + 5 * 4) + 63 * 28 * 256 * 8
    s = work.least_seconds(w, "TPU v5 lite")
    assert s == pytest.approx(w["bytes"] / 819e9)     # the bytes bound it
    assert 0.8e-3 < s < 0.9e-3
    assert work.least_seconds(w, "TPU v5 lite", 4) == pytest.approx(s / 4)


def test_the_counts_know_nothing_of_the_program():
    import inspect

    src = inspect.getsource(work)
    assert "rabit_tpu" not in src and "import jax" not in src


def test_unknown_device_is_an_error_never_a_default():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("source")          # the table's own note is no device
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_issued_onehot_count_is_far_above_the_algorithms():
    issued = work.issued_onehot_flops(2_625_000, 28, 256, 6)
    assert issued == 2.0 * 2_625_000 * 28 * 256 * (128 * 6)
    assert issued > 1000 * work.hist_passes(2_625_000, 28, 256, 6)["adds"]
