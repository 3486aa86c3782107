"""cost.py against bytes and operations worked out by hand."""
import pytest

from chipbench import cost


def test_covtype_epoch():
    n, d = 581_008, 54
    c = cost.sgd_epoch(n, d)
    assert c["flops"] == 4 * 581_008 * 54 == 125_497_728
    # values 581,008 x 54 x 4 B, labels 581,008 x 4 B, model in and out
    assert c["bytes"] == 125_497_728 + 2_324_032 + 432
    assert cost.loss(n, d)["flops"] == 62_748_864


def test_w8a_epoch_is_priced_at_its_nonzeros():
    n, d, nnz = 64_696, 300, 753_708          # 11.65 a row
    c = cost.sgd_epoch(n, d, nnz)
    assert c["flops"] == 4 * 753_708
    # value and index of each nonzero, a label a row, model in and out
    assert c["bytes"] == 8 * 753_708 + 4 * 64_696 + 2400
    padded = cost.sgd_epoch(n, d, n * 114)
    assert padded["bytes"] > c["bytes"]       # the ELL width is not priced


def test_epoch_with_loss_reads_the_data_once():
    n, d, nnz = 64_696, 300, 753_708
    both = cost.epoch_with_loss(n, d, nnz)
    assert both["flops"] == 6 * nnz
    assert both["bytes"] == 8 * nnz + 4 * n + 2400


def test_least_seconds_is_the_larger_bound():
    peak = cost.peaks("TPU v5 lite")
    work = cost.sgd_epoch(581_008, 54)
    assert cost.least_seconds(work, peak) == pytest.approx(
        work["bytes"] / 819e9)                # bandwidth bound
    assert cost.least_seconds({"flops": 197e12, "bytes": 0}, peak) == 1.0


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        cost.peaks("cpu")
