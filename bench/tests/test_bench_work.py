"""Scan-work formulas against hand counts."""
import pytest

from harness import work

FLAT = {"d": 768, "fcvi": {"backend": "flat"}}
IVF = {"d": 128, "nlist": 1024, "nprobe": 32, "fcvi": {"backend": "ivf"}}


def test_row_bytes():
    assert work.row_bytes(FLAT) == 768 * 4 + 4
    assert work.row_bytes({"d": 768, "fcvi": {"storage_dtype": "bfloat16"}}) \
        == 768 * 2 + 4
    assert work.row_bytes({"d": 768, "fcvi": {"storage_dtype": "int8"}}) \
        == 768 + 8


def test_flat_one_read_per_call():
    b, f = work.scan_work(FLAT, "similarity", 64, 1 << 20)
    assert b == (1 << 20) * 3076
    assert f == 2 * 768 * 64 * (1 << 20)


def test_ivf_expected_union():
    b, f = work.scan_work(IVF, "similarity", 1, 1_000_000)
    # one query probes exactly nprobe lists
    assert b == pytest.approx(32 * (1_000_000 / 1024) * 516)
    assert f == pytest.approx(2 * 128 * 32 * 1_000_000 / 1024)
    b64, _ = work.scan_work(IVF, "similarity", 64, 1_000_000)
    lists = 1024 * (1 - (1 - 32 / 1024) ** 64)
    assert b64 == pytest.approx(lists * (1_000_000 / 1024) * 516)
    assert 880 < lists < 900


def test_predicate_eligible_rows_only():
    b, f = work.scan_work(FLAT, "predicate", 10, 1 << 20, n_elig=10_000)
    assert b == 10_000 * 3076
    assert f == 2 * 768 * 10 * 10_000


def test_least_time_takes_the_binding_bound():
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    assert work.least_time(819e9, 1.0, peak) == pytest.approx(1.0)
    assert work.least_time(1.0, 394e12, peak) == pytest.approx(2.0)
