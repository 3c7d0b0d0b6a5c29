"""Byte counts and peaks against hand-computed values."""
import pytest

from bench import peaks, roofline


def test_pagerank_bytes_small_graph_by_hand():
    # 10 edges x (8 B edge + 4 B contribution) + 4 vertices x 3 vectors x 4 B
    assert roofline.pagerank_iteration_bytes(4, 10) == 120 + 48
    assert roofline.pagerank_bytes(4, 10, 2) == 336


def test_pagerank_bytes_kron22():
    per_iter = roofline.pagerank_iteration_bytes(1 << 22, 1 << 26)
    assert per_iter == 67_108_864 * 12 + 4_194_304 * 12 == 855_638_016


def test_roofline_share():
    assert roofline.roofline_share(819e9, 1.0, 819e9) == pytest.approx(100.0)
    assert roofline.roofline_share(819e6, 2.0, 819e9) == pytest.approx(0.05)
    with pytest.raises(ValueError):
        roofline.roofline_share(1.0, 0.0, 819e9)


def test_peaks_known_and_unknown_device():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert "source" in v5e
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
