"""The work of one token update and the table of peaks."""

import json

import pytest

from bench import roofline


def test_token_work():
    assert roofline.token_work(1000) == (4000.0, 8000.0)


def test_v5e_rate_is_bandwidth_bound():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    assert roofline.roofline_tokens_per_s(1000, peak) == \
        pytest.approx(819e9 / 8000)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_every_peak_names_its_source():
    for kind, row in json.loads(roofline.PEAKS_FILE.read_text()).items():
        assert row["source"], kind
