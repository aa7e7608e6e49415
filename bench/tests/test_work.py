"""Work and byte counts against hand-computed values."""
from bench.lib import work
from bench.lib.peaks import peaks

VCONV42 = {"name": "Vconv4.2", "C": 512, "Cout": 512, "H": 28, "W": 28,
           "k": 3, "pad": 1}
ACONV2 = {"name": "Aconv2", "C": 48, "Cout": 128, "H": 27, "W": 27, "k": 5,
          "pad": 2}


def test_vconv42_batch32():
    # 2 * 32 * 512 * 512 * 28 * 28
    assert work.conv_work(VCONV42, 32) == 13_153_337_344
    # 4 * (x 32*512*28*28 + k 512*512*9 + b 512 + y 32*512*28*28)
    assert work.conv_bytes(VCONV42, 32) == 4 * (12_845_056 + 2_359_296
                                                + 512 + 12_845_056)


def test_aconv2_batch128():
    # 2 * 128 * 48 * 128 * 27 * 27 (5x5, pad 2: 27 -> 27)
    assert work.conv_work(ACONV2, 128) == 1_146_617_856
    # 4 * (x 128*48*27*27 + k 128*48*25 + b 128 + y 128*128*27*27)
    assert work.conv_bytes(ACONV2, 128) == 4 * (4_478_976 + 153_600 + 128
                                                + 11_943_936)


def test_least_time_is_the_larger_bound():
    pk = peaks("TPU v5 lite")
    t = work.least_time_s(VCONV42, 32, pk)
    assert t == max(13_153_337_344 / 197e12, 112_199_680 / 819e9)
    assert t == 112_199_680 / 819e9           # memory-bound at W


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
