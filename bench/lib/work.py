"""Work and bytes of a convolution layer, counted from its shapes alone.

Work is W = 2·B·C·Cout·Ho·Wo: one multiply-add per output element per
input channel.  It reads the same whatever implements the layer (direct,
FFT at any tile size, Winograd), and every such algorithm only approaches
it as its tile grows, so a share of a peak taken from it cannot pass 100%
by a change of algorithm or of matmul precision.  It is not the direct
convolution's count, which is kh·kw times larger.

Bytes are one read of x, k and the bias and one write of y, at the
configuration's item size: the least traffic any implementation has.
"""
from __future__ import annotations


def out_hw(layer: dict) -> tuple:
    k, pad = layer["k"], layer["pad"]
    return layer["H"] + 2 * pad - k + 1, layer["W"] + 2 * pad - k + 1


def conv_work(layer: dict, batch: int) -> int:
    ho, wo = out_hw(layer)
    return 2 * batch * layer["C"] * layer["Cout"] * ho * wo


def conv_bytes(layer: dict, batch: int, itemsize: int = 4) -> int:
    ho, wo = out_hw(layer)
    c, co, k = layer["C"], layer["Cout"], layer["k"]
    elems = (batch * c * layer["H"] * layer["W"] + co * c * k * k + co
             + batch * co * ho * wo)
    return itemsize * elems


def least_time_s(layer: dict, batch: int, peaks) -> float:
    """The layer's floor on the chip: the larger of work over peak FLOP/s
    and bytes over peak bandwidth."""
    return max(conv_work(layer, batch) / peaks.flops,
               conv_bytes(layer, batch) / peaks.hbm_bw)
