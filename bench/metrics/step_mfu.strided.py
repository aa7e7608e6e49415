"""The whole step's share of the chip's peak, in percent, with each
layer's work counted at its stride (W = 2·B·C·Cout·Ho·Wo summed over the
layers, ``bench.lib.strided_work``) times steps per second of the window,
over the ``PEAKS`` FLOP/s of the device kind."""
from bench.lib.strided_work import conv_work


def read(ctx):
    work = sum(conv_work(l, ctx["batch"]) for l in ctx["cfg"]["layers"])
    return 100.0 * work * ctx["steps"] / ctx["window_s"] / ctx["peaks"].flops
