"""Work and bytes of a convolution layer with a stride, counted from its
shapes alone.

The same yardstick as ``bench.lib.work`` (W = 2·B·C·Cout·Ho·Wo, one f32
read of x, k and the bias and one write of y), with the output extent
of a layer that has a ``stride`` (1 where it has none):
``Ho = (H + 2·pad − k) // stride + 1``.  At stride 1 every count equals
``work``'s; ``work`` itself counts every layer at stride 1, which puts a
stride-2 layer's output, and its work, 4× too high.
"""
from __future__ import annotations


def out_hw(layer: dict) -> tuple:
    k, pad, s = layer["k"], layer["pad"], layer.get("stride", 1)
    return ((layer["H"] + 2 * pad - k) // s + 1,
            (layer["W"] + 2 * pad - k) // s + 1)


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


def roofline_share(ctx, layers) -> float:
    """Percent: the least time of ``layers`` over every step of the
    traced window, over the device time of the ops inside their
    ``named_scope``s.  ``None`` without a trace, without layers, or where
    the trace attributes no op to them."""
    tr = ctx.get("trace")
    if not tr or not layers:
        return None
    device_s = sum(tr["scope_s"].get(l["name"], 0.0) for l in layers)
    if device_s <= 0:
        return None
    floor = sum(least_time_s(l, ctx["batch"], ctx["peaks"]) for l in layers)
    return 100.0 * floor * ctx["steps"] / device_s
