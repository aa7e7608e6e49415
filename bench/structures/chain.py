"""A chain of convolutions, each with bias + ReLU, and 2x2 max-pools after
the layers the configuration names (``pool_after``): the VGG trunk.

One input, ``(B, in_channels, image, image)``; one output, the last
layer's pooled activations.  The program's forward runs
``plan_network -> NetworkPlan.prepare`` layers, each inside
``jax.named_scope(<layer name>)``; the reference beside it runs the same
arithmetic with plain ``lax`` convolutions.
"""
from __future__ import annotations

import jax

from bench.lib import reference as R


def input_shapes(cfg, batch):
    return ((batch, cfg["in_channels"], cfg["image"], cfg["image"]),)


def network_convs(cfg, batch):
    from repro.conv import Epilogue, NetworkConv
    ep = Epilogue(bias=True, activation=cfg["activation"])
    return tuple(NetworkConv(
        name=l["name"], x_shape=(batch, l["C"], l["H"], l["W"]),
        k_shape=(l["Cout"], l["C"], l["k"], l["k"]), padding=l["pad"],
        epilogue=ep) for l in cfg["layers"])


def forward(cfg):
    from repro.models.layers import maxpool2x2
    pools = frozenset(cfg["pool_after"])

    def fwd(prepared, biases, inputs):
        (x,) = inputs
        for name in prepared:
            with jax.named_scope(name):
                x = prepared[name](x, bias=biases[name])
            if name in pools:
                with jax.named_scope("pool"):
                    x = maxpool2x2(x)
        return (x,)
    return fwd


def reference(cfg, conv):
    pools = frozenset(cfg["pool_after"])
    layers = [(l["name"], l["pad"]) for l in cfg["layers"]]

    def ref(kernels, biases, inputs):
        (x,) = inputs
        for name, pad in layers:
            x = R.bias_relu(conv(x, kernels[name], pad), biases[name])
            if name in pools:
                x = R.maxpool2x2(x)
        return (x,)
    return ref
