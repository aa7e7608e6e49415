"""Independent layers, each with bias + ReLU and an input of its own: the
paper's per-layer sets, whose layers cannot be chained (per-group halves,
layers that follow a stride the planner cannot express).

One input and one output per layer, in the configuration's order; one
step runs every layer once.  The program's forward runs
``plan_network -> NetworkPlan.prepare`` layers, each inside
``jax.named_scope(<layer name>)``; the reference beside it runs the same
arithmetic with plain ``lax`` convolutions.
"""
from __future__ import annotations

import jax

from bench.lib import reference as R


def input_shapes(cfg, batch):
    return tuple((batch, l["C"], l["H"], l["W"]) for l in cfg["layers"])


def network_convs(cfg, batch):
    from repro.conv import Epilogue, NetworkConv
    ep = Epilogue(bias=True, activation=cfg["activation"])
    return tuple(NetworkConv(
        name=l["name"], x_shape=(batch, l["C"], l["H"], l["W"]),
        k_shape=(l["Cout"], l["C"], l["k"], l["k"]), padding=l["pad"],
        epilogue=ep) for l in cfg["layers"])


def forward(cfg):
    def fwd(prepared, biases, inputs):
        out = []
        for name, x in zip(prepared, inputs):
            with jax.named_scope(name):
                out.append(prepared[name](x, bias=biases[name]))
        return tuple(out)
    return fwd


def reference(cfg, conv):
    layers = [(l["name"], l["pad"]) for l in cfg["layers"]]

    def ref(kernels, biases, inputs):
        return tuple(R.bias_relu(conv(x, kernels[name], pad), biases[name])
                     for (name, pad), x in zip(layers, inputs))
    return ref
