"""A residual network: ResNet-34's stem, basic blocks and head.

Each layer of the configuration names the tensor it reads (``input``:
``image``, a pool, or another layer), the tensor added to its output
before the ReLU (``residual``, or null) and whether a ReLU follows
(``relu``); every layer adds its bias and has its own ``stride``.
``pools`` name the max pool after the stem and the global mean pool
before the classifier.

One input, ``(B, in_channels, image, image)``; two outputs, the last
block's activations and the logits ``(B, num_classes)``, so that the
comparison sees the network before the mean pool averages its errors
away.  The program's forward is ``repro.models.resnet.resnet_forward``
over ``plan_network -> NetworkPlan.prepare``, with a
``jax.named_scope`` per layer; the reference beside it walks the
configuration's wiring with plain ``lax`` convolutions, a strided conv
being the unit-stride conv subsampled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def input_shapes(cfg, batch):
    return ((batch, cfg["in_channels"], cfg["image"], cfg["image"]),)


def network_convs(cfg, batch):
    from repro.conv import Epilogue, NetworkConv
    return tuple(NetworkConv(
        name=l["name"], x_shape=(batch, l["C"], l["H"], l["W"]),
        k_shape=(l["Cout"], l["C"], l["k"], l["k"]), padding=l["pad"],
        epilogue=Epilogue(bias=True,
                          activation="relu" if l["relu"] else "none",
                          residual=l["residual"] is not None),
        stride=l["stride"]) for l in cfg["layers"])


def forward(cfg):
    from repro.models.resnet import resnet_forward

    def fwd(prepared, biases, inputs):
        (x,) = inputs
        return resnet_forward(biases, features=True)(prepared, x)
    return fwd


def _pool(p, x):
    if p["kind"] == "max":
        pad = p["pad"]
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, p["k"], p["k"]),
            (1, 1, p["stride"], p["stride"]),
            ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    if p["kind"] == "mean":
        return jnp.mean(x, axis=(2, 3), keepdims=True)
    raise ValueError(f"unknown pool kind {p['kind']!r}")


def reference(cfg, conv):
    layers = cfg["layers"]
    pools = {p["input"]: p for p in cfg["pools"]}
    trunk, logits = cfg["outputs"]

    def ref(kernels, biases, inputs):
        (x,) = inputs
        t = {"image": x}
        for l in layers:
            s = l["stride"]
            y = conv(t[l["input"]], kernels[l["name"]], l["pad"])[
                :, :, ::s, ::s] + biases[l["name"]][None, :, None, None]
            if l["residual"] is not None:
                y = y + t[l["residual"]]
            t[l["name"]] = jnp.maximum(y, 0.0) if l["relu"] else y
            if l["name"] in pools:
                p = pools[l["name"]]
                t[p["name"]] = _pool(p, t[l["name"]])
        return t[trunk], t[logits].reshape(x.shape[0], -1)
    return ref
