"""ResNet-34 on the conv engine.

He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1, 34-layer column, with projection shortcuts
(option B) where the shape changes; torchvision's ``resnet34`` has the
same layers under the same names.  Every conv is a ``NetworkConv`` that
``plan_network`` plans like any other layer: the 7x7/2 stem, the basic
blocks' 3x3 convs, the 1x1/2 projections and the 1000-way classifier,
which is a 1x1 conv on the pooled 1x1 map.  BatchNorm is folded into each
conv's kernel and bias (the inference form), so every layer has a bias,
and the residual add and the ReLU after it ride conv2's fused epilogue:

    stem    relu(conv1(x)), 3x3/2 max pool with pad 1
    block   relu(conv2(relu(conv1(x))) + shortcut), where shortcut is x
            or downsample(x) (a 1x1 conv, bias only)
    head    global average pool, fc (bias only)

Usage::

    net = plan_network(resnet34_convs(batch))
    prepared = net.prepare(kernels)
    logits = jax.jit(resnet_forward(biases))(prepared, x)
"""
from __future__ import annotations

import jax

from repro.conv import Epilogue, NetworkConv
from repro.models.layers import global_avgpool, maxpool3x3s2

BLOCKS = (3, 4, 6, 3)               # basic blocks per stage
WIDTHS = (64, 128, 256, 512)        # channels per stage


def resnet34_convs(batch: int, *, image: int = 224, widths=WIDTHS) -> tuple:
    """The ``NetworkConv`` of every layer, in the order the forward runs
    them, named as torchvision names them (``conv1``,
    ``layer1.0.conv1`` … ``layer4.2.conv2``,
    ``layer{2,3,4}.0.downsample``, ``fc``), on RGB input with a
    1000-way classifier.  ``image`` and ``widths`` (the four stages'
    channels) shrink the network for tests; the published one is the
    default."""
    relu = Epilogue(bias=True, activation="relu")
    add_relu = Epilogue(bias=True, activation="relu", residual=True)
    bias = Epilogue(bias=True)
    layers = []

    def conv(name, c, h, cout, k, stride, epilogue):
        pad = k // 2
        layers.append(NetworkConv(
            name=name, x_shape=(batch, c, h, h), k_shape=(cout, c, k, k),
            padding=pad, epilogue=epilogue, stride=stride))
        return (h + 2 * pad - k) // stride + 1

    h = conv("conv1", 3, image, widths[0], 7, 2, relu)
    h = (h + 2 - 3) // 2 + 1                            # the max pool
    c = widths[0]
    for i, (n, width) in enumerate(zip(BLOCKS, widths)):
        for j in range(n):
            block = f"layer{i + 1}.{j}"
            stride = 2 if i > 0 and j == 0 else 1
            if stride != 1 or c != width:
                conv(f"{block}.downsample", c, h, width, 1, stride, bias)
            ho = conv(f"{block}.conv1", c, h, width, 3, stride, relu)
            conv(f"{block}.conv2", width, ho, width, 3, 1, add_relu)
            c, h = width, ho
    conv("fc", c, 1, 1000, 1, 1, bias)
    return tuple(layers)


def _blocks(names) -> list:
    """Block prefixes (``layer1.0`` …) in the order their layers come."""
    return list(dict.fromkeys(n.rsplit(".", 1)[0] for n in names
                              if n.startswith("layer")))


def resnet_forward(biases, *, features: bool = False):
    """``forward(prepared, x)`` over a prepared ``resnet34_convs``
    network (``biases`` maps layer name -> (Cout,) bias): the logits
    ``(B, 1000)``, or ``(layer4 output, logits)`` with
    ``features``.  Each conv runs inside ``jax.named_scope(<layer
    name>)``, the max pool inside ``pool`` and the average pool inside
    ``head``."""
    def forward(prepared, x):
        def conv(name, x, residual=None):
            extra = {} if residual is None else {"residual": residual}
            with jax.named_scope(name):
                return prepared[name](x, bias=biases[name], **extra)

        x = conv("conv1", x)
        with jax.named_scope("pool"):
            x = maxpool3x3s2(x)
        names = list(prepared)
        for block in _blocks(names):
            shortcut = x
            if f"{block}.downsample" in names:
                shortcut = conv(f"{block}.downsample", x)
            y = conv(f"{block}.conv1", x)
            x = conv(f"{block}.conv2", y, residual=shortcut)
        trunk = x
        with jax.named_scope("head"):
            x = global_avgpool(x)
        logits = conv("fc", x).reshape(x.shape[0], -1)
        return (trunk, logits) if features else logits
    return forward
