"""ResNet-34 through the engine's normal path (``plan_network ->
prepare -> resnet_forward``) against a plain reference: the published
layout (3/4/6/3 basic blocks, every stride, the projection shortcuts) at
a small size on seeded random weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.conv import plan_network
from repro.models.layers import global_avgpool, maxpool3x3s2
from repro.models.resnet import resnet34_convs, resnet_forward

BATCH, IMAGE, WIDTHS = 2, 32, (8, 16, 32, 64)
# max|y - ref| / max|ref|, float32 throughout and the reference at
# HIGHEST.  The FFT layers differ from a direct conv by rounding alone:
# with every unit-stride layer on fft-xla the trunk reads 5.4e-7 and the
# logits 1.5e-6 on the CPU, ~70x inside this bound, while one layer's
# kernel scaled by 1.01 reads 3.1e-3, 30x above it.
TOL = 1e-4


def _reference(kernels, biases, x):
    """ResNet-34 in plain ``lax``: wiring written out from the paper's
    Table 1 (34-layer, option-B shortcuts), BatchNorm folded into each
    conv's bias; no plans, no prepared state."""
    def conv(name, x, stride, pad):
        y = jax.lax.conv_general_dilated(
            x, kernels[name], (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return y + biases[name][None, :, None, None]

    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(conv("conv1", x, 2, 3))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                                  (1, 1, 2, 2),
                                  ((0, 0), (0, 0), (1, 1), (1, 1)))
        for stage, blocks in enumerate((3, 4, 6, 3), start=1):
            for j in range(blocks):
                name = f"layer{stage}.{j}"
                stride = 2 if stage > 1 and j == 0 else 1
                shortcut = (conv(f"{name}.downsample", x, 2, 0)
                            if stride == 2 else x)
                y = jax.nn.relu(conv(f"{name}.conv1", x, stride, 1))
                x = jax.nn.relu(conv(f"{name}.conv2", y, 1, 1) + shortcut)
        pooled = jnp.mean(x, axis=(2, 3), keepdims=True)
        logits = conv("fc", pooled, 1, 0).reshape(x.shape[0], -1)
    return x, logits


def _params(convs, seed):
    rng = np.random.default_rng(seed)
    kernels = {c.name: jnp.asarray(
        rng.standard_normal(c.k_shape) * np.sqrt(2.0 / np.prod(
            c.k_shape[1:])), jnp.float32) for c in convs}
    biases = {c.name: jnp.asarray(0.01 * rng.standard_normal(c.k_shape[0]),
                                  jnp.float32) for c in convs}
    return kernels, biases


def _convs():
    return resnet34_convs(BATCH, image=IMAGE, widths=WIDTHS)


def _rel_err(y, ref):
    return float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))


def test_published_layout():
    convs = resnet34_convs(128)
    by = {c.name: c for c in convs}
    assert len(convs) == 37                  # 33 weighted convs + 3 + fc
    assert sum(1 for n in by if n.endswith((".conv1", ".conv2"))) == 32
    strided = sorted(n for n, c in by.items() if c.stride == 2)
    assert strided == ["conv1", "layer2.0.conv1", "layer2.0.downsample",
                       "layer3.0.conv1", "layer3.0.downsample",
                       "layer4.0.conv1", "layer4.0.downsample"]
    assert by["conv1"].k_shape == (64, 3, 7, 7) and by["conv1"].padding == 3
    assert by["layer1.0.conv1"].x_shape == (128, 64, 56, 56)
    assert by["layer4.2.conv2"].x_shape == (128, 512, 7, 7)
    assert by["layer3.0.downsample"].k_shape == (256, 128, 1, 1)
    assert by["fc"].x_shape == (128, 512, 1, 1)
    assert by["fc"].k_shape == (1000, 512, 1, 1)
    assert by["layer2.0.conv2"].epilogue.residual
    assert by["layer2.0.conv2"].epilogue.activation == "relu"
    assert by["layer2.0.conv1"].epilogue.activation == "relu"
    assert not by["layer2.0.conv1"].epilogue.residual
    for name in ("layer2.0.downsample", "fc"):
        ep = by[name].epilogue
        assert ep.bias and ep.activation == "none" and not ep.residual
    # 21.8 M parameters, as published (BatchNorm folded into the biases)
    n = sum(int(np.prod(c.k_shape)) + c.k_shape[0] for c in convs)
    assert 21.7e6 < n < 21.9e6


def test_auto_plans_strided_layers_on_direct_and_unit_layers_on_fft():
    net = plan_network(resnet34_convs(128), backend="auto")
    backends = {n: net[n].backend for n in net}
    assert all(backends[n] == "direct"
               for n in net if net[n].spec.stride > 1)
    # by predicted chip time the unit-stride 3x3 layers (64-512
    # channels, 56 to 7 wide at batch 128) run faster on direct
    unit = [n for n in net if net[n].spec.stride == 1 and n != "fc"]
    assert len(unit) == 29
    assert all(backends[n] == "direct" for n in unit)


def test_pools():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 7, 8)),
                    jnp.float32)
    y = np.asarray(maxpool3x3s2(x))
    xp = np.pad(np.asarray(x), ((0, 0), (0, 0), (1, 1), (1, 1)),
                constant_values=-np.inf)
    want = np.stack([np.stack([xp[:, :, 2 * i:2 * i + 3, 2 * j:2 * j + 3]
                               .max(axis=(2, 3)) for j in range(4)], -1)
                     for i in range(4)], -2)
    np.testing.assert_array_equal(y, want)
    np.testing.assert_allclose(global_avgpool(x)[..., 0, 0],
                               np.asarray(x).mean(axis=(2, 3)), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("backend", ["auto", "fft-xla"])
def test_matches_the_plain_reference(backend):
    convs = _convs()
    if backend != "auto":
        # every unit-stride layer on the FFT pipeline, with its fused
        # residual epilogue; the strided layers stay on direct
        convs = tuple(dataclasses.replace(c, overrides=(("backend",
                                                         "direct"),))
                      if c.stride > 1 else c for c in convs)
    net = plan_network(convs, backend=backend)
    kernels, biases = _params(convs, seed=11)
    x = jnp.asarray(np.random.default_rng(12).standard_normal(
        (BATCH, 3, IMAGE, IMAGE)), jnp.float32)
    trunk, logits = jax.jit(resnet_forward(biases, features=True))(
        net.prepare(kernels), x)
    want_trunk, want_logits = _reference(kernels, biases, x)
    assert trunk.shape == (BATCH, WIDTHS[-1], 1, 1)
    assert logits.shape == (BATCH, 1000)
    assert _rel_err(trunk, want_trunk) < TOL
    assert _rel_err(logits, want_logits) < TOL
    plain = jax.jit(resnet_forward(biases))(net.prepare(kernels), x)
    np.testing.assert_array_equal(plain, logits)


def test_a_wrong_layer_is_seen():
    convs = _convs()
    net = plan_network(convs)
    kernels, biases = _params(convs, seed=13)
    x = jnp.asarray(np.random.default_rng(14).standard_normal(
        (BATCH, 3, IMAGE, IMAGE)), jnp.float32)
    want_trunk, _ = _reference(kernels, biases, x)
    kernels["layer3.2.conv1"] = 1.01 * kernels["layer3.2.conv1"]
    trunk, _ = resnet_forward(biases, features=True)(net.prepare(kernels),
                                                      x)
    assert _rel_err(trunk, want_trunk) > 10 * TOL


def test_each_layer_runs_in_its_own_scope():
    convs = _convs()
    net = plan_network(convs)
    kernels, biases = _params(convs, seed=15)
    x = jax.ShapeDtypeStruct((BATCH, 3, IMAGE, IMAGE), jnp.float32)
    hlo = jax.jit(resnet_forward(biases)).lower(
        net.prepare(kernels), x).as_text(debug_info=True)
    for c in convs:
        assert f"/{c.name}/" in hlo, c.name
    for scope in ("pool", "head"):
        assert f"/{scope}/" in hlo, scope
