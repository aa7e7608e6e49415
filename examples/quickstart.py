"""Quickstart: the paper's FFT-based convolution behind the plan/execute API.

    PYTHONPATH=src python examples/quickstart.py

``plan_conv`` freezes the algorithm (direct or FFT; ``backend="auto"``
picks the one with the smaller predicted device time) and the schedule,
and the returned plan executes (and differentiates) like a plain
function. Plans are cached by shape, so
planning inside a layer loop is free after the first call.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.conv import plan_conv, plan_cache_info
from repro.core import conv2d_direct

rng = np.random.default_rng(0)

# A VGG-ish layer: 64 -> 128 channels, 56x56, 3x3, unit stride, pad 1.
x = jnp.asarray(rng.standard_normal((2, 64, 56, 56)), jnp.float32)
k = jnp.asarray(rng.standard_normal((128, 64, 3, 3)), jnp.float32)

plan = plan_conv(x.shape, k.shape, padding=1, backend="fft-xla")
y_fft = plan(x, k)                                 # execute
y_ref = conv2d_direct(x, k, padding=1)             # direct oracle

err = float(jnp.max(jnp.abs(y_fft - y_ref)) / jnp.max(jnp.abs(y_ref)))
print(f"output {y_fft.shape}, rel err vs direct conv: {err:.2e}")
print(plan.describe())

spec = plan.spec
print(f"tiling: {spec.X}x{spec.D} tiles of {spec.delta}x{spec.delta}, "
      f"P={spec.freq_points()} frequency points (compact half-spectrum), "
      f"CGEMM {spec.M}x{spec.C}x{spec.Cout}")

# backend="auto" picks by predicted device time: a 64-channel layer runs
# faster on direct; the FFT wins with wide channels and many tiles.
for shapes in ((x.shape, k.shape), ((32, 512, 28, 28), (512, 512, 3, 3))):
    auto = plan_conv(*shapes, padding=1)
    print(f"auto backend for {shapes[0]} * {shapes[1]}: {auto.backend}")

# Plans are differentiable on every backend x schedule (plan-level VJP).
def loss(k):
    return jnp.mean((plan(x, k) - y_ref) ** 2)

g = jax.grad(loss)(k)
print("grad norm through the plan:", float(jnp.linalg.norm(g)))
print("plan cache:", plan_cache_info())

# Serving: prepare once (the kernel transform is cached under a weights
# version), then every call runs stages 1/3/4 only.
prepared = plan.prepare(k, weights_version=0)
y_prep = prepared(x)
print("prepared exec matches one-shot:",
      bool(jnp.allclose(y_prep, y_fft, atol=1e-5)))
