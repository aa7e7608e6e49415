"""Plain reference arithmetic, independent of the program under test.

``conv_highest`` is the float32 convolution a configuration states:
``lax.conv_general_dilated`` at ``Precision.HIGHEST``, which a TPU computes
to float32 accuracy.  ``conv_high`` is the control, the step below it that
would tempt a later change: XLA's ``high`` (bf16_3x) arithmetic, spelled
out — each operand split into a head and a tail, each rounded to
bfloat16 (to nearest, ties to even), and the three products head·head,
head·tail and tail·head summed in float32 — so that it computes the same
on any backend, the CPU included.  The rounding is done on the bits: a
round trip through the ``bfloat16`` type is folded away by XLA on a TPU
(excess precision is allowed there), which leaves the tail 0 and the
control a single bf16 pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def conv_highest(x, k, pad):
    return jax.lax.conv_general_dilated(
        x, k, window_strides=(1, 1), padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bf16_head(a):
    """``a`` rounded to the nearest bfloat16 (ties to even), as float32;
    ``a`` finite."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split_bf16(a):
    head = _bf16_head(a)
    return head, _bf16_head(a - head)


def conv_high(x, k, pad):
    xh, xt = _split_bf16(x)
    kh, kt = _split_bf16(k)
    return (conv_highest(xh, kh, pad) + conv_highest(xh, kt, pad)
            + conv_highest(xt, kh, pad))


CONVS = {"highest": conv_highest, "high": conv_high}


def bias_relu(y, b):
    return jnp.maximum(y + b[None, :, None, None], 0.0)


def maxpool2x2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                                 (1, 1, 2, 2), "VALID")
