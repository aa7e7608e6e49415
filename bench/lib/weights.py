"""Weights and inputs, made on the device from the run's seed.

Each is one jitted call that takes the key as an argument, so every seed
runs the same compiled program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

KERNELS, INPUTS, BIASES = 0, 1, 2


def key(seed: int, stream: int):
    return jax.random.fold_in(jax.random.key(seed), stream)


@functools.partial(jax.jit, static_argnums=0)
def _kernels(shapes, k):
    out = {}
    for i, (name, (co, c, kh, kw)) in enumerate(shapes):
        std = (2.0 / (c * kh * kw)) ** 0.5
        out[name] = std * jax.random.normal(jax.random.fold_in(k, i),
                                            (co, c, kh, kw), jnp.float32)
    return out


@functools.partial(jax.jit, static_argnums=0)
def _biases(shapes, k):
    return {name: 0.01 * jax.random.normal(jax.random.fold_in(k, i),
                                           (co,), jnp.float32)
            for i, (name, co) in enumerate(shapes)}


def params(layers, seed: int):
    """(kernels, biases): name -> (Cout, C, k, k) He-normal kernel, and
    name -> (Cout,) bias."""
    kshapes = tuple((l["name"], (l["Cout"], l["C"], l["k"], l["k"]))
                    for l in layers)
    bshapes = tuple((l["name"], l["Cout"]) for l in layers)
    return (_kernels(kshapes, key(seed, KERNELS)),
            _biases(bshapes, key(seed, BIASES)))


@functools.partial(jax.jit, static_argnums=0)
def _normal(shapes, k):
    return tuple(jax.random.normal(jax.random.fold_in(k, i), s, jnp.float32)
                 for i, s in enumerate(shapes))


def normal_arrays(shapes, seed: int, stream: int = INPUTS) -> tuple:
    """N(0, 1) float32 arrays of ``shapes``, in one call from the seed."""
    return _normal(tuple(tuple(s) for s in shapes), key(seed, stream))
