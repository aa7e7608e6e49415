"""The comparison that decides ``correct``.

Each number compared is a reading with a limit of its own, from the cell
file (``bench/cells/<cell>.json``).  ``max_rel_err`` is
``max|y - ref| / max|ref|`` of an output, the largest over the outputs
compared; ``ref`` is the plain reference in float32 at ``HIGHEST``.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp


@jax.jit
def _rel_err(y, ref):
    return jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))


def rel_err(y, ref) -> float:
    if tuple(y.shape) != tuple(ref.shape):
        return float("inf")
    return float(_rel_err(y, ref))


def max_rel_err(pairs) -> float:
    """The largest ``rel_err`` over (output, reference) pairs."""
    return max(rel_err(y, r) for y, r in pairs)


def verdict(readings: dict, limits: dict) -> tuple:
    """(correct, check): each reading beside its limit, in the order of
    ``limits``; a reading that is missing or above its limit fails."""
    check = {name: {"value": readings.get(name), "limit": lim}
             for name, lim in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in check.values())
    return ok, check


def print_check(check: dict) -> None:
    """The numbers compared, as the last lines of standard error."""
    for name, c in check.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
