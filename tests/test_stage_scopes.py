"""Device-op names of the conv stages.

Each forward stage op opens a ``jax.named_scope`` of its own name, nested
under whatever scope the caller opened, so the compiled HLO's ``op_name``
metadata says which stage each instruction belongs to.  The benchmark's
stage reading (``bench/lib/stages.py``, run by ``bench/stages.py``) looks
for exactly these names in a device trace: a renamed scope fails here
instead of emptying that reading in silence.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.conv import Epilogue, plan_conv, stage_trace

X_SHAPE, K_SHAPE = (2, 8, 16, 16), (8, 8, 3, 3)
CALLER = "conv_layer"

# backend -> (scope names its forward must carry, stage_trace string counts)
CASES = {
    "fft-xla": (("input_transform", "cgemm", "output_inverse"),
                {"input_transform": 1, "cgemm": 1, "output_inverse": 1}),
    "fft-pallas": (("input_transform", "cgemm", "output_inverse"),
                   {"input_transform": 1, "cgemm": 1, "output_inverse": 1}),
    "direct": (("direct",), {}),
}
ALL_STAGES = ("input_transform", "cgemm", "output_inverse", "direct")


def _compiled(backend):
    """(compiled HLO text, stage_trace counts) of a prepared local plan
    with a bias + ReLU epilogue, called inside the caller's scope."""
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend=backend,
                     schedule="local",
                     epilogue=Epilogue(bias=True, activation="relu"),
                     cache=False)
    kx, kk = jax.random.split(jax.random.PRNGKey(0))
    prepared = plan.prepare(jax.random.normal(kk, K_SHAPE))
    x = jax.random.normal(kx, X_SHAPE)

    def fwd(p, x, b):
        with jax.named_scope(CALLER):
            return p(x, bias=b)

    with stage_trace() as counts:
        text = jax.jit(fwd).lower(prepared, x, jnp.ones((8,))).compile() \
            .as_text()
    return text, counts


@pytest.mark.parametrize("backend", sorted(CASES))
def test_stage_scopes_reach_compiled_op_names(backend):
    expected, _ = CASES[backend]
    text, _ = _compiled(backend)
    paths = [n.split("/") for n in re.findall(r'op_name="([^"]*)"', text)]
    found = set()
    for parts in paths:
        for stage in ALL_STAGES:
            if stage in parts:
                # the stage nests directly under the caller's scope
                assert parts[parts.index(stage) - 1] == CALLER, parts
                found.add(stage)
    assert found == set(expected)


@pytest.mark.parametrize("backend", sorted(CASES))
def test_stage_scopes_leave_stage_counts(backend):
    _, expected = CASES[backend]
    _, counts = _compiled(backend)
    assert {k: v for k, v in counts.items() if isinstance(k, str)} == \
        expected
