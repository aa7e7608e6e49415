"""Distributed tests (subprocess with a forced multi-device host platform,
so the main pytest process keeps its single real device)."""
import os
import subprocess
import sys

import pytest

_SCRIPT_NFFT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, re
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
from repro.conv import plan_conv
from repro.core import conv2d_direct
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((4, 8, 28, 28)), jnp.float32)
k = jnp.asarray(rng.standard_normal((8, 8, 3, 3)), jnp.float32)
y0 = conv2d_direct(x, k, padding=1)
for strat in ("nfft", "wfft"):
    f = jax.jit(plan_conv(x.shape, k.shape, schedule=strat, mesh=mesh,
                          padding=1))
    y = f(x, k)
    err = float(jnp.max(jnp.abs(y - y0))) / float(jnp.max(jnp.abs(y0)))
    assert err < 1e-4, (strat, err)
    hlo = f.lower(x, k).compile().as_text()
    kinds = set(re.findall(
        r"(all-to-all|all-reduce|all-gather|reduce-scatter)", hlo))
    if strat == "nfft":
        assert "all-to-all" in kinds, kinds
        assert "all-reduce" not in kinds, ("nfft must keep the CGEMM "
                                           "collective-free", kinds)
    else:
        assert "all-reduce" in kinds, kinds
# Regression for the replicate_kernel_transform stage-4 Cout (previously a
# dead conditional): with n_model=4 > 1 the replicated path must still
# invert a C'/N output slab per rank and match the oracle.
f = jax.jit(plan_conv(x.shape, k.shape, schedule="nfft", mesh=mesh,
                      padding=1, replicate_kernel_transform=True))
y = f(x, k)
err = float(jnp.max(jnp.abs(y - y0))) / float(jnp.max(jnp.abs(y0)))
assert err < 1e-4, ("nfft_repG", err)
hlo = f.lower(x, k).compile().as_text()
assert "all-reduce" not in hlo, "repG must not introduce an all-reduce"
# the collectives move exactly the compact spectrum's bytes per rank:
# P = 130 points (132 padded to the 4-way model axis for nfft), 2x2
# tiles of 14 outputs, 2 images and 2 of the 8 channels per rank, re/im
from repro.conv import analyze
rows = 2 * 4
want = {"nfft": 2 * 132 * rows * 2 * 4            # D boundary
                + 2 * 33 * rows * 8 * 4            # Z boundary
                + 2 * 132 * 8 * 2 * 4,             # kernel boundary
        "wfft": 2 * 130 * rows * 8 * 4}            # the hot psum pair
for strat, nbytes in want.items():
    prof = analyze(plan_conv(x.shape, k.shape, schedule=strat, mesh=mesh,
                             padding=1))
    assert prof.collective_bytes == prof.counted_collective_bytes == nbytes, \
        (strat, prof.collective_bytes, prof.counted_collective_bytes)
    assert prof.check().ok, prof.check().violations
print("DIST_OK")
"""

_SCRIPT_TRAIN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
from repro.configs import get_config
from repro.optim import AdamWConfig
from repro.train import make_train_step, init_train_state
from repro.launch import shardings as SH
from repro.models.common import ShapeCell
from repro.parallel.act_sharding import activation_sharding
import dataclasses
cfg = get_config("qwen3-14b", smoke=True)
cfg = dataclasses.replace(cfg, n_heads=8, n_kv=4, pad_heads=8, d_model=128,
                          head_dim=16, d_ff=256)
params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
cell = ShapeCell("t", 16, 4, "train")
pspec = SH.named(mesh, SH.param_specs(cfg, params, mesh, fsdp=False))
ospec = {"mu": pspec, "nu": pspec, "step": SH.named(mesh, P())}
bspec = SH.named(mesh, SH.batch_specs(cfg, cell, mesh))
step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3, total_steps=5)),
               in_shardings=(pspec, ospec, bspec),
               out_shardings=(pspec, ospec, None))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16))),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)))}
with activation_sharding(mesh):
    params, opt, m = step(params, opt, batch)
loss_sharded = float(m["loss"])
# single-device reference
params0, opt0 = init_train_state(cfg, jax.random.PRNGKey(0))
step0 = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3, total_steps=5)))
_, _, m0 = step0(params0, opt0, batch)
assert abs(loss_sharded - float(m0["loss"])) < 1e-2, (loss_sharded,
                                                      float(m0["loss"]))
print("TRAIN_DIST_OK", loss_sharded)
"""


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_nfft_wfft_distributed_correct_and_collective_profile():
    out = _run(_SCRIPT_NFFT)
    assert "DIST_OK" in out


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = _run(_SCRIPT_TRAIN)
    assert "TRAIN_DIST_OK" in out
