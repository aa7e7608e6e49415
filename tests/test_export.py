"""AOT-exported plan artifacts (``repro.conv.export``): round-trip
parity against live-planned execution, compatibility-mismatch fallback,
fingerprint certification, the spec-first kwarg unification, the
``keystr`` checkpoint key fix, and plan artifacts riding checkpoints."""
import json
import os
import subprocess
import sys
import warnings
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.conv import (
    Epilogue, NetworkConv, export_network, load_network, plan_conv,
    plan_network,
)
from repro.conv import export as planx

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _rand(shape, seed=0, s=0.5):
    return jnp.asarray(
        s * np.random.default_rng(seed).standard_normal(shape),
        jnp.float32)


def _net(schedule="auto", mesh=None, batch=2, image=8):
    layers = [
        NetworkConv("c1", (batch, 2, image, image), (4, 2, 3, 3),
                    padding=1, epilogue=Epilogue(bias=True,
                                                 activation="relu")),
        NetworkConv("c2", (batch, 4, image, image), (4, 4, 3, 3),
                    padding=1),
    ]
    return plan_network(layers, backend="fft-xla", schedule=schedule,
                        mesh=mesh)


def _params():
    return {"c1": _rand((4, 2, 3, 3), 1), "c2": _rand((4, 4, 3, 3), 2)}


def _run_live(net, prepared_net, x, bias):
    y = prepared_net["c1"](x, bias=bias)
    return prepared_net["c2"](y)


# --------------------------------------------------------------------------
# Round-trip parity: {local, nfft} x {prepared, raw}
# --------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", ["local", "nfft"])
@pytest.mark.parametrize("prepared", [True, False])
def test_roundtrip_parity(tmp_path, schedule, prepared):
    mesh = make_mesh((1, 1), ("data", "model")) \
        if schedule == "nfft" else None
    net = _net(schedule=schedule, mesh=mesh)
    params = _params()
    path = str(tmp_path / "net.rpa")
    net.export(path, params=params if prepared else None,
               weights_version=3)

    prep = net.prepare(params, weights_version=3)
    x = _rand((2, 2, 8, 8), 7, s=1.0)
    bias = _rand((4,), 9)
    want = _run_live(net, prep, x, bias)

    loaded = load_network(path)
    assert loaded.source == "aot"
    assert loaded.weights_version == 3
    if prepared:
        got = loaded["c2"](loaded["c1"](x, bias=bias))
    else:
        got = loaded["c2"](loaded["c1"](x, params["c1"], bias=bias),
                           params["c2"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_loaded_layer_arg_conventions(tmp_path):
    net = _net()
    params = _params()
    path = str(tmp_path / "net.rpa")
    net.export(path, params=params)
    loaded = load_network(path)
    x = _rand((2, 2, 8, 8), 3)
    with pytest.raises(TypeError, match="takes only x"):
        loaded["c1"](x, params["c1"], bias=_rand((4,), 1))
    with pytest.raises(ValueError, match="bias"):
        loaded["c1"](x)                     # epilogue declares bias
    with pytest.raises(ValueError, match="bias"):
        loaded["c2"](x, bias=_rand((4,), 1))   # c2 has no bias


# --------------------------------------------------------------------------
# Native-executable fast path and its StableHLO fallback
# --------------------------------------------------------------------------

def test_native_exe_and_stablehlo_agree(tmp_path):
    net = _net()
    params = _params()
    path = str(tmp_path / "net.rpa")
    net.export(path, params=params)
    man = planx.read_manifest(path)
    entries = man["nets"]["net"]["layers"]
    assert all(e.get("exe") for e in entries.values()), \
        "export should ship native executables on this backend"

    x = _rand((2, 2, 8, 8), 5, s=1.0)
    bias = _rand((4,), 6)
    native = load_network(path)
    assert all(lc.native for lc in native.layers.values())
    y_native = native["c2"](native["c1"](x, bias=bias))

    # sabotage the exe blobs -> per-layer fallback to the portable module
    broken = str(tmp_path / "noexe.rpa")
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(broken, "w") as zout:
        for m in zin.namelist():
            data = zin.read(m)
            if m.startswith("exe/"):
                data = b"not a pickle"
            zout.writestr(m, data)
    portable = load_network(broken)
    assert portable.source == "aot"
    assert not any(lc.native for lc in portable.layers.values())
    y_port = portable["c2"](portable["c1"](x, bias=bias))
    np.testing.assert_allclose(np.asarray(y_native), np.asarray(y_port),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Fresh-process rehydration (the actual fleet cold-start path)
# --------------------------------------------------------------------------

_SUBPROC = r"""
import json, sys
import jax.numpy as jnp
import numpy as np
from repro.conv import load_network
loaded = load_network(sys.argv[1])
assert loaded.source == "aot", loaded.source
rng = np.random.default_rng(7)
x = jnp.asarray(0.5 * rng.standard_normal((2, 2, 8, 8)), jnp.float32)
rng9 = np.random.default_rng(9)
bias = jnp.asarray(0.5 * rng9.standard_normal((4,)), jnp.float32)
y = loaded["c2"](loaded["c1"](x, bias=bias))
print("RESULT" + json.dumps(np.asarray(y).ravel().tolist()))
"""


def test_subprocess_bitwise_parity(tmp_path):
    net = _net()
    params = _params()
    path = str(tmp_path / "net.rpa")
    net.export(path, params=params)

    prep = net.prepare(params, weights_version=None)
    rng = np.random.default_rng(7)
    x = jnp.asarray(0.5 * rng.standard_normal((2, 2, 8, 8)), jnp.float32)
    rng9 = np.random.default_rng(9)
    bias = jnp.asarray(0.5 * rng9.standard_normal((4,)), jnp.float32)
    want = np.asarray(_run_live(net, prep, x, bias))

    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _SUBPROC, path],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    got = np.asarray(json.loads(line[len("RESULT"):]),
                     np.float32).reshape(want.shape)
    # same device kind, same jax, same module: bitwise
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# Compatibility mismatch -> live fallback (or error)
# --------------------------------------------------------------------------

def _tamper(path, out, **fields):
    with zipfile.ZipFile(path) as zin, zipfile.ZipFile(out, "w") as zout:
        for m in zin.namelist():
            data = zin.read(m)
            if m == "manifest.json":
                man = json.loads(data)
                man.update(fields)
                data = json.dumps(man)
            zout.writestr(m, data)
    return out


@pytest.mark.parametrize("fields", [
    {"jax_version": "0.0.1"},
    {"device_kind": "TPU v9000"},
    {"artifact_version": 1},        # written while plans stored a spectrum
])
def test_mismatch_falls_back_to_live(tmp_path, fields):
    net = _net()
    params = _params()
    path = str(tmp_path / "net.rpa")
    net.export(path, params=params, weights_version=1)
    bad = _tamper(path, str(tmp_path / "bad.rpa"), **fields)

    with pytest.warns(UserWarning, match="falling back to live planning"):
        loaded = load_network(bad)
    assert loaded.source == "live"

    x = _rand((2, 2, 8, 8), 7, s=1.0)
    bias = _rand((4,), 9)
    prep = net.prepare(params, weights_version=1)
    want = _run_live(net, prep, x, bias)
    got = loaded["c2"](loaded["c1"](x, bias=bias))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    with pytest.raises(planx.ArtifactMismatch):
        load_network(bad, on_mismatch="error")
    with pytest.raises(ValueError, match="on_mismatch"):
        load_network(bad, on_mismatch="explode")


def test_verify_fingerprints(tmp_path):
    net = _net()
    path = str(tmp_path / "net.rpa")
    net.export(path, params=_params())
    v = planx.verify(path)
    assert v["ok"] and v["n_checked"] == 2 and not v["mismatches"]

    # corrupt one stamp -> verify names the layer
    with zipfile.ZipFile(path) as zf:
        man = json.loads(zf.read("manifest.json"))
    man["nets"]["net"]["layers"]["c1"]["fingerprint"] = "sha256:bogus"
    bad = str(tmp_path / "bad.rpa")
    with zipfile.ZipFile(path) as zin, zipfile.ZipFile(bad, "w") as zout:
        for m in zin.namelist():
            zout.writestr(m, json.dumps(man) if m == "manifest.json"
                          else zin.read(m))
    v = planx.verify(bad)
    assert not v["ok"]
    assert [m["layer"] for m in v["mismatches"]] == ["c1"]


def test_bucketed_export_labels(tmp_path):
    def make_layers(b):
        return [NetworkConv("c1", (b, 2, 8, 8), (4, 2, 3, 3), padding=1)]
    nets = plan_network(make_layers, buckets=(1, 2), backend="fft-xla")
    path = str(tmp_path / "b.rpa")
    nets.export(path, params={"c1": _rand((4, 2, 3, 3), 1)})
    loaded = load_network(path)
    assert sorted(loaded) == ["b1", "b2"]
    assert loaded["b2"]["c1"].x_shape == (2, 2, 8, 8)


# --------------------------------------------------------------------------
# Spec-first kwarg unification (plan_conv / tune take a ConvSpec)
# --------------------------------------------------------------------------

def test_plan_conv_spec_first():
    from repro.core.conv_spec import ConvSpec
    spec = ConvSpec(B=2, C=2, Cout=4, H=8, W=8, kh=3, kw=3,
                    pad_h=1, pad_w=1)
    a = plan_conv(spec, backend="fft-xla")
    b = plan_conv((2, 2, 8, 8), (4, 2, 3, 3), padding=1,
                  backend="fft-xla")
    assert a is b                       # identical cache entry
    with pytest.raises(TypeError, match="already carries"):
        plan_conv(spec, (4, 2, 3, 3))
    with pytest.raises(TypeError, match="k_shape"):
        plan_conv((2, 2, 8, 8))


def test_tune_spec_first(tmp_path, monkeypatch):
    from repro.conv import autotune
    from repro.core.conv_spec import ConvSpec
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE_REPS", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_BUDGET_MS", "200")
    spec = ConvSpec(B=1, C=2, Cout=2, H=8, W=8, kh=3, kw=3)
    cfg = autotune.tune(spec, reps=1)
    cfg2 = autotune.tune((1, 2, 8, 8), (2, 2, 3, 3), padding=(0, 0),
                         reps=1)
    assert cfg.backend == cfg2.backend
    assert cfg.schedule == cfg2.schedule
    with pytest.raises(TypeError, match="already carries"):
        autotune.tune(spec, (2, 2, 3, 3))


# --------------------------------------------------------------------------
# Checkpoint keys: keystr fix + legacy restore + plan artifacts
# --------------------------------------------------------------------------

def test_checkpoint_keystr_roundtrip(tmp_path):
    import collections
    from repro import checkpoint
    Pair = collections.namedtuple("Pair", ["w", "b"])
    tree = {
        "a": {"b": jnp.arange(3.0)},
        "a.b": jnp.arange(4.0),            # collides under the old join
        "lst": [jnp.ones((2,)), Pair(w=jnp.zeros((2, 2)),
                                     b=jnp.full((1,), 7.0))],
    }
    d = str(tmp_path / "ck")
    checkpoint.save(d, 1, tree, weights_version=5)
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    got, meta = checkpoint.restore(d, 1, like)
    assert meta["weights_version"] == 5
    assert meta["format"] == 2
    flat_a, _ = jax.tree_util.tree_flatten(tree)
    flat_b, _ = jax.tree_util.tree_flatten(got)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_legacy_layout_restores(tmp_path):
    from repro import checkpoint
    tree = {"w": jnp.arange(4.0), "inner": {"b": jnp.ones((2,))}}
    d = str(tmp_path / "ck" / "step_00000003")
    os.makedirs(d)
    # hand-write the pre-keystr layout: <joined-key>.npy, no files map
    np.save(os.path.join(d, "w.npy"), np.arange(4.0, dtype=np.float32))
    np.save(os.path.join(d, "inner.b.npy"), np.ones((2,), np.float32))
    with open(os.path.join(d, "meta.json"), "w") as f:
        json.dump({"step": 3, "keys": ["inner.b", "w"], "extra": {}}, f)
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    got, meta = checkpoint.restore(str(tmp_path / "ck"), 3, like)
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.arange(4.0, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(got["inner"]["b"]),
                                  np.ones((2,), np.float32))


def test_plan_artifact_rides_checkpoint(tmp_path):
    from repro import checkpoint
    net = _net()
    params = _params()
    d = str(tmp_path / "ck")
    with pytest.raises(FileNotFoundError, match="save the weights"):
        checkpoint.save_plan_artifact(d, 2, net, params)
    checkpoint.save(d, 2, params, weights_version=2)
    assert not checkpoint.has_plan_artifact(d, 2)
    checkpoint.save_plan_artifact(d, 2, net, params)
    assert checkpoint.has_plan_artifact(d, 2)
    loaded = checkpoint.load_plan_artifact(d, 2)
    assert loaded.source == "aot"
    assert loaded.weights_version == 2      # defaults to the step
    with pytest.raises(FileNotFoundError, match="no plan artifact"):
        checkpoint.load_plan_artifact(d, 99)


# --------------------------------------------------------------------------
# ServeEngine: export_plans / load_plans
# --------------------------------------------------------------------------

def _engine_bits():
    def make_layers(b):
        return [
            NetworkConv("s1", (b, 2, 8, 8), (4, 2, 3, 3), padding=1),
            NetworkConv("s2", (b, 4, 8, 8), (4, 4, 3, 3), padding=1),
        ]

    params = {"s1": _rand((4, 2, 3, 3), 1), "s2": _rand((4, 4, 3, 3), 2)}
    return make_layers, params


def test_engine_export_load_parity_zero_misses(tmp_path):
    from repro.conv.plan import plan_cache_info
    from repro.launch.batcher import BucketPolicy, ServeEngine
    make_layers, params = _engine_bits()
    policy = BucketPolicy(max_batch=2)
    live = ServeEngine(make_layers, params, policy=policy,
                       backend="fft-xla", collect_results=True)
    path = str(tmp_path / "plans.rpa")
    live.export_plans(path)

    aot = ServeEngine(make_layers, params, policy=policy,
                      backend="fft-xla", collect_results=True,
                      load_plans=path)
    assert aot.plan_source == "aot"
    with pytest.raises(RuntimeError, match="export_plans"):
        aot.export_plans(str(tmp_path / "again.rpa"))

    x = _rand((2, 2, 8, 8), 11, s=1.0)
    misses0 = plan_cache_info().misses
    ra = aot.submit(x)
    rl = live.submit(x)
    aot.drain()
    live.drain()
    assert plan_cache_info().misses == misses0   # nothing planned
    np.testing.assert_allclose(np.asarray(aot.results[ra]),
                               np.asarray(live.results[rl]),
                               rtol=1e-5, atol=1e-5)
    assert aot.report()["plan_cache_misses_after_warmup"] == 0

    # weight update drops the artifact and re-plans live
    params2 = {k: v + 0.01 for k, v in params.items()}
    aot.update_weights(params2, weights_version=1)
    assert aot.plan_source == "live"
    r2 = aot.submit(x)
    aot.drain()
    assert np.isfinite(np.asarray(aot.results[r2])).all()


def test_engine_stale_artifact_falls_back(tmp_path):
    from repro.launch.batcher import BucketPolicy, ServeEngine
    make_layers, params = _engine_bits()
    policy = BucketPolicy(max_batch=2)
    live = ServeEngine(make_layers, params, policy=policy,
                      backend="fft-xla")
    path = str(tmp_path / "plans.rpa")
    live.export_plans(path)

    with pytest.warns(UserWarning, match="falling back to live"):
        eng = ServeEngine(make_layers, params, policy=policy,
                          backend="fft-xla", load_plans=path,
                          weights_version=99)     # artifact holds None
    assert eng.plan_source == "live"
    rep = eng.report()
    assert rep["plan_source"] == "live"
    assert rep["startup_s"] > 0
