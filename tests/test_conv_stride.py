"""Strided convolution in the planner: ``plan_conv``/``plan_network``
take ``stride``, ``auto`` and ``tuned`` plan it on ``direct``, the FFT
backends refuse it, it enters the plan-cache key, the export manifest and
the tuner's signature, and plan-lint certifies that no strided layer runs
on an FFT pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.conv import (
    Epilogue, NetworkConv, autotune, load_network, plan_cache_info,
    plan_conv, plan_network,
)
from repro.conv.analyze import analyze, seeded_violation
from repro.conv.plan import predicted_s
from repro.core.conv_spec import ConvSpec


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _lax(x, k, pad, stride):
    return jax.lax.conv_general_dilated(
        x, k, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("size", [15, 16])
@pytest.mark.parametrize("k,pad", [(1, 0), (3, 1), (7, 3), (3, 0), (1, 1),
                                   (7, 0)])
def test_strided_plan_matches_lax(k, pad, size):
    x = _rand((2, 3, size, size), 0)
    w = _rand((5, 3, k, k), 1)
    plan = plan_conv(x.shape, w.shape, padding=pad, stride=2)
    want = _lax(x, w, pad, 2)
    assert plan.backend == "direct"
    assert plan.out_shape == want.shape
    np.testing.assert_allclose(plan(x, w), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(plan.prepare(w)(x), want, rtol=1e-5,
                               atol=1e-5)


def test_stride_three_and_spec_geometry():
    spec = ConvSpec(B=1, C=2, Cout=2, H=17, W=12, kh=3, kw=3, pad_h=1,
                    pad_w=1, stride=3)
    assert (spec.Ho, spec.Wo) == (6, 4)
    x, w = _rand((1, 2, 17, 12), 2), _rand((2, 2, 3, 3), 3)
    plan = plan_conv(spec)
    assert plan.out_shape == (1, 2, 6, 4)
    np.testing.assert_allclose(plan(x, w), _lax(x, w, 1, 3), rtol=1e-5,
                               atol=1e-5)
    with pytest.raises(ValueError, match="stride"):
        ConvSpec(B=1, C=2, Cout=2, H=8, W=8, kh=3, kw=3, stride=0)
    with pytest.raises(TypeError, match="already carries"):
        plan_conv(spec, stride=2)


def test_strided_epilogue_and_grad():
    x, w = _rand((2, 4, 12, 12), 4), _rand((6, 4, 3, 3), 5)
    b, r = _rand((6,), 6), _rand((2, 6, 6, 6), 7)
    plan = plan_conv(x.shape, w.shape, padding=1, stride=2,
                     epilogue=Epilogue(bias=True, activation="relu",
                                       residual=True))
    want = jax.nn.relu(_lax(x, w, 1, 2) + b[None, :, None, None] + r)
    np.testing.assert_allclose(plan(x, w, bias=b, residual=r), want,
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda a: jnp.sum(plan(a, w, bias=b, residual=r) ** 2))(x)
    g0 = jax.grad(lambda a: jnp.sum(jax.nn.relu(
        _lax(a, w, 1, 2) + b[None, :, None, None] + r) ** 2))(x)
    np.testing.assert_allclose(g, g0, rtol=1e-4, atol=1e-4)


def test_plan_network_strided_layers_match_lax():
    ep = Epilogue(bias=True, activation="relu")
    net = plan_network([
        NetworkConv("stem", (2, 3, 16, 16), (8, 3, 7, 7), padding=3,
                    epilogue=ep, stride=2),
        NetworkConv("body", (2, 8, 8, 8), (8, 8, 3, 3), padding=1,
                    epilogue=ep),
        NetworkConv("down", (2, 8, 8, 8), (16, 8, 1, 1), stride=2),
    ], backend="auto")
    assert net["stem"].backend == net["down"].backend == "direct"
    assert net["down"].out_shape == (2, 16, 4, 4)
    ks = {"stem": _rand((8, 3, 7, 7), 8), "body": _rand((8, 8, 3, 3), 9),
          "down": _rand((16, 8, 1, 1), 10)}
    b = {"stem": _rand((8,), 11), "body": _rand((8,), 12)}
    x = _rand((2, 3, 16, 16), 13)
    prep = net.prepare(ks)
    y = prep["down"](prep["body"](prep["stem"](x, bias=b["stem"]),
                                  bias=b["body"]))
    relu = jax.nn.relu
    h = relu(_lax(x, ks["stem"], 3, 2) + b["stem"][None, :, None, None])
    h = relu(_lax(h, ks["body"], 1, 1) + b["body"][None, :, None, None])
    np.testing.assert_allclose(y, _lax(h, ks["down"], 0, 2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("backend", ["auto", "tuned"])
def test_auto_and_tuned_plan_strided_layers_on_direct(backend):
    plan = plan_conv((8, 64, 56, 56), (64, 64, 3, 3), padding=1, stride=2,
                     backend=backend)
    assert plan.backend == "direct" and plan.spec.stride == 2
    # at unit stride the same geometry is a choice, and predicted time
    # makes it: 64 channels run faster on direct than through the FFT
    unit = plan_conv((8, 64, 56, 56), (64, 64, 3, 3), padding=1,
                     backend="auto")
    t = predicted_s(unit.spec)
    assert unit.backend == "direct" and t["direct"] < t["fft-xla"]


@pytest.mark.parametrize("backend", ["fft-xla", "fft-pallas"])
def test_fft_backends_refuse_stride(backend):
    with pytest.raises(ValueError, match="stride 2.*only the 'direct'"):
        plan_conv((2, 4, 16, 16), (4, 4, 3, 3), padding=1, stride=2,
                  backend=backend)
    plan_conv((2, 4, 16, 16), (4, 4, 3, 3), padding=1, stride=1,
              backend=backend)


def test_stride_enters_the_plan_cache_key():
    args = ((2, 4, 16, 16), (4, 4, 3, 3))
    unit = plan_conv(*args, padding=1)
    assert plan_conv(*args, padding=1, stride=1) is unit
    misses = plan_cache_info().misses
    two = plan_conv(*args, padding=1, stride=2)
    assert two is not unit and two.spec.stride == 2
    assert plan_cache_info().misses == misses + 1
    assert plan_conv(*args, padding=1, stride=2) is two
    assert "stride=2" in two.describe() and "stride=1" in unit.describe()


def test_tuner_signature_carries_stride_and_keeps_unit_keys():
    sig = autotune.spec_signature((2, 4, 16, 16), (4, 4, 3, 3),
                                  padding=1)
    assert sig == autotune.spec_signature((2, 4, 16, 16), (4, 4, 3, 3),
                                          padding=1, stride=1)
    assert "stride" not in sig
    strided = autotune.spec_signature((2, 4, 16, 16), (4, 4, 3, 3),
                                      padding=1, stride=2)
    assert "|stride=2|" in strided and strided != sig
    spec = ConvSpec(B=2, C=4, Cout=4, H=16, W=16, kh=3, kw=3, pad_h=1,
                    pad_w=1, stride=2)
    assert [(c.backend, c.schedule) for c in autotune.candidates(spec)] \
        == [("direct", "local")]


def test_export_roundtrip_with_a_strided_layer(tmp_path):
    net = plan_network([
        NetworkConv("down", (2, 4, 12, 12), (8, 4, 3, 3), padding=1,
                    stride=2, epilogue=Epilogue(bias=True,
                                                activation="relu")),
        NetworkConv("body", (2, 8, 6, 6), (8, 8, 3, 3), padding=1),
    ], backend="auto")
    params = {"down": _rand((8, 4, 3, 3), 20), "body": _rand((8, 8, 3, 3),
                                                              21)}
    path = str(tmp_path / "net.rpa")
    net.export(path, params=params, weights_version=1)
    x, b = _rand((2, 4, 12, 12), 22), _rand((8,), 23)
    prep = net.prepare(params, weights_version=1)
    want = prep["body"](prep["down"](x, bias=b))
    loaded = load_network(path)
    got = loaded["body"](loaded["down"](x, bias=b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    from repro.conv.export import read_manifest, rebuild_plan
    (entry,) = [n["layers"]["down"]
                for n in read_manifest(path)["nets"].values()]
    assert entry["stride"] == 2
    assert rebuild_plan(entry) == net["down"]


def test_plan_lint_records_stride_and_certifies_it():
    strided = plan_conv((2, 4, 16, 16), (4, 4, 3, 3), padding=1, stride=2)
    prof = analyze(strided)
    assert prof.strides == (2,) and prof.to_dict()["strides"] == [2]
    assert prof.check().ok
    fft = analyze(plan_conv((2, 4, 16, 16), (4, 4, 3, 3), padding=1,
                            backend="fft-xla"))
    assert fft.strides == () and fft.check().ok
    assert "no-strided-fft" in fft.check().checked


def test_seeded_strided_fft_is_caught():
    with seeded_violation("strided-fft"):
        plan = plan_conv((2, 4, 16, 16), (4, 4, 3, 3), padding=1,
                         stride=2, backend="fft-xla", cache=False)
        prof = analyze(plan)
    assert prof.strides == (2,)
    names = [v.invariant for v in prof.check().violations]
    assert "no-strided-fft" in names
    with pytest.raises(ValueError, match="stride"):  # restored on exit
        plan_conv((2, 4, 16, 16), (4, 4, 3, 3), padding=1, stride=2,
                  backend="fft-xla", cache=False)


def test_plan_lint_gate_fails_when_a_pipeline_takes_a_stride(capsys):
    from repro.conv.analyze import main
    assert main(["--check", "--limit", "1", "--batch", "2"]) == 0
    capsys.readouterr()
    assert main(["--check", "--limit", "1", "--batch", "2",
                 "--inject", "strided-fft"]) == 1
    out = capsys.readouterr().out
    assert "stride2" in out and "no-strided-fft" in out
