"""The ``residual`` structure (ResNet-34) and its stride-aware counts, on
the CPU: the configuration plans the program's own network, a tiny run
agrees with the plain reference, the control fails the cell's limit, an
altered answer is not correct, and ``strided_work`` counts what ``work``
counts at unit stride and the stride-2 output sizes elsewhere."""
import copy

import jax.numpy as jnp
import pytest

import tiny
from bench import lib
from bench.lib import strided_work, work
from bench.lib.peaks import peaks

CELL = "resnet34.b128"


def _config(image: int = 32) -> dict:
    """``resnet34`` at its published widths and depth, its input cut to
    ``image`` pixels (every extent scaled; the pooled 1x1 map stays 1)."""
    cfg = copy.deepcopy(lib.load_json("configs", "resnet34.json"))
    for layer in cfg["layers"]:
        for key in ("H", "W"):
            layer[key] = max(1, layer[key] * image // cfg["image"])
    cfg["image"] = image
    return cfg


def test_configuration_plans_the_programs_network():
    from repro.models.resnet import resnet34_convs
    structure = lib.load_module("structures", "residual")
    cfg = lib.load_json("configs", "resnet34.json")
    assert structure.network_convs(cfg, 128) == resnet34_convs(128)
    assert cfg["reduced"] == {} and "batchnorm" in cfg["assumed"]
    assert [l["name"] for l in cfg["layers"] if l["stride"] == 2] == [
        "conv1", "layer2.0.downsample", "layer2.0.conv1",
        "layer3.0.downsample", "layer3.0.conv1", "layer4.0.downsample",
        "layer4.0.conv1"]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_control_fails_the_limit_the_program_meets(seed):
    limits = lib.load_json("cells", CELL + ".json")
    cfg = _config()
    r, loop = tiny.make_run("chain.closed", seed, cfg)
    assert loop.control(r) > limits["limits"]["max_rel_err"]
    out = tiny.run("chain.closed", seed=seed, cfg=cfg, limits=limits)
    assert out["correct"], out["check"]


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.conv.plan import PreparedConv
    call = PreparedConv.__call__

    def altered(self, x, **kw):
        y = call(self, x, **kw)
        return y.at[:, 0, 0, 0].add(1e-3 * jnp.max(jnp.abs(y)))
    monkeypatch.setattr(PreparedConv, "__call__", altered)
    out = tiny.run("chain.closed", cfg=_config(),
                   limits=lib.load_json("cells", CELL + ".json"))
    assert not out["correct"]
    assert out["check"]["max_rel_err"]["value"] > 1e-4


@pytest.mark.parametrize("config", ["vgg16", "table1_ar"])
def test_strided_work_equals_work_at_unit_stride(config):
    pk = peaks("TPU v5 lite")
    for layer in lib.load_json("configs", config + ".json")["layers"]:
        assert strided_work.out_hw(layer) == work.out_hw(layer)
        assert strided_work.conv_work(layer, 32) == work.conv_work(layer, 32)
        assert strided_work.conv_bytes(layer, 32) == \
            work.conv_bytes(layer, 32)
        assert strided_work.least_time_s(layer, 32, pk) == \
            work.least_time_s(layer, 32, pk)


def test_strided_work_counts_stride_two_outputs():
    layers = {l["name"]: l for l in lib.load_json(
        "configs", "resnet34.json")["layers"]}
    assert strided_work.out_hw(layers["conv1"]) == (112, 112)
    assert strided_work.out_hw(layers["layer2.0.conv1"]) == (28, 28)
    assert strided_work.out_hw(layers["layer3.0.downsample"]) == (14, 14)
    assert strided_work.out_hw(layers["layer4.0.conv1"]) == (7, 7)
    assert strided_work.out_hw(layers["fc"]) == (1, 1)
    # 2 * 128 * 3 * 64 * 112 * 112: a quarter of the unit-stride count
    assert strided_work.conv_work(layers["conv1"], 128) == 616_562_688
    assert work.conv_work(layers["conv1"], 128) == 4 * 616_562_688
    # 4 * (x 128*3*224*224 + k 64*3*7*7 + b 64 + y 128*64*112*112)
    assert strided_work.conv_bytes(layers["conv1"], 128) == 4 * (
        19_267_584 + 9_408 + 64 + 102_760_448)


def _ctx(scope_s, layers):
    return {"trace": {"scope_s": scope_s}, "cfg": {"layers": layers},
            "batch": 128, "steps": 10, "window_s": 2.0,
            "peaks": peaks("TPU v5 lite")}


def test_downsample_roofline_reads_only_the_strided_layers():
    metric = lib.load_module("metrics", "downsample_roofline")
    layers = lib.load_json("configs", "resnet34.json")["layers"]
    strided = [l for l in layers if l["stride"] > 1]
    scope_s = {l["name"]: 0.01 for l in layers}
    floor = sum(strided_work.least_time_s(l, 128, peaks("TPU v5 lite"))
                for l in strided)
    got = metric.read(_ctx(scope_s, layers))
    assert got == pytest.approx(100 * floor * 10 / (0.01 * len(strided)))
    chain = lib.load_json("configs", "vgg16.json")["layers"]
    assert metric.read(_ctx({l["name"]: 0.01 for l in chain}, chain)) \
        is None
    assert metric.read(dict(_ctx({}, layers), trace=None)) is None
    everything = lib.load_module("metrics", "conv_roofline.strided")
    assert everything.read(_ctx({}, layers)) is None
    assert 0 < everything.read(_ctx(scope_s, layers)) < got


def test_step_mfu_strided_counts_each_layer_at_its_stride():
    metric = lib.load_module("metrics", "step_mfu.strided")
    layers = lib.load_json("configs", "resnet34.json")["layers"]
    w = sum(strided_work.conv_work(l, 128) for l in layers)
    assert metric.read(_ctx({}, layers)) == pytest.approx(
        100 * w * 10 / 2.0 / 197e12)
