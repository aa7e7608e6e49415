"""Substrate tests: optimizer, data determinism, checkpoint/restart,
fault tolerance, roofline HLO parsing."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAVE_HYPOTHESIS, requires_hypothesis

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.data import DataConfig, lm_batch, image_batch
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_lr
from repro.train import make_train_step, init_train_state, cross_entropy
import repro.checkpoint as ckpt
from repro.launch.roofline import parse_collectives, roofline_terms


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------

def test_adamw_matches_reference():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      clip_norm=1e9, warmup_steps=0, total_steps=1,
                      min_lr_frac=1.0)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.25])}
    st_ = adamw_init(p)
    new_p, st_, _ = adamw_update(g, st_, p, cfg)
    # reference AdamW step 1
    m = 0.1 * np.asarray([0.5, 0.25])
    v = 0.01 * np.asarray([0.25, 0.0625])
    mh, vh = m / 0.1, v / 0.01
    ref = np.asarray([1.0, -2.0]) - 0.1 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), ref, rtol=1e-5)


def test_grad_clip_caps_update():
    cfg = AdamWConfig(lr=1.0, clip_norm=1.0, warmup_steps=0, total_steps=1,
                      min_lr_frac=1.0, weight_decay=0.0)
    p = {"w": jnp.zeros((4,))}
    g = {"w": 100.0 * jnp.ones((4,))}
    _, _, m = adamw_update(g, adamw_init(p), p, cfg)
    assert float(m["grad_norm"]) == pytest.approx(200.0, rel=1e-4)


if HAVE_HYPOTHESIS:
    @requires_hypothesis
    @settings(max_examples=20, deadline=None)
    @given(step=st.integers(0, 9999))
    def test_cosine_schedule_bounds(step):
        cfg = AdamWConfig(lr=1e-3, warmup_steps=100, total_steps=10000,
                          min_lr_frac=0.1)
        lr = float(cosine_lr(cfg, jnp.int32(step)))
        assert 0.0 <= lr <= cfg.lr * (1 + 1e-6)
        if step >= cfg.warmup_steps:
            assert lr >= cfg.lr * cfg.min_lr_frac * (1 - 1e-6)
else:
    @requires_hypothesis
    def test_cosine_schedule_bounds():
        pass


def test_cross_entropy_reference():
    logits = jnp.asarray(np.random.default_rng(0)
                         .standard_normal((2, 3, 7)), jnp.float32)
    labels = jnp.asarray([[1, 2, 3], [0, 6, 5]], jnp.int32)
    ce = cross_entropy(logits, labels, z_loss=0.0)
    lp = jax.nn.log_softmax(logits)
    ref = -np.mean([lp[b, s, labels[b, s]] for b in range(2)
                    for s in range(3)])
    assert float(ce) == pytest.approx(float(ref), rel=1e-5)


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_data_deterministic_and_seekable():
    dc = DataConfig(vocab=100, seq_len=17, global_batch=4, seed=7)
    b1, b2 = lm_batch(dc, 5), lm_batch(dc, 5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = lm_batch(dc, 6)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


if HAVE_HYPOTHESIS:
    @requires_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(step=st.integers(0, 10000), seed=st.integers(0, 100))
    def test_data_tokens_in_range(step, seed):
        dc = DataConfig(vocab=64, seq_len=9, global_batch=2, seed=seed)
        b = lm_batch(dc, step)
        t = np.asarray(b["tokens"])
        assert t.min() >= 0 and t.max() < 64
else:
    @requires_hypothesis
    def test_data_tokens_in_range():
        pass


# --------------------------------------------------------------------------
# checkpoint / fault tolerance
# --------------------------------------------------------------------------

def _tiny_train(steps, params, opt, step_fn, dc, start=0):
    for i in range(start, steps):
        params, opt, m = step_fn(params, opt, lm_batch(dc, i))
    return params, opt, float(m["loss"])


def test_crash_restart_is_bit_exact():
    cfg = get_config("qwen3-14b", smoke=True)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
    dc = DataConfig(vocab=cfg.vocab, seq_len=17, global_batch=4, seed=1)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg))
    params, opt, _ = _tiny_train(6, params, opt, step_fn, dc)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 6, {"p": params, "o": opt})
        # continue uninterrupted
        pa, oa, loss_a = _tiny_train(10, params, opt, step_fn, dc, start=6)
        # "crash" + restore + continue
        state, meta = ckpt.restore(d, 6, {"p": params, "o": opt})
        pb, ob, loss_b = _tiny_train(10, state["p"], state["o"], step_fn,
                                     dc, start=6)
    assert loss_a == loss_b
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_checkpoint_and_latest():
    with tempfile.TemporaryDirectory() as d:
        t = ckpt.save_async(d, 3, {"x": jnp.arange(5)})
        t.join()
        ckpt.save(d, 7, {"x": jnp.arange(5) * 2})
        assert ckpt.latest_step(d) == 7
        state, meta = ckpt.restore(d, 7, {"x": jnp.zeros(5, jnp.int32)})
        np.testing.assert_array_equal(np.asarray(state["x"]),
                                      np.arange(5) * 2)


def test_atomic_commit_ignores_partial(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"x": jnp.ones(3)})
    # simulate a crash mid-write: stray .tmp dir must be ignored
    os.makedirs(os.path.join(d, "step_00000002.tmp"))
    assert ckpt.latest_step(d) == 1


# --------------------------------------------------------------------------
# roofline HLO parsing
# --------------------------------------------------------------------------

_FAKE_HLO = """
HloModule m

%cond.1 (a: s32[]) -> pred[] {
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(s32[] %a, s32[] %c), direction=LT
}

%body.2 (a: s32[]) -> s32[] {
  %ar = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %x), replica_groups={}
  ROOT %n = s32[] add(s32[] %a, s32[] %one)
}

ENTRY %main () -> f32[] {
  %ag = bf16[2,2]{1,0} all-gather(bf16[1,2]{1,0} %p), dimensions={0}
  %w = s32[] while(s32[] %z), condition=%cond.1, body=%body.2
  ROOT %r = f32[] constant(0)
}
"""


def test_parse_collectives_counts_loop_trips():
    out = parse_collectives(_FAKE_HLO)
    # all-gather once: 2*2*2 = 8 bytes; all-reduce inside while x10:
    # 4*8*4 = 128 bytes * 10 = 1280
    assert out["bytes"]["all-gather"] == 8
    assert out["bytes"]["all-reduce"] == 1280
    assert out["total_bytes"] == 1288


def test_roofline_terms_dominance():
    t = roofline_terms(197e12 * 2, 819e9, 50e9 * 3,
                       device_kind="TPU v5 lite")
    assert t["dominant"] == "collective"
    assert t["bound_s"] == pytest.approx(3.0)
    with pytest.raises(KeyError, match="no peak figures"):
        roofline_terms(1.0, 1.0, 1.0, device_kind="cpu")


# --------------------------------------------------------------------------
# compile cache placement
# --------------------------------------------------------------------------

def test_compile_cache_dir_from_environment_wins(monkeypatch, tmp_path):
    from repro.launch.env import compile_cache_dir
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_dir_defaults_to_the_checkout(monkeypatch):
    from repro.launch.env import compile_cache_dir
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    # set, then delete: monkeypatch then removes the variable the helper
    # exports when the test ends
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache_dir() == want
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache_dir() == want          # a fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
