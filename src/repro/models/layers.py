"""Neural-net layers shared by the ten assigned architectures.

Pure functions over param pytrees (dicts of jnp arrays). Conventions:
  * params are float32; compute dtype per ModelConfig (bf16 default).
  * RoPE is the interleaved-pair form (shard-friendly along head_dim:
    pairs are adjacent, so a head_dim shard of >=2 never splits a pair).
  * attention is either `attend_full` (materialised scores; decode and
    short-seq train) or `attend_flash` (online-softmax block scan; long
    prefill, with a banded fast path for sliding-window layers).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.parallel.act_sharding import constrain

NEG_INF = -2.3819763e38   # most-negative bf16-representable


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, shape, scale=0.02):
    return (scale * jax.random.truncated_normal(key, -2, 2, shape,
                                                jnp.float32))


def split_keys(key, n):
    return list(jax.random.split(key, n))


# --------------------------------------------------------------------------
# convolution (through the plan/execute engine)
# --------------------------------------------------------------------------

def conv2d_planned(x, k, *, padding=1, backend="auto", schedule="auto",
                   mesh=None, compute_dtype=None, weights_version=None):
    """NCHW convolution through ``repro.conv`` for model layers.

    Training (``weights_version=None``): executes ``plan(x, k)`` — fully
    differentiable in ``x`` and ``k`` via the plan-level VJP, on every
    backend x schedule.

    Serving (``weights_version`` given, e.g. the train step the weights
    were loaded from): executes a *prepared* plan — the kernel transform is
    cached under (plan, version) and skipped on every call; passing a new
    version after a weight update invalidates and re-prepares.
    """
    from repro.conv import plan_conv
    plan = plan_conv(tuple(x.shape), tuple(k.shape), padding=padding,
                     backend=backend, schedule=schedule, mesh=mesh,
                     compute_dtype=compute_dtype)
    if weights_version is None:
        return plan(x, k)
    return plan.prepare(k, weights_version=weights_version)(x)


def maxpool2x2(x):
    """2x2/stride-2 max pool over the spatial axes of NCHW ``x``."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, 2, 2), (1, 1, 2, 2), "VALID")


def maxpool3x3s2(x):
    """3x3/stride-2 max pool with 1 pixel of padding (which never wins)
    over the spatial axes of NCHW ``x``: the ResNet stem's pool."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, 3, 3), (1, 1, 2, 2),
                                 ((0, 0), (0, 0), (1, 1), (1, 1)))


def global_avgpool(x):
    """Mean over the spatial axes of NCHW ``x``, kept as a 1x1 map."""
    return jnp.mean(x, axis=(2, 3), keepdims=True)


def conv_block(x, k, bias=None, *, activation="none", residual=None,
               padding=1, backend="auto", schedule="auto", mesh=None,
               compute_dtype=None, weights_version=None):
    """Conv + bias + activation (+ residual) as ONE fused plan.

    The elementwise tail is an ``Epilogue`` frozen into the plan and
    executed inside the pipeline's stage 4 — on the local output slab,
    before the f32 -> x.dtype cast, with zero extra collectives under the
    sharded schedules — instead of separate XLA ops on the gathered
    output.  Differentiable in ``x``, ``k`` AND ``bias``/``residual`` via
    the plan-level VJP; ``weights_version`` routes through a prepared plan
    exactly like ``conv2d_planned``.
    """
    from repro.conv import Epilogue, plan_conv
    ep = Epilogue(bias=bias is not None, activation=activation,
                  residual=residual is not None)
    plan = plan_conv(tuple(x.shape), tuple(k.shape), padding=padding,
                     backend=backend, schedule=schedule, mesh=mesh,
                     compute_dtype=compute_dtype, epilogue=ep)
    if weights_version is None:
        return plan(x, k, bias=bias, residual=residual)
    return plan.prepare(k, weights_version=weights_version)(
        x, bias=bias, residual=residual)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x, gamma, beta, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * gamma + beta
    return out.astype(x.dtype)


def make_norm_params(key, d, kind):
    if kind == "rms":
        return {"gamma": jnp.zeros((d,), jnp.float32)}
    return {"gamma": jnp.ones((d,), jnp.float32),
            "beta": jnp.zeros((d,), jnp.float32)}


def apply_norm(x, p, kind):
    if kind == "rms":
        return rms_norm(x, p["gamma"])
    return layer_norm(x, p["gamma"], p["beta"])


# --------------------------------------------------------------------------
# RoPE (interleaved pairs)
# --------------------------------------------------------------------------

def rope(x, positions, theta):
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (hd // 2, 2))
    x0, x1 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def _softcap(s, cap):
    return jnp.tanh(s / cap) * cap if cap else s


def _static_zero_window(window) -> bool:
    return isinstance(window, int) and window == 0


def attend_full(q, k, v, *, q_positions, kv_positions, window=0,
                softcap=0.0, causal=True, kv_len=None):
    """Materialised-score attention, head-expanded layout.

    q, k, v: (B, H, S, hd) — GQA kv heads are pre-expanded to H by the
    caller (a free local slice under head-TP sharding).
    window: 0 / static int / traced scalar (HUGE_WINDOW disables in effect).
    kv_len: optional (B,) valid cache length for decode.
    """
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    s = _softcap(s, softcap)
    qp = q_positions[:, None, :, None]
    kp = kv_positions[:, None, None, :]
    mask = jnp.ones(s.shape, dtype=bool)
    if causal:
        mask &= kp <= qp
    if not _static_zero_window(window):
        mask &= kp > qp - window
    if kv_len is not None:
        mask &= kp < kv_len[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(v.dtype)


def attend_flash(q, k, v, *, q_positions, kv_positions, window=0,
                 softcap=0.0, causal=True, q_block=512, kv_block=512):
    """Online-softmax blocked attention (pure-JAX flash).

    q, k, v: (B, H, S, hd), kv pre-expanded to H. Static sliding-window
    layers get a banded schedule: only the kv blocks intersecting the window
    are visited (O(S*W) instead of O(S^2)). A traced window applies the mask
    but visits all blocks. The inner step is jax.checkpoint'ed so the
    backward pass recomputes score blocks instead of storing O(S^2)
    residuals (the flash recompute schedule)."""
    B, H, Sq, hd = q.shape
    Skv, vd = k.shape[2], v.shape[-1]

    def pick_block(S, pref):
        """Largest block <= pref dividing S (hymba: S = 4096 + 128 meta)."""
        b = min(pref, S)
        while S % b:
            b -= 1
        return b

    q_block, kv_block = pick_block(Sq, q_block), pick_block(Skv, kv_block)
    nq, nk = Sq // q_block, Skv // kv_block
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qb = q.reshape(B, H, nq, q_block, hd).astype(jnp.float32)
    kb = k.reshape(B, H, nk, kv_block, hd).astype(jnp.float32)
    vb = v.reshape(B, H, nk, kv_block, vd).astype(jnp.float32)
    qp = q_positions.reshape(B, nq, q_block)
    kp = kv_positions.reshape(B, nk, kv_block)

    banded = isinstance(window, int) and window > 0
    masked = not _static_zero_window(window)
    if banded:
        # kv block j for q block i runs over offsets i - wb .. i,
        # wb = ceil((window + q_block) / kv_block)
        wb = -(-(window + q_block) // kv_block)
        n_steps = min(nk, wb + 1)
    else:
        n_steps = nk

    def per_qblock(qi, q_i, qp_i):
        # q_i: (B, H, q_block, hd); qp_i: (B, q_block)
        m0 = jnp.full((B, H, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, vd), jnp.float32)

        @jax.checkpoint
        def step(carry, js):
            m, l, acc = carry
            if banded:
                j_raw = qi - (n_steps - 1) + js
                visit = j_raw >= 0            # clamped re-visits are masked
                j = jnp.maximum(j_raw, 0)
            else:
                j, visit = js, None
            k_j = jax.lax.dynamic_index_in_dim(kb, j, 2, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vb, j, 2, keepdims=False)
            kp_j = jax.lax.dynamic_index_in_dim(kp, j, 1, keepdims=False)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j) * scale
            s = _softcap(s, softcap)
            msk = jnp.ones(s.shape, dtype=bool)
            if causal:
                msk &= kp_j[:, None, None, :] <= qp_i[:, None, :, None]
            if masked:
                msk &= kp_j[:, None, None, :] > \
                    qp_i[:, None, :, None] - window
            if visit is not None:
                msk &= visit
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_j)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      jnp.arange(n_steps))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.vmap(per_qblock, in_axes=(0, 2, 1), out_axes=2)(
        jnp.arange(nq), qb, qp)
    # out: (B, H, nq, q_block, vd) -> (B, H, Sq, vd)
    return out.reshape(B, H, Sq, vd).astype(v.dtype)


# --------------------------------------------------------------------------
# GQA attention layer (with qk-norm, softcap, local/global, cache)
# --------------------------------------------------------------------------

def head_mask(cfg: ModelConfig):
    """(padded_heads,) 1.0 for real head slots, 0.0 for padding slots.
    Real heads of real kv-group g occupy slots [g*G_pad, g*G_pad+G_real);
    padded kv groups (g >= n_kv) are entirely dead."""
    Hp, Hkvp = cfg.padded_heads, cfg.padded_kv
    g_pad, g_real = Hp // Hkvp, cfg.n_heads // cfg.n_kv
    m = [1.0 if (h // g_pad) < cfg.n_kv and (h % g_pad) < g_real else 0.0
         for h in range(Hp)]
    return jnp.asarray(m, jnp.float32)


def make_attn_params(key, cfg: ModelConfig):
    d, H, Hkv, hd = cfg.d_model, cfg.padded_heads, cfg.padded_kv, cfg.head_dim
    ks = split_keys(key, 4)
    p = {"wq": dense_init(ks[0], (d, H, hd)),
         "wk": dense_init(ks[1], (d, Hkv, hd)),
         "wv": dense_init(ks[2], (d, Hkv, hd)),
         "wo": dense_init(ks[3], (H, hd, d))}
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def attn_forward(p, x, cfg: ModelConfig, *, positions, window,
                 theta, cache=None, cache_index=None, use_flash=False,
                 ring=False):
    """Self-attention. x: (B, S, d).

    window: 0 (global) / static int (banded local) / traced scalar.
    cache: None (train/prefill-no-cache) or dict(k, v, (B,Hkv,Smax,hd)).
    cache_index: scalar write offset for decode; None -> prefill writes 0..S.
    ring: cache is a window-sized ring buffer (slot = position % W); only
    valid with a static local window.
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    H, Hkv, hd = cfg.padded_heads, cfg.padded_kv, cfg.head_dim
    G = H // Hkv
    cdt = x.dtype
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt)),
                  "heads")
    k = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt)),
                  "heads")
    v = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt)),
                  "heads")
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    q = q.transpose(0, 2, 1, 3)                      # (B, H, S, hd)
    k = k.transpose(0, 2, 1, 3)                      # (B, Hkv, S, hd)
    v = v.transpose(0, 2, 1, 3)

    def expand(t):                                   # kv -> H heads
        return jnp.repeat(t, G, axis=1) if G > 1 else t

    softcap = cfg.softcap_attn
    new_cache = None
    if cache is not None and ring:
        W = cache["k"].shape[2]
        idx = jnp.int32(0) if cache_index is None else cache_index
        if S > 1:
            if S >= W:
                # prefill: keep the last W tokens, rolled so slot == pos % W
                kW, vW = k[:, :, -W:], v[:, :, -W:]
                shift = (idx + S) % W
                ck = jnp.roll(kW, shift, axis=2)
                cv = jnp.roll(vW, shift, axis=2)
            else:        # short prefill: contiguous write (no wrap at idx=0)
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k, idx % W, axis=2)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v, idx % W, axis=2)
            new_cache = {"k": ck, "v": cv}
            fn = attend_flash if use_flash else attend_full
            out = fn(q, expand(k), expand(v), q_positions=positions,
                     kv_positions=positions, window=window, softcap=softcap)
        else:
            slot = idx % W
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                     axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                     axis=2)
            new_cache = {"k": ck, "v": cv}
            slots = jnp.arange(W)
            delta = jnp.mod(idx - slots, W)          # age of each slot
            kv_pos = jnp.where(delta <= idx, idx - delta, idx + 1)
            kv_positions = jnp.broadcast_to(kv_pos[None], (B, W))
            out = attend_full(q, expand(ck), expand(cv),
                              q_positions=positions,
                              kv_positions=kv_positions, window=window,
                              softcap=softcap)
        out = out.transpose(0, 2, 1, 3)              # (B, S, H, hd)
        if cfg.padded_heads != cfg.n_heads or cfg.padded_kv != cfg.n_kv:
            out = out * head_mask(cfg).astype(cdt)[None, None, :, None]
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))
        return out, new_cache
    if cache is not None:
        idx = 0 if cache_index is None else cache_index
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, axis=2)
        new_cache = {"k": ck, "v": cv}
        if S > 1:
            # prefill: the cache was written starting at idx (== 0 for a
            # fresh cache), so attention over it equals attention over the
            # freshly-projected local k/v — use the flash path on those
            # rather than score-materialising against the padded cache.
            fn = attend_flash if use_flash else attend_full
            out = fn(q, expand(k), expand(v), q_positions=positions,
                     kv_positions=positions, window=window, softcap=softcap)
        else:
            kv_positions = jnp.broadcast_to(
                jnp.arange(ck.shape[2])[None], (B, ck.shape[2]))
            kv_len = (idx + S) * jnp.ones((B,), jnp.int32)
            out = attend_full(q, expand(ck), expand(cv),
                              q_positions=positions,
                              kv_positions=kv_positions, window=window,
                              softcap=softcap, kv_len=kv_len)
    elif use_flash:
        out = attend_flash(q, expand(k), expand(v), q_positions=positions,
                           kv_positions=positions, window=window,
                           softcap=softcap)
    else:
        out = attend_full(q, expand(k), expand(v), q_positions=positions,
                          kv_positions=positions, window=window,
                          softcap=softcap)
    out = out.transpose(0, 2, 1, 3)                  # (B, S, H, hd)
    if cfg.padded_heads != cfg.n_heads or cfg.padded_kv != cfg.n_kv:
        # zero the padding slots: exact n_heads semantics (and zero grads
        # into the dead wq/wk/wv/wo rows)
        out = out * head_mask(cfg).astype(cdt)[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))
    return out, new_cache


# --------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# --------------------------------------------------------------------------

def make_mla_params(key, cfg: ModelConfig):
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, dl = cfg.head_dim, cfg.rope_dim, cfg.v_head_dim, cfg.kv_lora
    ks = split_keys(key, 6)
    return {
        "w_dkv": dense_init(ks[0], (d, dl)),          # down-proj to latent
        "w_kr": dense_init(ks[1], (d, dr)),           # shared rope key
        "w_uk": dense_init(ks[2], (dl, H, dn)),       # latent -> key(nope)
        "w_uv": dense_init(ks[3], (dl, H, dv)),       # latent -> value
        "w_q": dense_init(ks[4], (d, H, dn + dr)),    # query (lite: no q-lora)
        "wo": dense_init(ks[5], (H, dv, d)),
    }


def mla_forward(p, x, cfg: ModelConfig, *, positions, theta,
                cache=None, cache_index=None, use_flash=False):
    """MLA. Cache holds the compressed latent (c_kv, k_rope) only.

    * decode (S==1): the *absorbed* form — q projected into latent space, so
      per-step compute/cache scale with kv_lora, not H*head_dim.
    * train / prefill: the *folded* form — k = [k_nope | k_rope broadcast]
      so the score is one dot product and the standard (flash) attention
      kernels apply. Prefill still writes only the compressed cache.
    """
    B, S, d = x.shape
    H, dn, dr, dv, dl = (cfg.n_heads, cfg.head_dim, cfg.rope_dim,
                         cfg.v_head_dim, cfg.kv_lora)
    cdt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(cdt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, theta)
    c_kv = jnp.einsum("bsd,dl->bsl", x, p["w_dkv"].astype(cdt))
    k_rope = rope(jnp.einsum("bsd,dr->bsr", x,
                             p["w_kr"].astype(cdt))[:, :, None, :],
                  positions, theta)[:, :, 0, :]

    new_cache = None
    if cache is not None:
        idx = 0 if cache_index is None else cache_index
        c_all = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, idx,
                                                    axis=1)
        r_all = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope,
                                                    idx, axis=1)
        new_cache = {"c_kv": c_all, "k_rope": r_all}

    if cache is not None and S == 1:
        Skv = c_all.shape[1]
        kv_len = (0 if cache_index is None else cache_index) + S
        # absorbed: q_nope -> latent space
        q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["w_uk"].astype(cdt))
        s = (jnp.einsum("bshl,btl->bhst", q_lat.astype(jnp.float32),
                        c_all.astype(jnp.float32))
             + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                          r_all.astype(jnp.float32)))
        s = s / jnp.sqrt(dn + dr).astype(jnp.float32)
        kp = jnp.arange(Skv)[None, None, None, :]
        qp = positions[:, None, :, None]
        mask = (kp <= qp) & (kp < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btl->bshl", pr,
                           c_all.astype(jnp.float32)).astype(cdt)
        out = jnp.einsum("bshl,lhv->bshv", o_lat, p["w_uv"].astype(cdt))
    else:
        # folded: concat nope+rope into one head_dim, standard attention.
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uk"].astype(cdt))
        vv = jnp.einsum("bsl,lhv->bshv", c_kv, p["w_uv"].astype(cdt))
        k_fold = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, dr))], axis=-1)
        q_fold = jnp.concatenate([q_nope, q_rope], axis=-1)
        # MLA scales by sqrt(dn+dr); attend_* scale by sqrt(head_dim)=same.
        qf = q_fold.transpose(0, 2, 1, 3)                # (B, H, S, hd')
        kf = k_fold.transpose(0, 2, 1, 3)
        vf = vv.transpose(0, 2, 1, 3)
        fn = attend_flash if use_flash else attend_full
        out = fn(qf, kf, vf, q_positions=positions, kv_positions=positions,
                 window=0)
        out = out.transpose(0, 2, 1, 3)                  # (B, S, H, dv)
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(cdt)), new_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def make_mlp_params(key, d, dff, kind):
    ks = split_keys(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], (d, dff)),
                "w_up": dense_init(ks[1], (d, dff)),
                "w_down": dense_init(ks[2], (dff, d))}
    return {"w_up": dense_init(ks[0], (d, dff)),
            "w_down": dense_init(ks[1], (dff, d))}


def mlp_forward(p, x, kind):
    cdt = x.dtype
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else functools.partial(
            jax.nn.gelu, approximate=True)
        h = act(x @ p["w_gate"].astype(cdt)) * (x @ p["w_up"].astype(cdt))
    else:
        h = jax.nn.gelu(x @ p["w_up"].astype(cdt), approximate=True)
    return h @ p["w_down"].astype(cdt)


# --------------------------------------------------------------------------
# MoE (sorted capacity dispatch + per-expert block einsum; TP over d_ff)
# --------------------------------------------------------------------------

def make_moe_params(key, cfg: ModelConfig):
    d, E, dff = cfg.d_model, cfg.n_experts, cfg.expert_dff
    ks = split_keys(key, 5)
    p = {"w_gate_router": dense_init(ks[0], (d, E)),
         "w1": dense_init(ks[1], (E, d, dff)),        # gate proj
         "w2": dense_init(ks[2], (E, d, dff)),        # up proj
         "w3": dense_init(ks[3], (E, dff, d))}        # down proj
    if cfg.n_shared:
        p["shared"] = make_mlp_params(ks[4], d, cfg.n_shared * dff, cfg.mlp)
    return p


def _moe_group(xt, p, cfg: ModelConfig, cap: int):
    """Dispatch + expert compute for one group of tokens. xt: (Tg, d)."""
    Tg, d = xt.shape
    E, K = cfg.n_experts, cfg.top_k
    cdt = xt.dtype
    logits = (xt @ p["w_gate_router"].astype(cdt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)              # (Tg, K)
    if cfg.renorm_topk:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    flat_e = topi.reshape(-1)                         # (Tg*K,)
    flat_t = jnp.repeat(jnp.arange(Tg), K)
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e)                       # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(Tg * K) - starts[se]
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, E * cap)   # overflow -> scratch

    buf = jnp.zeros((E * cap + 1, d), cdt).at[slot].set(
        xt[st] * keep[:, None].astype(cdt))
    eb = buf[:E * cap].reshape(E, cap, d)
    if cfg.mlp in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else functools.partial(
            jax.nn.gelu, approximate=True)
        h = act(jnp.einsum("ecd,edf->ecf", eb, p["w1"].astype(cdt))) * \
            jnp.einsum("ecd,edf->ecf", eb, p["w2"].astype(cdt))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", eb,
                                   p["w1"].astype(cdt)), approximate=True)
    eo = jnp.einsum("ecf,efd->ecd", h, p["w3"].astype(cdt))
    gathered = eo.reshape(E * cap, d)[jnp.minimum(slot, E * cap - 1)]
    contrib = gathered * (sw * keep).astype(cdt)[:, None]
    return jnp.zeros((Tg, d), cdt).at[st].add(contrib)


def moe_forward(p, x, cfg: ModelConfig):
    """Token-choice top-k MoE with capacity; differentiable sort dispatch.

    Tokens are split into ``cfg.moe_groups`` dispatch groups (the launcher
    sets this to the DP size), vmapped so sort/scatter stay shard-local
    under GSPMD. The (E, C, d) expert batch keeps d_ff TP-sharded (the
    nFFT-style "keep the hot GEMM local" schedule; EP a2a is a strategy
    variant, see DESIGN.md)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    G = cfg.moe_groups if T % cfg.moe_groups == 0 else 1
    Tg = T // G
    cap = int(min(Tg, max(8, round(Tg * K / E * cfg.capacity_factor))))
    xg = x.reshape(G, Tg, d)
    out = jax.vmap(lambda xt: _moe_group(xt, p, cfg, cap))(xg)
    out = out.reshape(B, S, d)
    if cfg.n_shared:
        out = out + mlp_forward(p["shared"], x, cfg.mlp)
    return out


# --------------------------------------------------------------------------
# Mamba2 (SSD, chunked) + single-step decode
# --------------------------------------------------------------------------

def make_mamba_params(key, cfg: ModelConfig):
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = split_keys(key, 9)
    return {
        "w_z": dense_init(ks[0], (d, di)),
        "w_x": dense_init(ks[1], (d, di)),
        "w_B": dense_init(ks[2], (d, N)),
        "w_C": dense_init(ks[3], (d, N)),
        "w_dt": dense_init(ks[4], (d, H)),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "conv_x": dense_init(ks[5], (cfg.conv_width, di), 0.2),
        "conv_B": dense_init(ks[6], (cfg.conv_width, N), 0.2),
        "conv_C": dense_init(ks[7], (cfg.conv_width, N), 0.2),
        "out_norm": jnp.zeros((di,), jnp.float32),
        "w_out": dense_init(ks[8], (di, d)),
    }


def _causal_conv1d(x, w, state=None):
    """Depthwise causal conv. x: (B, S, C); w: (W, C).
    state: (B, W-1, C) carry for decode. Returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype)
            for i in range(W))
    new_state = xp[:, -(W - 1):, :] if W > 1 else None
    return y, new_state


def ssd_chunked(xh, dt, A, Bm, Cm, *, chunk):
    """Mamba2 SSD, chunked linear-time scan.

    xh: (B, S, H, P) head inputs; dt: (B, S, H) softplus'd step sizes;
    A: (H,) negative decay rates; Bm/Cm: (B, S, N) (single group).
    Returns y: (B, S, H, P) and final state (B, H, P, N).
    """
    Bsz, S, H, Pd = xh.shape
    N = Bm.shape[-1]
    nc = S // chunk
    f32 = jnp.float32
    xc = xh.reshape(Bsz, nc, chunk, H, Pd).astype(f32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(f32)
    Bc = Bm.reshape(Bsz, nc, chunk, N).astype(f32)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(f32)

    dA = dtc * A[None, None, None, :]                 # (B, nc, Q, H) <= 0
    dAcs = jnp.cumsum(dA, axis=2)                     # inclusive cumsum
    # intra-chunk: L[i,j] = exp(dAcs_i - dAcs_j) for i >= j
    Ldec = dAcs[:, :, :, None, :] - dAcs[:, :, None, :, :]   # (B,nc,Q,Q,H)
    Ldec = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool))[None, None,
                                                              :, :, None],
                     jnp.exp(Ldec), 0.0)
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)    # (B,nc,Q,Q)
    w = scores[..., None] * Ldec * dtc[:, :, None, :, :]     # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w, xc)

    # chunk summary state: S_c = sum_j exp(dAcs_Q - dAcs_j) dt_j B_j x_j
    decay_to_end = jnp.exp(dAcs[:, :, -1:, :] - dAcs)         # (B,nc,Q,H)
    Sc = jnp.einsum("bcjh,bcjn,bcjhp->bchpn",
                    decay_to_end * dtc, Bc, xc)               # (B,nc,H,P,N)
    # inter-chunk recurrence over c
    chunk_decay = jnp.exp(dAcs[:, :, -1, :])                  # (B,nc,H)

    def scan_fn(h, inp):
        Sc_c, dec_c = inp
        h_new = h * dec_c[..., None, None] + Sc_c
        return h_new, h                                       # emit state BEFORE chunk

    h0 = jnp.zeros((Bsz, H, Pd, N), f32)
    hT, h_prev = jax.lax.scan(
        scan_fn, h0,
        (Sc.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)                  # (B,nc,H,P,N)
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp",
                         Cc, h_prev, jnp.exp(dAcs))
    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)
    return y.astype(xh.dtype), hT


def mamba_forward(p, x, cfg: ModelConfig, *, state=None):
    """Mamba2 mixer. x: (B, S, d).
    state: None (train) or dict(ssm (B,H,P,N) f32, conv_x/conv_B/conv_C).
    Decode path (S small) updates state stepwise."""
    B, S, d = x.shape
    di, N, H, Pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    cdt = x.dtype
    z = x @ p["w_z"].astype(cdt)
    xi = x @ p["w_x"].astype(cdt)
    Bm = x @ p["w_B"].astype(cdt)
    Cm = x @ p["w_C"].astype(cdt)
    dt_raw = (x @ p["w_dt"].astype(cdt)).astype(jnp.float32) + p["dt_bias"]
    dt = jax.nn.softplus(dt_raw)                      # (B, S, H)
    A = -jnp.exp(p["A_log"])                          # (H,)

    def pick_chunk(S, pref):
        b = min(pref, S)
        while S % b:
            b -= 1
        return b

    cs = {} if state is None else state
    xi, cx = _causal_conv1d(xi, p["conv_x"], cs.get("conv_x"))
    Bm, cB = _causal_conv1d(Bm, p["conv_B"], cs.get("conv_B"))
    Cm, cC = _causal_conv1d(Cm, p["conv_C"], cs.get("conv_C"))
    xi, Bm, Cm = jax.nn.silu(xi), jax.nn.silu(Bm), jax.nn.silu(Cm)
    xh = xi.reshape(B, S, H, Pd)

    if state is None:
        y, _ = ssd_chunked(xh, dt, A, Bm, Cm,
                           chunk=pick_chunk(S, cfg.ssm_chunk))
        new_state = None
    elif S >= 8:
        # prefill: chunked SSD from zero state, carry the final state out.
        y, hT = ssd_chunked(xh, dt, A, Bm, Cm,
                            chunk=pick_chunk(S, cfg.ssm_chunk))
        new_state = {"ssm": hT, "conv_x": cx, "conv_B": cB, "conv_C": cC}
    else:
        # stepwise recurrence (decode): h' = h * exp(dt A) + dt B (x) ;
        # y = C . h' + D x  — scan over the S new tokens (usually S == 1).
        def step(h, inp):
            x_t, dt_t, B_t, C_t = inp        # (B,H,P),(B,H),(B,N),(B,N)
            dec = jnp.exp(dt_t * A[None, :])              # (B,H)
            upd = jnp.einsum("bh,bn,bhp->bhpn", dt_t, B_t,
                             x_t.astype(jnp.float32))
            h = h * dec[..., None, None] + upd
            y_t = jnp.einsum("bn,bhpn->bhp", C_t, h)
            return h, y_t
        h0 = cs["ssm"]
        hT, ys = jax.lax.scan(
            step, h0,
            (xh.transpose(1, 0, 2, 3).astype(jnp.float32),
             dt.transpose(1, 0, 2),
             Bm.transpose(1, 0, 2).astype(jnp.float32),
             Cm.transpose(1, 0, 2).astype(jnp.float32)))
        y = ys.transpose(1, 0, 2, 3).astype(cdt)          # (B,S,H,P)
        new_state = {"ssm": hT, "conv_x": cx, "conv_B": cB, "conv_C": cC}

    y = y + xh * p["D"].astype(cdt)[None, None, :, None]
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"])
    return y @ p["w_out"].astype(cdt), new_state


def init_mamba_state(cfg: ModelConfig, batch, dtype=jnp.float32):
    W = cfg.conv_width
    return {
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.ssm_state), jnp.float32),
        "conv_x": jnp.zeros((batch, W - 1, cfg.d_inner), dtype),
        "conv_B": jnp.zeros((batch, W - 1, cfg.ssm_state), dtype),
        "conv_C": jnp.zeros((batch, W - 1, cfg.ssm_state), dtype),
    }
