"""Shared neural-net layers: norms, RoPE, GQA/MLA attention, SwiGLU, MoE.

Pure-functional: ``init_*`` build param dicts, ``apply``-style functions
take (params, inputs).  All matmul dims are kept MXU-friendly (128-ish
multiples at production scale).  Attention dispatches through
``repro.kernels.ops.flash_attention`` so the impl (pallas / xla_chunked /
xla) is a runtime choice.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops

Params = Dict[str, Any]


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (with partial/2D fraction for ChatGLM, NTK theta configurable)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0):
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    inv, rot = rope_freqs(d, theta, fraction)
    if rot == 0:
        return x
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * inv   # (B, S, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dt),
        "wk": dense_init(ks[1], (d, kv * hd), dtype=dt),
        "wv": dense_init(ks[2], (d, kv * hd), dtype=dt),
        "wo": dense_init(ks[3], (h * hd, d), dtype=dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def attention(p: Params, cfg: ArchConfig, x: jax.Array,
              positions: jax.Array,
              kv_cache: Optional[Dict[str, jax.Array]] = None,
              window: Optional[int] = None,
              attn_impl: str = "xla_chunked") -> Tuple[jax.Array, Optional[Dict]]:
    """Self-attention with GQA, RoPE and optional KV cache.

    Without cache: causal attention over x (training / prefill).
    With cache: x is the new token(s); cache holds prior K/V; returns
    updated cache.  Cache layout: {"k","v": (B, S_cache, KV, HD),
    "length": scalar} — a ring buffer if window is set and S_cache==window.
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"])
    k = jnp.einsum("bsd,df->bsf", x, p["wk"])
    v = jnp.einsum("bsd,df->bsf", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    new_cache = None
    if kv_cache is not None:
        cache_len = kv_cache["k"].shape[1]
        pos0 = kv_cache["length"]         # (B,) per-slot tokens seen so far
        ring = bool(kv_cache.get("ring", window is not None))
        slot = (pos0 % cache_len) if ring else pos0
        ck = _batched_update(kv_cache["k"], k, slot)
        cv = _batched_update(kv_cache["v"], v, slot)
        new_cache = {"k": ck, "v": cv, "length": pos0 + s, "ring": ring}
        out = decode_attention(q, ck, cv, length=pos0 + s, window=window,
                               ring=ring)
    else:
        out = kops.flash_attention(q, k, v, causal=True, window=window,
                                   impl=attn_impl)
    out = out.reshape(b, s, h * hd)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"]), new_cache


def _batched_update(cache: jax.Array, new: jax.Array,
                    pos: jax.Array) -> jax.Array:
    """Per-slot cache write: cache (B, C, ...), new (B, s, ...),
    pos (B,) — each batch entry writes at its OWN position (continuous
    batching: slots restart independently)."""
    def one(c, x, p):
        return jax.lax.dynamic_update_slice_in_dim(
            c, x.astype(c.dtype), p, axis=0)
    return jax.vmap(one)(cache, new, pos)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array, window: Optional[int] = None,
                     ring: bool = False) -> jax.Array:
    """Single-token (or short-q) attention over a KV cache.

    q (B, S, H, D) with small S (decode: S=1); cache (B, C, KV, HD).
    ``length`` (B,) = per-slot tokens written INCLUDING the current ones.
    ring=True: the cache is a ring buffer holding the last C tokens, every
    live slot is in-window; stale slots are those >= length when the ring
    hasn't wrapped yet.  ring=False: slot == position; mask slots >= length
    and (optionally) more than ``window`` behind the newest position.
    s > 1 (chunked prefill through the decode path, non-ring only): query
    row i sits at position length-s+i, so it may only see slots up to and
    including its own — the per-row causal mask below.
    O(C) per token — no flash kernel needed for a 1-row query.
    """
    b, s, h, d = q.shape
    c = k_cache.shape[1]
    kv = k_cache.shape[2]
    # GQA via GROUPED einsums, never jnp.repeat: expanding the kv heads
    # of a sequence-sharded cache triggers GSPMD "involuntary full
    # rematerialization" — a 2.15 GB/layer cache gather measured on
    # qwen2.5-32b decode_32k (EXPERIMENTS.md §Perf H4).
    g = h // kv
    qg = q.reshape(b, s, kv, g, d)
    scores = jnp.einsum("bskgd,bckd->bkgsc", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores * (d ** -0.5)
    slots = jnp.arange(c)
    length = jnp.broadcast_to(length, (b,))
    qpos = length[:, None] - s + 1 + jnp.arange(s)[None, :]     # (b, s)
    valid = slots[None, None, :] < jnp.minimum(qpos, c)[:, :, None]
    if not ring and window is not None:
        valid = valid & (slots[None, None, :] >= (qpos - window)[:, :, None])
    scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
    p_ = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsc,bckd->bskgd", p_.astype(q.dtype), v_cache)
    return out.reshape(b, s, h, d)


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ArchConfig) -> Params:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dt),
        "wk": dense_init(ks[1], (d, h * hd), dtype=dt),
        "wv": dense_init(ks[2], (d, h * hd), dtype=dt),
        "wo": dense_init(ks[3], (h * hd, d), dtype=dt),
    }


def cross_attention(p: Params, cfg: ArchConfig, x: jax.Array,
                    enc: jax.Array, attn_impl: str = "xla_chunked"
                    ) -> jax.Array:
    b, s, _ = x.shape
    f = enc.shape[1]
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bfd,de->bfe", enc, p["wk"]).reshape(b, f, h, hd)
    v = jnp.einsum("bfd,de->bfe", enc, p["wv"]).reshape(b, f, h, hd)
    out = kops.flash_attention(q, k, v, causal=False, window=None,
                               impl=attn_impl)
    return jnp.einsum("bsf,fd->bsd", out.reshape(b, s, h * hd), p["wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    qd = m.nope_dim + m.rope_dim
    return {
        "wq": dense_init(ks[0], (d, h * qd), dtype=dt),
        "w_dkv": dense_init(ks[1], (d, m.kv_lora), dtype=dt),   # compress
        "w_kr": dense_init(ks[2], (d, m.rope_dim), dtype=dt),   # shared rope key
        "w_uk": dense_init(ks[3], (m.kv_lora, h * m.nope_dim), dtype=dt),
        "w_uv": dense_init(ks[4], (m.kv_lora, h * m.v_dim), dtype=dt),
        "wo": dense_init(ks[5], (h * m.v_dim, d), dtype=dt),
        "norm_ckv": init_rmsnorm(m.kv_lora, dt),
    }


def mla_attention_absorbed(p: Params, cfg: ArchConfig, x: jax.Array,
                           positions: jax.Array,
                           kv_cache: Dict[str, jax.Array],
                           window: Optional[int] = None
                           ) -> Tuple[jax.Array, Dict]:
    """Absorbed-matrix MLA decode (DeepSeek-V2 §2.1 inference path).

    Mathematically identical to decompress-then-attend, but the score and
    context computations run in the COMPRESSED kv_lora space:

        scores = (q_nope W_uk) . c_kv  +  q_rope . k_rope
        out    = (softmax . c_kv) W_uv W_o

    Per step this is O(S * (kv_lora + rope)) per head instead of
    O(S * kv_lora * h * (nope + v)) for cache decompression — the
    difference between re-projecting the whole 32k cache every token and
    a plain compressed-space dot product.
    """
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    qd = m.nope_dim + m.rope_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(b, s, h, qd)
    q_nope, q_rope = q[..., :m.nope_dim], q[..., m.nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rmsnorm(p["norm_ckv"], jnp.einsum("bsd,dc->bsc", x, p["w_dkv"]),
                  cfg.norm_eps)
    kr = jnp.einsum("bsd,dr->bsr", x, p["w_kr"])[:, :, None, :]
    kr = apply_rope(kr, positions, cfg.rope_theta)[:, :, 0, :]

    cache_len = kv_cache["ckv"].shape[1]
    pos0 = kv_cache["length"]
    ring = bool(kv_cache.get("ring", window is not None))
    slot = (pos0 % cache_len) if ring else pos0
    ckv_c = _batched_update(kv_cache["ckv"], ckv, slot)
    kr_c = _batched_update(kv_cache["kr"], kr, slot)
    new_cache = {"ckv": ckv_c, "kr": kr_c, "length": pos0 + s, "ring": ring}

    # absorb W_uk into the query:  q~ (b,s,h,lora).  All einsums
    # accumulate in f32 via preferred_element_type WITHOUT materialising
    # f32 copies of the (huge) cache — that cast alone doubled the HBM
    # traffic in the first version (EXPERIMENTS.md §Perf iter 4).
    f32 = jnp.float32
    w_uk = p["w_uk"].reshape(m.kv_lora, h, m.nope_dim)
    q_abs = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk,
                       preferred_element_type=f32).astype(x.dtype)
    scores = (jnp.einsum("bshl,bSl->bhsS", q_abs, ckv_c,
                         preferred_element_type=f32)
              + jnp.einsum("bshr,bSr->bhsS", q_rope, kr_c,
                           preferred_element_type=f32))
    scores = scores * (qd ** -0.5)
    slots = jnp.arange(cache_len)
    newlen = jnp.broadcast_to(pos0 + s, (b,))
    # per-row causal mask (query row i sits at position newlen-s+i) so a
    # multi-token chunk (chunked prefill) stays causal; s==1 reduces to
    # the plain slots < length mask
    qpos = newlen[:, None] - s + 1 + jnp.arange(s)[None, :]      # (b, s)
    valid = slots[None, None, :] < jnp.minimum(qpos, cache_len)[:, :, None]
    if not ring and window is not None:
        valid = valid & (slots[None, None, :] >= (qpos - window)[:, :, None])
    scores = jnp.where(valid[:, None, :, :], scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhsS,bSl->bshl", attn, ckv_c,
                     preferred_element_type=f32)
    # absorb W_uv on the way out:  (b,s,h,v)
    w_uv = p["w_uv"].reshape(m.kv_lora, h, m.v_dim)
    out = jnp.einsum("bshl,lhv->bshv", ctx, w_uv,
                     preferred_element_type=f32)
    out = out.reshape(b, s, h * m.v_dim).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"]), new_cache


def mla_attention(p: Params, cfg: ArchConfig, x: jax.Array,
                  positions: jax.Array,
                  kv_cache: Optional[Dict[str, jax.Array]] = None,
                  window: Optional[int] = None,
                  attn_impl: str = "xla_chunked",
                  absorbed: bool = True
                  ) -> Tuple[jax.Array, Optional[Dict]]:
    """MLA: cache holds the COMPRESSED c_kv (kv_lora) + shared rope key —
    the memory saving that defines MLA.  Cache: {"ckv": (B, S, kv_lora),
    "kr": (B, S, rope_dim), "length"}.  Decode uses the absorbed-matrix
    path by default (see ``mla_attention_absorbed``)."""
    if kv_cache is not None and absorbed:
        return mla_attention_absorbed(p, cfg, x, positions, kv_cache,
                                      window=window)
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    qd = m.nope_dim + m.rope_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(b, s, h, qd)
    q_nope, q_rope = q[..., :m.nope_dim], q[..., m.nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rmsnorm(p["norm_ckv"], jnp.einsum("bsd,dc->bsc", x, p["w_dkv"]),
                  cfg.norm_eps)
    kr = jnp.einsum("bsd,dr->bsr", x, p["w_kr"])[:, :, None, :]  # 1 shared head
    kr = apply_rope(kr, positions, cfg.rope_theta)[:, :, 0, :]

    new_cache = None
    if kv_cache is not None:
        cache_len = kv_cache["ckv"].shape[1]
        pos0 = kv_cache["length"]
        ring = bool(kv_cache.get("ring", window is not None))
        slot = (pos0 % cache_len) if ring else pos0
        ckv_c = _batched_update(kv_cache["ckv"], ckv, slot)
        kr_c = _batched_update(kv_cache["kr"], kr, slot)
        new_cache = {"ckv": ckv_c, "kr": kr_c, "length": pos0 + s,
                     "ring": ring}
        ckv, kr = ckv_c, kr_c

    # decompress (on TPU this fuses into the attention matmuls; the
    # "absorbed" decode optimisation is a beyond-paper perf lever)
    k_nope = jnp.einsum("bsc,cf->bsf", ckv, p["w_uk"]).reshape(
        b, -1, h, m.nope_dim)
    vv = jnp.einsum("bsc,cf->bsf", ckv, p["w_uv"]).reshape(b, -1, h, m.v_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                  k_nope.shape[:3] + (m.rope_dim,))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    if kv_cache is not None:
        out = decode_attention(qf, k, vv, length=new_cache["length"],
                               window=window, ring=new_cache["ring"])
    else:
        # the Pallas kernel needs equal q/v head dims; MLA's differ
        impl = ("xla_chunked" if attn_impl == "pallas" and qd != m.v_dim
                else attn_impl)
        out = kops.flash_attention(qf, k, vv, causal=True,
                                   window=window, impl=impl)
    out = out.reshape(b, s, h * m.v_dim)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, d_ff), dtype=dtype),
        "w_up": dense_init(ks[1], (d, d_ff), dtype=dtype),
        "w_down": dense_init(ks[2], (d_ff, d), dtype=dtype),
    }


def mlp(p: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


# ---------------------------------------------------------------------------
# MoE FFN (GShard-style capacity dispatch + shared experts)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ArchConfig) -> Params:
    mo = cfg.moe
    d = cfg.d_model
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    e = mo.n_experts
    f = mo.d_ff_expert
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], (d, e), scale=scale, dtype=jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) / math.sqrt(f)).astype(dt),
    }
    if mo.n_shared:
        p["shared"] = init_mlp(ks[4], d, f * mo.n_shared, dtype=dt)
    return p


def moe_ffn(p: Params, cfg: ArchConfig, x: jax.Array,
            dropless: bool = False,
            group_size: int = 512,
            capacity_override: Optional[int] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed experts with GROUPED capacity-factor dispatch einsums.

    Returns (output, router aux load-balance loss).  Tokens are split into
    groups of ``group_size``; routing capacity is enforced per group
    (GShard).  This keeps the one-hot dispatch tensor at
    (g, group, E, cap) — linear in total tokens, quadratic only in the
    small group — which is what makes the 1M-token prefill shape
    shardable.  The launcher shards the expert dim over the ``model``
    mesh axis (expert parallelism -> all_to_all) and the group dim over
    ``data``.

    ``dropless=True`` (decode path: one token per sequence) computes ALL
    experts densely and gates — exact top-k with no capacity drops; for a
    single token this is a batch of matvecs, cheap and deterministic.
    """
    if dropless:
        return _moe_ffn_dropless(p, cfg, x)
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.n_experts, mo.top_k
    gs = min(group_size, t)
    pad = (-t) % gs
    xt = x.reshape(t, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    ng = (t + pad) // gs
    xg = xt.reshape(ng, gs, d)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (g, gs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    cap = (capacity_override if capacity_override is not None
           else max(int(gs * k / e * mo.capacity_factor), 1))
    # position of each (token, slot) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)    # (g, gs, k, e)
    flat = onehot.reshape(ng, gs * k, e)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat               # (g, gs*k, e)
    pos = jnp.sum(pos_in_e * flat, axis=-1).reshape(ng, gs, k)
    keep = pos < cap
    gate_vals = gate_vals * keep

    d_e = jax.nn.one_hot(gate_idx, e, dtype=x.dtype)         # (g, gs, k, e)
    d_c = jax.nn.one_hot(pos, cap, dtype=x.dtype) * keep[..., None]
    dispatch = jnp.einsum("gtke,gtkc->gtec", d_e, d_c)       # (g, gs, e, c)
    xe = jnp.einsum("gtec,gtd->gecd", dispatch, xg)          # (g, e, c, d)
    gg = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
    uu = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", jax.nn.silu(gg) * uu, p["w_down"])
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", d_e, d_c,
                         gate_vals.astype(x.dtype))
    yg = jnp.einsum("gtec,gecd->gtd", combine, ye)           # (g, gs, d)
    yt = yg.reshape(ng * gs, d)
    if pad:
        yt = yt[:t]

    if mo.n_shared:
        yt = yt + mlp(p["shared"], x.reshape(t, d)[None])[0]

    # GShard aux loss: E * sum_e (fraction_tokens_e * mean_prob_e)
    me = jnp.mean(probs, axis=(0, 1))
    fe = jnp.mean(jax.nn.one_hot(gate_idx, e, dtype=jnp.float32),
                  axis=(0, 1, 2)) * k
    aux = e * jnp.sum(fe * me)
    return yt.reshape(b, s, d), aux


def _moe_ffn_dropless(p: Params, cfg: ArchConfig, x: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.n_experts, mo.top_k
    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    gates = jnp.zeros((t, e), x.dtype)
    gates = jax.vmap(lambda g, gi, gv: g.at[gi].set(gv.astype(x.dtype)))(
        gates, gate_idx, gate_vals)
    g = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["w_up"])
    ye = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"])
    yt = jnp.einsum("te,ted->td", gates, ye)
    if mo.n_shared:
        yt = yt + mlp(p["shared"], xt[None])[0]
    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(jax.nn.one_hot(gate_idx, e, dtype=jnp.float32),
                  axis=(0, 1)) * k
    aux = e * jnp.sum(fe * me)
    return yt.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Embedding with sparse-gradient instrumentation (the paper's trigger)
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * d ** -0.5).astype(dtype)


def embed(table: jax.Array, ids: jax.Array,
          tap: Optional[jax.Array] = None) -> jax.Array:
    """Embedding lookup.

    ``tap=None``: ordinary lookup — autodiff produces the DENSE scatter-add
    gradient (i.e. the already-densified representation; this is what the
    paper's sparse_as_dense fix ultimately computes).

    ``tap`` given (zeros (B, S, d)): the lookup output is routed through
    ``tap`` with the table stop-gradiented, so ``d(loss)/d(tap)`` is the
    PER-TOKEN cotangent — exactly ``tf.gather``'s IndexedSlices values.
    ``repro.training.gradients`` packages it as IndexedSlices, reproducing
    TensorFlow's sparse path faithfully.
    """
    if tap is None:
        return table[ids]
    return jax.lax.stop_gradient(table)[ids] + tap


def tied_logits(table: jax.Array, h: jax.Array) -> jax.Array:
    """Projection through the shared embedding: produces the DENSE
    cotangent contribution to the tied weight."""
    return jnp.einsum("bsd,vd->bsv", h, table)


# ---------------------------------------------------------------------------
# Wait-free backprop: per-block custom_vjp gradient hook
# ---------------------------------------------------------------------------

def backward_hook(bwd_fn):
    """Identity boundary on a parameter block whose ``custom_vjp``
    backward runs ``bwd_fn`` on the block's cotangent the MOMENT
    autodiff emits it — the MG-WFBP hook that lets the ExchangePlan
    launch a bucket's collective while earlier layers are still
    differentiating.

    ``bwd_fn(g_block, state, extra) -> (g_out, new_state)``:
    ``g_block`` is the raw cotangent pytree of the block, ``state`` is
    arbitrary differentiable side state (e.g. this block's codec
    residuals) threaded OUT of the backward as the cotangent of the
    ``state`` input, and ``extra`` rides along read-only (e.g. partial
    microbatch sums; its cotangent is zeros and gets DCE'd).  The
    returned hook is ``hook(block_params, state, extra) ->
    block_params`` — an exact identity in forward, so the loss graph
    (and therefore every cotangent) is bitwise identical to the
    unhooked model."""
    @jax.custom_vjp
    def hook(x, state, extra):
        return x

    def fwd(x, state, extra):
        return x, (state, extra)

    def bwd(res, g):
        state, extra = res
        g_out, new_state = bwd_fn(g, state, extra)

        def zero_ct(x):     # integer leaves take float0 cotangents
            if jnp.issubdtype(x.dtype, jnp.inexact):
                return jnp.zeros_like(x)
            import numpy as _np
            return _np.zeros(x.shape, jax.dtypes.float0)

        zeros = jax.tree_util.tree_map(zero_ct, extra)
        return g_out, new_state, zeros

    hook.defvjp(fwd, bwd)
    return hook
