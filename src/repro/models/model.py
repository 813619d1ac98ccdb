"""Model assembly: one ``Model`` facade over all supported families.

Families
  dense   llama/qwen/chatglm/deepseek-7b style decoder (GQA + SwiGLU)
  moe     dense skeleton with MoE FFN (llama4-scout) and optional MLA
          attention (deepseek-v2)
  vlm     dense decoder consuming [patch-embeds ; token-embeds] prefix
  audio   enc-dec decoder with cross-attention to stub frame embeddings
          (seamless-m4t, and the paper's transformer-big)
  ssm     xLSTM (sLSTM + mLSTM recurrent blocks)
  hybrid  Zamba2: Mamba2 stack with ONE shared attention block applied
          every ``attn_every`` layers

All families scan over stacked layer params (``jax.lax.scan``) so the
lowered HLO is O(1) in depth — essential for the 512-device dry-run.

The embedding can run in ``sparse instrumentation`` mode (taps) to emit
true IndexedSlices gradients — see ``repro.training.gradients``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.activation_sharding import constrain_batch
from repro.telemetry import hooks as scopes

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# per-family layer blocks
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p: Params = {"norm1": L.init_rmsnorm(cfg.d_model, dt),
                 "norm2": L.init_rmsnorm(cfg.d_model, dt)}
    if cfg.mla is not None:
        p["attn"] = L.init_mla(ks[0], cfg)
    else:
        p["attn"] = L.init_attention(ks[0], cfg)
    if cfg.moe is not None:
        p["ffn"] = L.init_moe(ks[1], cfg)
    else:
        p["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype=dt)
    if cfg.frontend is not None and cfg.frontend.cross_attention:
        p["norm_x"] = L.init_rmsnorm(cfg.d_model, dt)
        p["xattn"] = L.init_cross_attention(ks[2], cfg)
    return p


def _block(p: Params, cfg: ArchConfig, x: jax.Array, positions,
           cache: Optional[Dict], enc: Optional[jax.Array],
           window: Optional[int], attn_impl: str,
           moe_mode: str = "dropless"
           ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Generic attention+FFN block (dense/moe/vlm/audio)."""
    attn_fn = L.mla_attention if cfg.mla is not None else L.attention
    with jax.named_scope(scopes.SELF_ATTN):
        a, new_cache = attn_fn(p["attn"], cfg,
                               L.rmsnorm(p["norm1"], x, cfg.norm_eps),
                               positions, kv_cache=cache, window=window,
                               attn_impl=attn_impl)
        x = x + a
    if enc is not None and "xattn" in p:
        with jax.named_scope(scopes.CROSS_ATTN):
            x = x + L.cross_attention(
                p["xattn"], cfg, L.rmsnorm(p["norm_x"], x, cfg.norm_eps),
                enc, attn_impl=attn_impl)
    with jax.named_scope(scopes.FFN):
        f, aux = _ffn(p, cfg, x, cache, moe_mode)
        x = constrain_batch(x + f)
    return x, new_cache, aux


def _ffn(p: Params, cfg: ArchConfig, x: jax.Array, cache: Optional[Dict],
         moe_mode: str) -> Tuple[jax.Array, jax.Array]:
    """The block's feed-forward (MLP or experts) on ``norm2(x)``:
    (output, router aux loss)."""
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        if cache is not None and moe_mode == "capacity":
            # beyond-paper decode MoE: capacity dispatch over the decode
            # batch with cap = 4x the balanced load (t*k/E).  Expert
            # matmul work is E*cap*3*d*f — ~E/(4k) times less than the
            # naive dropless path that runs all E experts on every token.
            # P(drop) under near-uniform routing is negligible
            # (Binomial tail beyond 4x mean); cf. EXPERIMENTS.md §Perf.
            t = x.shape[0] * x.shape[1]
            mo = cfg.moe
            cap = max(8, -(-t * mo.top_k * 4 // mo.n_experts))
            f, aux = L.moe_ffn(p["ffn"], cfg, h, dropless=False,
                               group_size=t,
                               capacity_override=min(cap, t))
        else:
            # default decode: dense all-experts gating (exact, simple);
            # training/prefill: grouped capacity dispatch
            f, aux = L.moe_ffn(p["ffn"], cfg, h,
                               dropless=cache is not None)
    else:
        f = L.mlp(p["ffn"], h)
    return f, aux


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ---------------- init ----------------
    def init(self, key) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        k_emb, k_layers, k_head, k_attn = jax.random.split(key, 4)
        params: Params = {
            "embedding": L.init_embedding(k_emb, cfg.vocab, cfg.d_model, dt),
            "final_norm": L.init_rmsnorm(cfg.d_model, dt),
        }
        if not cfg.tied_embeddings:
            params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.vocab),
                                             dtype=dt)
        if cfg.family == "hybrid":
            n = cfg.n_layers
            keys = jax.random.split(k_layers, n)
            params["mamba"] = jax.vmap(
                lambda k: S.init_mamba2(k, cfg))(keys)
            params["shared_attn"] = _init_block(k_attn, cfg)  # ONE shared
        elif cfg.family == "ssm":
            n = cfg.n_layers
            keys = jax.random.split(k_layers, n)
            params["mlstm"] = jax.vmap(lambda k: X.init_mlstm(k, cfg))(keys)
            params["slstm"] = jax.vmap(lambda k: X.init_slstm(k, cfg))(keys)
        else:
            keys = jax.random.split(k_layers, cfg.n_layers)
            params["layers"] = jax.vmap(lambda k: _init_block(k, cfg))(keys)
        return params

    # ---------------- wait-free backprop block partition ----------------
    def grad_blocks(self, params: Params) -> Tuple[str, ...]:
        """Top-level parameter blocks in BACKWARD-EMISSION order — the
        ``custom_vjp`` hook boundaries wait-free exchange
        (``ExchangeConfig(overlap='backward')``) snaps its buckets to.

        Layer stacks are scanned (``jax.lax.scan`` over stacked params
        for every family: transformer ``layers``, hybrid
        ``mamba``/``shared_attn``, ssm ``mlstm``/``slstm``), so the
        finest autodiff-visible emission events are the TOP-LEVEL param
        groups: a scanned stack's cotangent materialises in one piece
        when the scan's backward completes.  Dict flattening is
        key-sorted and backward emits leaves in reverse flatten order
        (head first, embedding last) — the same convention the
        BucketSchedule's readiness keys already encode — so the
        partition is simply the sorted keys, reversed."""
        return tuple(sorted(params.keys(), reverse=True))

    # ---------------- heads ----------------
    def head(self, params: Params, h: jax.Array) -> jax.Array:
        if self.cfg.tied_embeddings:
            return L.tied_logits(params["embedding"], h)
        return jnp.einsum("bsd,dv->bsv", h, params["lm_head"])

    # ---------------- forward (train / prefill) ----------------
    def forward(self, params: Params, batch: Dict[str, jax.Array],
                taps: Optional[jax.Array] = None,
                attn_impl: str = "xla_chunked",
                window: Optional[int] = None,
                remat: bool = False) -> jax.Array:
        """Returns final hidden states at TEXT token positions (B, S, d)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        with jax.named_scope(scopes.EMBED):
            x = constrain_batch(L.embed(params["embedding"], tokens,
                                        tap=taps))
        enc = None
        n_prefix = 0
        if cfg.frontend is not None:
            fe = batch["frontend"].astype(x.dtype)
            if cfg.frontend.cross_attention:
                enc = fe
            else:                                   # vlm prefix
                n_prefix = fe.shape[1]
                x = jnp.concatenate([fe, x], axis=1)
        positions = jnp.arange(x.shape[1])
        with jax.named_scope(scopes.LAYERS):
            x, aux = self._stack(params, x, positions, enc, window,
                                 attn_impl, remat)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if n_prefix:
            x = x[:, n_prefix:]
        return x, aux

    def _stack(self, params, x, positions, enc, window, attn_impl, remat):
        """The layer stack, scanned over its stacked params: (hidden
        states, router aux loss)."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            x = self._hybrid_forward(params, x, positions, window, attn_impl,
                                     remat)
            return x, jnp.zeros((), jnp.float32)
        if cfg.family == "ssm":
            return (self._xlstm_forward(params, x, remat),
                    jnp.zeros((), jnp.float32))

        def block_fn(lp, xx):
            return _block(lp, cfg, xx, positions, None, enc, window,
                          attn_impl)
        if remat:
            block_fn = jax.checkpoint(block_fn)

        def body(carry, lp):
            xx, aux = carry
            xx, _, a = block_fn(lp, xx)
            return (xx, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        return x, aux

    def _hybrid_forward(self, params, x, positions, window, attn_impl,
                        remat=False):
        cfg = self.cfg
        period = cfg.attn_every
        n = cfg.n_layers
        n_seg = n // period
        trailing = n - n_seg * period

        def seg_tree(a):
            return a[:n_seg * period].reshape((n_seg, period) + a.shape[1:])

        seg_params = jax.tree_util.tree_map(seg_tree, params["mamba"])
        shared = params["shared_attn"]

        def mamba_scan(x, stacked):
            def body(xx, lp):
                return constrain_batch(xx + S.mamba2_forward(lp, cfg, xx)), None
            x, _ = jax.lax.scan(body, x, stacked)
            return x

        def seg_fn(xx, lp):
            xx = mamba_scan(xx, lp)
            out, _, _ = _block(shared, cfg, xx, positions, None, None,
                               window, attn_impl)
            return out
        if remat:
            seg_fn = jax.checkpoint(seg_fn)

        def seg_body(xx, lp):
            return seg_fn(xx, lp), None

        x, _ = jax.lax.scan(seg_body, x, seg_params)
        if trailing:
            tail = jax.tree_util.tree_map(
                lambda a: a[n_seg * period:], params["mamba"])
            x = mamba_scan(x, tail)
        return x

    def _xlstm_forward(self, params, x, remat=False):
        cfg = self.cfg
        flags = jnp.array([i % cfg.xlstm.slstm_every == 1
                           for i in range(cfg.n_layers)])

        def body(xx, inp):
            flag, pm, ps = inp

            def do_s(xx):
                y, _ = X.slstm_forward(ps, cfg, xx)
                return y

            def do_m(xx):
                y, _ = X.mlstm_forward(pm, cfg, xx)
                return y

            return constrain_batch(xx + jax.lax.cond(flag, do_s, do_m, xx)), None

        if remat:
            inner = body
            def body(xx, inp):      # noqa: F811
                return jax.checkpoint(lambda a, b: inner(a, b)[0])(xx, inp), None
        x, _ = jax.lax.scan(body, x, (flags, params["mlstm"],
                                      params["slstm"]))
        return x

    # ---------------- loss ----------------
    def loss(self, params: Params, batch: Dict[str, jax.Array],
             taps: Optional[jax.Array] = None,
             attn_impl: str = "xla_chunked",
             window: Optional[int] = None,
             loss_chunk: int = 1024,
             remat: bool = False) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        h, aux = self.forward(params, batch, taps=taps, attn_impl=attn_impl,
                              window=window, remat=remat)
        labels = batch["labels"]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        b, s = labels.shape
        chunk = min(loss_chunk, s)
        pad = (-s) % chunk
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = (s + pad) // chunk
        hc = h.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
        mc = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

        def chunk_loss(carry, inp):
            hh, ll, mm = inp
            logits = self.head(params, hh).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, ll[..., None],
                                         axis=-1)[..., 0]
            nll = (lse - picked) * mm
            tot, cnt = carry
            return (tot + jnp.sum(nll), cnt + jnp.sum(mm)), None

        with jax.named_scope(scopes.HEAD):
            (tot, cnt), _ = jax.lax.scan(
                chunk_loss, (jnp.zeros((), jnp.float32),
                             jnp.zeros((), jnp.float32)), (hc, lc, mc))
        ce = tot / jnp.maximum(cnt, 1.0)
        total = ce
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_weight * aux
        metrics = {"ce": ce, "aux": aux, "tokens": cnt}
        return total, metrics

    # ---------------- serving ----------------
    def init_cache(self, batch: int, cache_len: int) -> Dict:
        """Zeros cache pytree.  ``cache_len`` = seq_len (full cache) or the
        sliding window size (ring=True)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        common = {"length": jnp.zeros((batch,), jnp.int32)}
        if cfg.family == "hybrid":
            n = cfg.n_layers
            n_seg = n // cfg.attn_every
            mamba = jax.vmap(lambda _: S.mamba2_init_cache(cfg, batch, dt))(
                jnp.arange(n))
            kvd = cfg.resolved_head_dim
            attn = {"k": jnp.zeros((n_seg, batch, cache_len, cfg.n_kv_heads,
                                    kvd), dt),
                    "v": jnp.zeros((n_seg, batch, cache_len, cfg.n_kv_heads,
                                    kvd), dt)}
            return {**common, "mamba": mamba, "attn": attn}
        if cfg.family == "ssm":
            n = cfg.n_layers
            ml = jax.vmap(lambda _: X.mlstm_init_state(cfg, batch))(
                jnp.arange(n))
            sl = jax.vmap(lambda _: X.slstm_init_state(cfg, batch))(
                jnp.arange(n))
            return {**common, "mlstm": ml, "slstm": sl}
        if cfg.mla is not None:
            m = cfg.mla
            return {**common,
                    "ckv": jnp.zeros((cfg.n_layers, batch, cache_len,
                                      m.kv_lora), dt),
                    "kr": jnp.zeros((cfg.n_layers, batch, cache_len,
                                     m.rope_dim), dt)}
        kvd = cfg.resolved_head_dim
        return {**common,
                "k": jnp.zeros((cfg.n_layers, batch, cache_len,
                                cfg.n_kv_heads, kvd), dt),
                "v": jnp.zeros((cfg.n_layers, batch, cache_len,
                                cfg.n_kv_heads, kvd), dt)}

    def prefill(self, params: Params, cache: Dict, tokens: jax.Array,
                enc: Optional[jax.Array] = None,
                embeds: Optional[jax.Array] = None,
                window: Optional[int] = None,
                attn_impl: str = "xla_chunked",
                ring: bool = False) -> Tuple[jax.Array, Dict]:
        """Sequential prefill: feed ``tokens`` (B, S) one position at a time
        through ``decode_step``, returning (last logits, cache).  ``embeds``
        (B, P, d), if given, are consumed FIRST (VLM patch prefix)."""
        if embeds is not None:
            def ebody(c, e):
                logits, c = self.decode_step(params, c, None, enc=enc,
                                             window=window,
                                             attn_impl=attn_impl, ring=ring,
                                             input_embeds=e[:, None, :])
                return c, logits
            cache, _ = jax.lax.scan(ebody, cache,
                                    embeds.transpose(1, 0, 2))

        def body(c, t):
            logits, c = self.decode_step(params, c, t[:, None], enc=enc,
                                         window=window, attn_impl=attn_impl,
                                         ring=ring)
            return c, logits

        cache, all_logits = jax.lax.scan(body, cache, tokens.T)
        return all_logits[-1], cache

    def reset_slots(self, cache: Dict, mask: jax.Array) -> Dict:
        """Continuous batching: reset the slots where ``mask`` (B,) is
        True to a fresh-request state.  Attention caches only need their
        per-slot ``length`` zeroed (masking hides stale rows); recurrent
        states (SSM/xLSTM/conv) are re-initialised in place."""
        b = cache["length"].shape[0]
        fresh = self.init_cache(b, _cache_len(cache))

        def sel(path, old, init):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "length":
                return jnp.where(mask, init, old)
            if old.ndim >= 2 and old.shape[1] == b:      # (L, B, ...)
                m = mask.reshape((1, b) + (1,) * (old.ndim - 2))
                return jnp.where(m, init, old)
            if old.ndim >= 1 and old.shape[0] == b:      # (B, ...)
                m = mask.reshape((b,) + (1,) * (old.ndim - 1))
                return jnp.where(m, init, old)
            return old

        return jax.tree_util.tree_map_with_path(sel, cache, fresh)

    def decode_step(self, params: Params, cache: Dict,
                    tokens: Optional[jax.Array],
                    enc: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    attn_impl: str = "xla_chunked",
                    ring: bool = False,
                    input_embeds: Optional[jax.Array] = None,
                    moe_mode: str = "dropless",
                    n_valid: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Dict]:
        """One decode step.  tokens (B, 1) -> logits (B, vocab).
        ``input_embeds`` (B, 1, d) bypasses the token embedding (VLM patch
        positions).

        Chunked prefill: tokens (B, s) with s > 1 runs all s positions
        through one step (attention families, non-ring caches only — the
        per-row causal mask in ``decode_attention`` keeps it exact) and
        returns ALL s logits rows (B, s, vocab).  ``n_valid`` (B,), when
        given, is the per-slot count of REAL tokens in the chunk: the
        cache length advances by ``n_valid`` instead of s, so rows past a
        slot's valid count are write-garbage the caller discards (the
        paged writeback drops them; dense callers must not mix lengths).
        """
        cfg = self.cfg
        if input_embeds is not None:
            x = input_embeds
        else:
            x = L.embed(params["embedding"], tokens)
        s = x.shape[1]
        length = cache["length"]                     # (B,) per-slot
        positions = length[:, None] + jnp.arange(s)[None, :]

        if cfg.family == "hybrid":
            x, cache = self._hybrid_decode(params, cache, x, positions,
                                           enc, window, attn_impl, ring)
        elif cfg.family == "ssm":
            x, cache = self._xlstm_decode(params, cache, x)
        else:
            if cfg.mla is not None:
                stacked = {"ckv": cache["ckv"], "kr": cache["kr"]}
            else:
                stacked = {"k": cache["k"], "v": cache["v"]}

            def body(xx, inp):
                lp, lc = inp
                lc = {**lc, "length": length, "ring": ring}
                xx, nc, _ = _block(lp, cfg, xx, positions, lc, enc,
                                   window, attn_impl, moe_mode=moe_mode)
                nc.pop("length"); nc.pop("ring")
                return xx, nc

            x, new_stacked = jax.lax.scan(body, x,
                                          (params["layers"], stacked))
            cache = {**cache, **new_stacked}
        cache["length"] = length + (n_valid if n_valid is not None else s)
        h = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self.head(params, h)
        return (logits if s > 1 else logits[:, -1]), cache

    def _hybrid_decode(self, params, cache, x, positions, enc, window,
                       attn_impl, ring):
        cfg = self.cfg
        period = cfg.attn_every
        n = cfg.n_layers
        n_seg = n // period
        trailing = n - n_seg * period
        length = cache["length"]

        def seg_tree(a):
            return a[:n_seg * period].reshape((n_seg, period) + a.shape[1:])

        seg_params = jax.tree_util.tree_map(seg_tree, params["mamba"])
        seg_cache = jax.tree_util.tree_map(seg_tree, cache["mamba"])
        shared = params["shared_attn"]

        def mamba_scan(x, stacked_p, stacked_c):
            def body(xx, inp):
                lp, lc = inp
                y, nc = S.mamba2_decode(lp, cfg, xx, lc)
                return xx + y, nc
            return jax.lax.scan(body, x, (stacked_p, stacked_c))

        def seg_body(xx, inp):
            lp, lc, ac = inp
            xx, ncm = mamba_scan(xx, lp, lc)
            ac = {**ac, "length": length, "ring": ring}
            xx, nca, _ = _block(shared, cfg, xx, positions, ac, enc,
                                window, attn_impl)
            nca.pop("length"); nca.pop("ring")
            return xx, (ncm, nca)

        x, (new_mamba_seg, new_attn) = jax.lax.scan(
            seg_body, x, (seg_params, seg_cache, cache["attn"]))
        new_mamba = jax.tree_util.tree_map(
            lambda a: a.reshape((n_seg * period,) + a.shape[2:]),
            new_mamba_seg)
        if trailing:
            tail_p = jax.tree_util.tree_map(
                lambda a: a[n_seg * period:], params["mamba"])
            tail_c = jax.tree_util.tree_map(
                lambda a: a[n_seg * period:], cache["mamba"])
            x, new_tail = mamba_scan(x, tail_p, tail_c)
            new_mamba = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                new_mamba, new_tail)
        cache = {**cache, "mamba": new_mamba, "attn": new_attn}
        return x, cache

    def _xlstm_decode(self, params, cache, x):
        cfg = self.cfg
        flags = jnp.array([i % cfg.xlstm.slstm_every == 1
                           for i in range(cfg.n_layers)])

        def body(xx, inp):
            flag, pm, ps, cm, cs = inp

            def do_s(args):
                xx, cm, cs = args
                y, ncs = X.slstm_forward(ps, cfg, xx, state=cs)
                return y, cm, ncs

            def do_m(args):
                xx, cm, cs = args
                y, ncm = X.mlstm_forward(pm, cfg, xx, state=cm)
                return y, ncm, cs

            y, ncm, ncs = jax.lax.cond(flag, do_s, do_m, (xx, cm, cs))
            return xx + y, (ncm, ncs)

        x, (new_m, new_s) = jax.lax.scan(
            body, x, (flags, params["mlstm"], params["slstm"],
                      cache["mlstm"], cache["slstm"]))
        return x, {**cache, "mlstm": new_m, "slstm": new_s}


def _cache_len(cache: Dict) -> int:
    """Recover the cache sequence length from a KV-style leaf."""
    for key in ("k", "ckv"):
        if key in cache:
            leaf = cache[key]
            return leaf.shape[2]                 # (L, B, C, ...)
    if "attn" in cache:
        return cache["attn"]["k"].shape[2]       # (n_seg, B, C, KV, HD)
    return 1          # pure-recurrent families have no length-shaped cache


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg=cfg)
