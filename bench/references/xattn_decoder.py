"""Plain reference of the pre-norm decoder, with cross-attention to
encoder states where the config has ``frontend_frames``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no chunking, no exchange plan, no sharded weights (its row
blocks alone are dealt over the cell's chips).  It imports nothing of
the program under test.  It defines

* ``check_config``: what of a config file this module can model;
* the weights: ``init_params`` draws them from the seed in one jitted
  call, in the dtype they are trained in (the benchmark hands the same
  values to the program, which must lay its parameters out the same way);
* the loss: token embedding, ``n_layers`` pre-norm blocks of causal
  self-attention with rotary positions, cross-attention to the encoder
  states (only with ``frontend_frames``) and a SwiGLU feed-forward, a
  final RMSNorm, logits through the tied embedding or a separate
  ``lm_head`` (d_model x vocab, untied) and the masked mean
  cross-entropy;
* one training step: gradients of the mean loss over the whole global
  batch (what averaging every chip's gradient computes) and the AdamW
  update under the Noam schedule;
* the counts the metrics read: ``train_step_flops`` and ``param_count``
  from the config's shapes.

Departures from the published architectures, which the program shares
and the reference therefore follows: RMSNorm instead of LayerNorm,
rotary positions instead of sinusoidal ones, SwiGLU (three matrices)
instead of a two-matrix ReLU feed-forward, and no encoder: the encoder
states are random inputs drawn by the benchmark.

``precision="fp8"`` is the control, the step that would tempt a change
to the program: every matmul takes its operands rounded to float8 e4m3
and, in the backward pass, its cotangent rounded to float8 e5m2, each
under a per-tensor scale, and accumulates in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
# the program's AdamW defaults and Noam schedule (Vaswani et al. 2017)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.98, 1e-9
NOAM_SCALE = 2.0
FP8, FP8_MAX = jnp.float8_e4m3fn, 448.0          # forward operands
FP8_GRAD, FP8_GRAD_MAX = jnp.float8_e5m2, 57344.0   # cotangents
# faults that ``train_readings`` can plant (besides "none"), and the one
# that needs several chips
FAULTS = ("none", "half_batch", "no_exchange", "dup_overwrite")
MULTI_CHIP_FAULTS = ("no_exchange",)
FAMILIES = ("audio", "dense")


def check_config(cfg: Dict) -> None:
    """Raise ValueError where the config is not the decoder this module
    models: heads that tile d_model (no separate head_dim), whole
    groups of query heads per key head, and no block of another kind."""
    d, nh, nkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    bad = []
    if cfg["family"] not in FAMILIES:
        bad.append(f"family {cfg['family']!r} not in {FAMILIES}")
    if d % nh or cfg.get("head_dim", d // nh) * nh != d:
        bad.append("head_dim x n_heads != d_model")
    if nh % nkv:
        bad.append("n_heads not a multiple of n_kv_heads")
    bad += [f"a {k!r} block" for k in ("moe", "mla", "ssm", "xlstm",
                                        "frontend") if k in cfg]
    if bad:
        raise ValueError(f"{cfg.get('name')}: not a decoder this reference "
                         f"models: {'; '.join(bad)}")


def _widths(cfg: Dict) -> Tuple[int, int, int, int, int, int]:
    """(d_model, d_ff, vocab, layers, query width, key/value width)."""
    d = cfg["d_model"]
    hd = d // cfg["n_heads"]
    return (d, cfg["d_ff"], cfg["vocab"], cfg["n_layers"],
            cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def forward_flops(cfg: Dict, rows: int, seq: int,
                  causal_full: bool = False) -> Dict[str, float]:
    """Forward matmul FLOPs of ``rows`` x ``seq`` tokens, by part."""
    d, f, v, n, h, kv = _widths(cfg)
    fr = cfg.get("frontend_frames", 0)
    tok = rows * seq
    pairs = rows * (seq * seq if causal_full else causal_pairs(seq))
    # q and o of cross-attention, where there is one
    xqo = d * h + h * d if fr else 0
    return {
        # q, k, v, o of self-attention; q and o of cross-attention;
        # the SwiGLU gate, up and down projections
        "token_matmuls": 2.0 * tok * n * (d * h + 2 * d * kv + h * d
                                          + xqo + 3 * d * f),
        # keys and values of the encoder states
        "frame_matmuls": 2.0 * rows * fr * n * 2 * d * h,
        # scores and weighted values
        "self_attention": 2.0 * 2 * pairs * h * n,
        "cross_attention": 2.0 * 2 * tok * fr * h * n,
        # tied or not, the head is one d x vocab matmul per token
        "head": 2.0 * tok * d * v,
    }


def train_step_flops(cfg: Dict, rows: int, seq: int,
                     causal_full: bool = False) -> float:
    """Forward + backward model FLOPs of one step over rows x seq: the
    backward of a matmul costs twice its forward, except the projections
    of the encoder states, inputs that take no gradient (once); causal
    self-attention counts the lower triangle the model needs, not the
    full square a kernel may compute."""
    fwd = forward_flops(cfg, rows, seq, causal_full)
    total = 0.0
    for part, x in fwd.items():
        total += x * (2.0 if part == "frame_matmuls" else 3.0)
    return total


def param_count(cfg: Dict) -> int:
    """Parameters of the layout ``param_shapes`` gives."""
    return sum(math.prod(shape) for shape, _ in param_shapes(cfg).values())


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Leaf path -> (shape, init scale); scale 0 marks a norm (ones).
    Cross-attention and its norm only with ``frontend_frames``; a
    separate ``lm_head`` (d_model x vocab) where the head is untied."""
    d, f, v, n, h, kv = _widths(cfg)
    cross = "frontend_frames" in cfg
    out = {"embedding": ((v, d), d ** -0.5),
           "final_norm.scale": ((d,), 0.0)}
    if not cfg["tied_embeddings"]:
        out["lm_head"] = ((d, v), d ** -0.5)
    mats = [("attn.wq", (d, h)), ("attn.wk", (d, kv)),
            ("attn.wv", (d, kv)), ("attn.wo", (h, d))]
    if cross:
        mats += [("xattn.wq", (d, h)), ("xattn.wk", (d, h)),
                 ("xattn.wv", (d, h)), ("xattn.wo", (h, d))]
    mats += [("ffn.w_gate", (d, f)), ("ffn.w_up", (d, f)),
             ("ffn.w_down", (f, d))]
    for name, shape in mats:
        out["layers." + name] = ((n,) + shape, shape[0] ** -0.5)
    for name in ("norm1", "norm2") + (("norm_x",) if cross else ()):
        out[f"layers.{name}.scale"] = ((n, d), 0.0)
    return out


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def init_params(cfg: Dict, seed: int, dtype: str, sharding=None) -> Dict:
    """Seeded normal weights scaled by fan-in (embedding: d_model**-0.5),
    norms at one, made on the device in one jitted call."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    return _init_fn(items, dtype, sharding)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _init_fn(cfg_items, dtype: str, sharding):
    shapes = param_shapes(dict(cfg_items))
    dt = jnp.dtype(dtype)

    def make(key):
        flat = {}
        for i, (path, (shape, scale)) in enumerate(sorted(shapes.items())):
            if scale == 0.0:
                flat[path] = jnp.ones(shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * scale).astype(dt)
        return _nest(flat)

    return jax.jit(make, out_shardings=sharding)


def leaf_names(params) -> List[str]:
    """Dotted leaf paths in the order ``jax.tree_util`` flattens them."""
    return [".".join(str(k.key) for k in path) for path, _ in
            jax.tree_util.tree_leaves_with_path(params)]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _quantize(x, dtype, fmax):
    """Round to a float8 format under a per-tensor scale."""
    scale = fmax / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(eq, a, b):
    return jnp.einsum(eq, _quantize(a, FP8, FP8_MAX),
                      _quantize(b, FP8, FP8_MAX), precision=HIGHEST)


def _fp8_einsum_fwd(eq, a, b):
    qa, qb = _quantize(a, FP8, FP8_MAX), _quantize(b, FP8, FP8_MAX)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_einsum_bwd(eq, res, g):
    """Gradients from the quantized operands and the cotangent rounded to
    float8 e5m2, as float8 training recipes compute them."""
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_quantize(g, FP8_GRAD, FP8_GRAD_MAX))


_fp8_einsum.defvjp(_fp8_einsum_fwd, _fp8_einsum_bwd)


def _mm(eq: str, a, b, precision: str):
    if precision == "fp8":
        return _fp8_einsum(eq, a, b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(scale, x, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotary positions on interleaved pairs, x (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.arange(s, dtype=np.float32)[:, None] * inv      # (S, D/2)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def _attend(q, k, v, causal: bool, precision: str):
    """q (B, Sq, H, D), k/v (B, Sk, H, D): softmax attention."""
    d = q.shape[-1]
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) * d ** -0.5
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = np.arange(sq)[:, None] >= np.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _mm("bhqk,bkhd->bqhd", p, v, precision)


def _block(lp, x, enc, cfg, precision):
    b, s, d = x.shape
    nh, nkv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = d // nh
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]

    h = _rmsnorm(lp["norm1"]["scale"], x, eps)
    a = lp["attn"]
    q = _mm("bsd,df->bsf", h, a["wq"], precision).reshape(b, s, nh, hd)
    k = _mm("bsd,df->bsf", h, a["wk"], precision).reshape(b, s, nkv, hd)
    v = _mm("bsd,df->bsf", h, a["wv"], precision).reshape(b, s, nkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    o = _attend(q, k, v, True, precision).reshape(b, s, nh * hd)
    x = x + _mm("bsf,fd->bsd", o, a["wo"], precision)

    if enc is not None:
        h = _rmsnorm(lp["norm_x"]["scale"], x, eps)
        c = lp["xattn"]
        fr = enc.shape[1]
        q = _mm("bsd,df->bsf", h, c["wq"], precision).reshape(b, s, nh, hd)
        k = _mm("bfd,de->bfe", enc, c["wk"], precision).reshape(b, fr, nh,
                                                                 hd)
        v = _mm("bfd,de->bfe", enc, c["wv"], precision).reshape(b, fr, nh,
                                                                 hd)
        o = _attend(q, k, v, False, precision).reshape(b, s, nh * hd)
        x = x + _mm("bsf,fd->bsd", o, c["wo"], precision)

    h = _rmsnorm(lp["norm2"]["scale"], x, eps)
    m = lp["ffn"]
    g = _mm("bsd,df->bsf", h, m["w_gate"], precision)
    u = _mm("bsd,df->bsf", h, m["w_up"], precision)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"],
                   precision)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lookup_overwrite(rows, table, ids):
    return table[ids]


def _lookup_overwrite_fwd(rows, table, ids):
    return table[ids], ids


def _lookup_overwrite_bwd(rows, ids, ct):
    """The planted densify fault: a row looked up more than once keeps one
    of its gradients instead of their sum."""
    d = ct.shape[-1]
    grad = jnp.zeros((rows, d), ct.dtype).at[ids.reshape(-1)].set(
        ct.reshape(-1, d))
    return grad, None


_lookup_overwrite.defvjp(_lookup_overwrite_fwd, _lookup_overwrite_bwd)


def nll_sum(params, batch, cfg: Dict, precision: str = "f32",
            overwrite: bool = False):
    """(sum of masked token NLLs, number of target tokens) of a batch;
    ``overwrite`` plants the densify fault in the embedding's gradient."""
    table = params["embedding"]
    x = (_lookup_overwrite(table.shape[0], table, batch["tokens"])
         if overwrite else table[batch["tokens"]])
    enc = batch.get("frontend")
    for i in range(cfg["n_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x = _block(lp, x, enc, cfg, precision)
    h = _rmsnorm(params["final_norm"]["scale"], x, cfg["norm_eps"])
    if "lm_head" in params:
        logits = _mm("bsd,dv->bsv", h, params["lm_head"], precision)
    else:
        logits = _mm("bsd,vd->bsv", h, params["embedding"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None],
                                 axis=-1)[..., 0]
    mask = batch["loss_mask"]
    return jnp.sum((lse - picked) * mask), jnp.sum(mask)


# ---------------------------------------------------------------------------
# one training step over the global batch, in blocks of rows
# ---------------------------------------------------------------------------

def noam_lr(step: int, d_model: int, warmup: int) -> float:
    t = max(float(step), 1.0)
    return NOAM_SCALE * d_model ** -0.5 * min(t ** -0.5, t * warmup ** -1.5)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "overwrite", "mesh"))
def _grad_blocks(params, blocks, cfg_items, precision, overwrite, mesh):
    """Sum of NLL gradients over row blocks stacked on dim 0, the blocks
    dealt out in order over the devices of ``mesh`` (axis ``rows``);
    returns (per-block NLL sums, per-block token counts, summed
    gradients on every device)."""
    cfg = dict(cfg_items)

    def local(params, blocks):
        def body(acc, blk):
            (s, n), g = jax.value_and_grad(
                lambda p: nll_sum(p, blk, cfg, precision, overwrite),
                has_aux=True)(params)
            return jax.tree_util.tree_map(jnp.add, acc, g), (s, n)

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        g, (sums, counts) = jax.lax.scan(body, zeros, blocks)
        return sums, counts, jax.lax.psum(g, "rows")

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P("rows")),
                         out_specs=(P("rows"), P("rows"), P()),
                         check_vma=False)(params, blocks)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adam(params, mu, nu, grads, t, lr):
    bc1 = 1 - ADAM_B1 ** t
    bc2 = 1 - ADAM_B2 ** t
    mu = jax.tree_util.tree_map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                                mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + ADAM_EPS)),
        params, mu, nu)
    return params, mu, nu


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.reshape(-1))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def _leaf_delta_norms(a, b):
    return jnp.stack([jnp.linalg.norm((x - y.astype(x.dtype)).reshape(-1))
                      for x, y in
                      zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b))])


def block_rows(cfg: Dict, rows: int, seq: int) -> int:
    """Rows per block: about 400 MB of f32 logits, dividing ``rows``."""
    r = max(1, int(4e8 // (4 * cfg["vocab"] * seq)))
    while rows % r:
        r -= 1
    return r


def _mesh(devices: List) -> Mesh:
    return Mesh(np.array(devices), ("rows",))


def train_readings(params0, batches: List[Dict[str, np.ndarray]], cfg: Dict,
                   warmup: int, shards: int, devices: List,
                   precision: str = "f32", fault: str = "none") -> Dict:
    """Train ``len(batches)`` steps from ``params0`` and read what the
    benchmark compares: each step's loss on the first shard's rows, the
    first step's gradient (on the host) and its per-leaf norms, and the
    per-leaf norm of the parameters' change over all the steps.

    The blocks of rows of a step are dealt out in order over
    ``devices``, as many of them as divide the rows trained on; each
    sums its blocks' gradients and the sums are added.  Weights, moments
    and the update are on every one.

    ``fault`` plants a fault for the calibration of the limits:
    ``"half_batch"`` trains on the first half of every batch,
    ``"no_exchange"`` on the first shard's rows alone (no exchange
    between chips), ``"dup_overwrite"`` densifies the embedding's
    gradient by overwriting rather than adding repeated rows (within each
    block of rows; the blocks' gradients are still added)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    every = NamedSharding(_mesh(devices), P())
    with jax.default_matmul_precision("highest"):
        # the starting weights, in their own dtype, and in float32
        p0 = jax.device_put(params0, every)
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p0)
        mu = jax.tree_util.tree_map(jnp.zeros_like, p)
        nu = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches, start=1):
            n_rows, seq = batch["tokens"].shape
            used = {"half_batch": n_rows // 2,
                    "no_exchange": n_rows // shards}.get(fault, n_rows)
            mesh = _mesh(devices[:math.gcd(used, len(devices))])
            per_dev = used // mesh.size
            r = block_rows(cfg, math.gcd(per_dev, n_rows // shards), seq)
            blocks = {k: jax.device_put(np.asarray(v)[:used].reshape(
                (used // r, r) + v.shape[1:]), NamedSharding(mesh, P("rows")))
                for k, v in batch.items()}
            sums, counts, g = _grad_blocks(
                jax.device_put(p, NamedSharding(mesh, P())), blocks,
                cfg_items, precision, fault == "dup_overwrite", mesh)
            g = jax.device_put(g, every)
            sums, counts = np.asarray(sums), np.asarray(counts)
            n_tot = counts.sum()
            g = jax.tree_util.tree_map(lambda a: a / n_tot, g)
            first = (n_rows // shards) // r           # blocks of shard 0
            losses.append(float(sums[:first].sum() / counts[:first].sum()))
            if t == 1:
                grad_norms = np.asarray(_leaf_norms(g))
                grad = [np.asarray(x) for x in jax.tree_util.tree_leaves(g)]
            lr = noam_lr(t, cfg["d_model"], warmup)
            p, mu, nu = _adam(p, mu, nu, g, jnp.float32(t), jnp.float32(lr))
            del g
        delta = np.asarray(_leaf_delta_norms(p, p0))
    return {"loss": losses, "grad_norm": grad_norms, "delta_norm": delta,
            "grad": grad, "leaves": leaf_names(p)}
