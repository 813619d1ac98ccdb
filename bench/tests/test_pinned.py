"""What the metric readers of the chip cells get from their config and
traffic files, pinned to the values the harness gave before it took one
reference module per config: the program's config (hence its compiled
step), the counts, the batches and the weights' layout.  A change of the
harness that moves any of them moves what an existing cell measures."""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, generator, harness, program
from bench.tests.conftest import ROOT

CELLS = {
    # config: (traffic, arch_config's fields, flops per step at the
    # traffic, parameters, densify bytes per chip, seed 7's pool,
    # the weights' layout)
    "transformer-big": (
        "train_16x256_dp1",
        {"name": "transformer-big", "family": "audio", "n_layers": 6,
         "d_model": 1024, "n_heads": 16, "n_kv_heads": 16, "d_ff": 4096,
         "vocab": 33708,
         "source": "arXiv:1706.03762 / tensorflow/models official "
                   "transformer",
         "head_dim": None, "tied_embeddings": True, "qkv_bias": False,
         "rope_theta": 10000.0, "rope_fraction": 1.0, "norm_eps": 1e-05,
         "dtype": "bfloat16", "sliding_window": 8192, "attn_every": None,
         "moe": None, "mla": None, "ssm": None, "xlstm": None,
         "frontend": {"kind": "audio", "n_embeds": 256,
                      "cross_attention": True}},
        3953701945344.0, 160365568, 12114944.0,
        "73a1da784f600ce97b01b188e5fe4e485eaaa37d7fc321e06def5053413acb41",
        "acff4a867b2ae82836291eb3d3bdb2702480a3f1fb43b99a8e036ee828bb3ab9"),
    "seamless-m4t-v2-dec6": (
        "train_4x256_dp1",
        {"name": "seamless-m4t-large-v2", "family": "audio", "n_layers": 6,
         "d_model": 1024, "n_heads": 16, "n_kv_heads": 16, "d_ff": 8192,
         "vocab": 256206, "source": "arXiv:2308.11596", "head_dim": None,
         "tied_embeddings": True, "qkv_bias": False, "rope_theta": 10000.0,
         "rope_fraction": 1.0, "norm_eps": 1e-05, "dtype": "bfloat16",
         "sliding_window": 8192, "attn_every": None, "moe": None,
         "mla": None, "ssm": None, "xlstm": None,
         "frontend": {"kind": "audio", "n_embeds": 1024,
                      "cross_attention": True}},
        3064719212544.0, 463700992, 3509248.0,
        "f0a6754a1e82bbbbeeef1d0a8c25345cbf0d2f97108e1f6325882a1853e77da6",
        "f7eee64a6ac27a8f28c2b6d0d3ffb6b1c6ad30681d390f4f09e3c86867537b6b"),
}
# seed 7's weights of the CPU test config, whose layout is the chip
# configs' at other sizes
TINY_WEIGHTS = \
    "b0cc6b26db858b9d50a4b7e8483e396f01ef7f41c3666de0737185e1857f3674"


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def pool_digest(pool) -> str:
    h = hashlib.sha256()
    for batch in pool:
        for k in sorted(batch):
            h.update(k.encode())
            h.update(np.ascontiguousarray(batch[k]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_program_config_is_pinned(name):
    arch = program.arch_config(config(name))
    assert dataclasses.asdict(arch) == CELLS[name][1]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_counts_are_pinned(name):
    traffic, _, step_flops, params, densify, pool_sha, _ = CELLS[name]
    cfg = config(name)
    tr = generator.load(traffic)
    rows, seq = tr["batch_per_chip"], tr["seq_len"]
    ref = harness.reference(cfg)
    assert ref.train_step_flops(cfg, rows, seq) == step_flops
    assert ref.param_count(cfg) == params == cfg["params"]
    pool = generator.make_pool(tr, cfg, 7, 1)
    assert pool_digest(pool) == pool_sha
    got = flops.densify_bytes(cfg, rows * seq, flops.unique_rows(
        [b["tokens"] for b in pool], 1), 2)
    assert got == densify


@pytest.mark.parametrize("name", sorted(CELLS))
def test_weight_layout_is_pinned(name):
    cfg = config(name)
    shapes = harness.reference(cfg).param_shapes(cfg)
    assert hashlib.sha256(repr(sorted(shapes.items())).encode()) \
        .hexdigest() == CELLS[name][6]


def test_weights_are_pinned():
    cfg = json.loads((ROOT / "bench" / "tests" / "data" / "configs"
                      / "tiny.json").read_text())
    ref = harness.reference(cfg)
    params = ref.init_params(cfg, 7, cfg["dtype"])
    h = hashlib.sha256()
    for path, x in sorted(zip(ref.leaf_names(params),
                              jax.tree_util.tree_leaves(params))):
        h.update(path.encode())
        h.update(np.asarray(x.astype(jnp.float32)).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS
