"""The analytic counts against hand counts and the program's own
jaxpr-level counter."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.tests.conftest import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_transformer_big_step_matches_hand_count():
    cfg = config("transformer-big")
    # 6 x 125.9M non-embedding params x 4096 tokens + the head's 0.85
    # TFLOP + ~0.15 TFLOP of attention: about 4.1 TFLOP per step
    total = flops.train_step_flops(cfg, 16, 256)
    assert total == pytest.approx(4.1e12, rel=0.05)
    head = 3 * flops.forward_flops(cfg, 16, 256)["head"]
    assert head == pytest.approx(0.85e12, rel=0.01)
    non_embedding = flops.param_count(cfg) - cfg["vocab"] * cfg["d_model"]
    assert non_embedding == pytest.approx(125.9e6, rel=0.001)


@pytest.mark.parametrize("name", ["transformer-big", "seamless-m4t-v2-dec6"])
def test_param_count_is_the_configs(name):
    cfg = config(name)
    assert flops.param_count(cfg) == cfg["params"]


def test_agrees_with_the_programs_jaxpr_count(monkeypatch):
    """On a reduced config, matmul FLOPs of the program's loss and
    gradient (repro.launch.flops, elementwise ops left out) equal the
    analytic count when causal attention is counted as the full square
    the program computes.  The count runs with jit disabled: the
    counter walks ``pjit`` equations, and this JAX names nested jitted
    calls ``jit``, so the attention inside them would go uncounted."""
    from repro.configs import get_config
    from repro.launch import flops as program_flops
    from repro.models import build_model
    from repro.training.gradients import grad_contributions

    monkeypatch.setattr(program_flops, "ELEMENTWISE_1", set())
    arch = get_config("transformer-big").reduced()
    cfg = {"d_model": arch.d_model, "n_heads": arch.n_heads,
           "n_kv_heads": arch.n_kv_heads, "d_ff": arch.d_ff,
           "vocab": arch.vocab, "n_layers": arch.n_layers,
           "frontend_frames": arch.frontend.n_embeds}
    rows, seq = 2, 32
    model = build_model(arch)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((rows, seq), jnp.int32),
             "frontend": jax.ShapeDtypeStruct(
                 (rows, cfg["frontend_frames"], cfg["d_model"]),
                 jnp.float32)}
    with jax.disable_jit():
        got = program_flops.count_fn_flops(
            lambda p, b: grad_contributions(model, p, b,
                                            sparse_embedding=True)[0],
            params, batch)["flops"]
    want = flops.train_step_flops(cfg, rows, seq, causal_full=True)
    assert got == pytest.approx(want, rel=1e-6)
    assert flops.train_step_flops(cfg, rows, seq) < want


def test_densify_bytes():
    cfg = config("transformer-big")
    # read 4096 ids and bf16 rows, read and write 1000 distinct rows
    assert flops.densify_bytes(cfg, 4096, 1000, 2) == \
        4096 * 4 + 4096 * 1024 * 2 + 2 * 1000 * 1024 * 2
    ids = np.array([[1, 1, 2, 3], [4, 4, 4, 4]])
    assert flops.unique_rows([ids], 1) == 4
    assert flops.unique_rows([ids], 2) == 2
