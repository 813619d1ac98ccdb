"""The analytic counts (the decoder reference's ``train_step_flops`` and
``param_count``, ``bench/flops.py``'s densify bytes) against hand counts
and the program's own jaxpr-level counter."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.references import xattn_decoder as ref
from bench.tests.conftest import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_transformer_big_step_matches_hand_count():
    cfg = config("transformer-big")
    # 6 x 125.9M non-embedding params x 4096 tokens + the head's 0.85
    # TFLOP + ~0.15 TFLOP of attention: about 4.1 TFLOP per step
    total = ref.train_step_flops(cfg, 16, 256)
    assert total == pytest.approx(4.1e12, rel=0.05)
    head = 3 * ref.forward_flops(cfg, 16, 256)["head"]
    assert head == pytest.approx(0.85e12, rel=0.01)
    non_embedding = ref.param_count(cfg) - cfg["vocab"] * cfg["d_model"]
    assert non_embedding == pytest.approx(125.9e6, rel=0.001)


@pytest.mark.parametrize("name", ["transformer-big", "seamless-m4t-v2-dec6"])
def test_param_count_is_the_configs(name):
    cfg = config(name)
    assert ref.param_count(cfg) == cfg["params"]


def program_step_flops(arch, rows, seq, monkeypatch):
    """Matmul FLOPs of the program's loss and gradient over rows x seq
    (repro.launch.flops, elementwise ops left out).  The count runs with
    jit disabled: the counter walks ``pjit`` equations, and this JAX
    names nested jitted calls ``jit``, so the attention inside them
    would go uncounted."""
    from repro.launch import flops as program_flops
    from repro.models import build_model
    from repro.training.gradients import grad_contributions

    monkeypatch.setattr(program_flops, "ELEMENTWISE_1", set())
    model = build_model(arch)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((rows, seq), jnp.int32)}
    if arch.frontend is not None:
        batch["frontend"] = jax.ShapeDtypeStruct(
            (rows, arch.frontend.n_embeds, arch.d_model), jnp.float32)
    with jax.disable_jit():
        return program_flops.count_fn_flops(
            lambda p, b: grad_contributions(model, p, b,
                                            sparse_embedding=True)[0],
            params, batch)["flops"]


def test_agrees_with_the_programs_jaxpr_count(monkeypatch):
    """On a reduced config, the program's count equals the analytic one
    when causal attention is counted as the full square the program
    computes."""
    from repro.configs import get_config

    arch = get_config("transformer-big").reduced()
    cfg = {"d_model": arch.d_model, "n_heads": arch.n_heads,
           "n_kv_heads": arch.n_kv_heads, "d_ff": arch.d_ff,
           "vocab": arch.vocab, "n_layers": arch.n_layers,
           "frontend_frames": arch.frontend.n_embeds}
    rows, seq = 2, 32
    got = program_step_flops(arch, rows, seq, monkeypatch)
    want = ref.train_step_flops(cfg, rows, seq, causal_full=True)
    assert got == pytest.approx(want, rel=1e-6)
    assert ref.train_step_flops(cfg, rows, seq) < want


def test_decoder_without_frontend_agrees_with_the_programs_count(
        monkeypatch):
    """The same for the CPU test config of a decoder with neither
    cross-attention nor a tied head: the counts leave the frames out and
    count the separate head's matmul and parameters."""
    from bench import program

    cfg = json.loads((ROOT / "bench" / "tests" / "data" / "configs"
                      / "tiny-dec.json").read_text())
    arch = program.arch_config(cfg)
    rows, seq = 2, 32
    got = program_step_flops(arch, rows, seq, monkeypatch)
    assert got == pytest.approx(
        ref.train_step_flops(cfg, rows, seq, causal_full=True), rel=1e-6)
    from repro.models import build_model
    params = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    assert ref.param_count(cfg) == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def test_densify_bytes():
    cfg = config("transformer-big")
    # read 4096 ids and bf16 rows, read and write 1000 distinct rows
    assert flops.densify_bytes(cfg, 4096, 1000, 2) == \
        4096 * 4 + 4096 * 1024 * 2 + 2 * 1000 * 1024 * 2
    # untied: no head matmul wrote the table; write all of it once
    untied = dict(cfg, tied_embeddings=False)
    assert flops.densify_bytes(untied, 4096, 1000, 2) == \
        4096 * 4 + 4096 * 1024 * 2 + 33708 * 1024 * 2
    ids = np.array([[1, 1, 2, 3], [4, 4, 4, 4]])
    assert flops.unique_rows([ids], 1) == 4
    assert flops.unique_rows([ids], 2) == 2
