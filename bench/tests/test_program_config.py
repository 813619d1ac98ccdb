"""``program.arch_config``: every key of a config file that names a field
of the registered ArchConfig is set on it, nested blocks included, and
the result is checked against the file."""
import json

import jax
import pytest

from bench import harness, program
from bench.tests.conftest import ROOT
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig
from repro.models import build_model

DATA = ROOT / "bench" / "tests" / "data" / "configs"


def config(name):
    return json.loads((DATA / f"{name}.json").read_text())


def test_moe_and_mla_blocks_are_set():
    cfg = config("tiny-mla-moe")
    arch = program.arch_config(cfg)
    assert (arch.n_layers, arch.d_model, arch.vocab) == (2, 128, 512)
    assert not arch.tied_embeddings and arch.frontend is None
    # the file's keys replace the registered block's; the rest stay
    assert arch.moe == MoEConfig(n_experts=8, top_k=2, d_ff_expert=64,
                                 n_shared=1, capacity_factor=1.25,
                                 router_aux_weight=0.01)
    assert arch.mla == MLAConfig(kv_lora=32, q_lora=0, rope_dim=16,
                                 nope_dim=16, v_dim=32)
    params = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    layers = params["layers"]
    assert layers["attn"]["w_dkv"].shape == (2, 128, 32)
    assert layers["ffn"]["w_gate"].shape[:2] == (2, 8)


def test_a_nested_value_the_program_did_not_build_is_refused(monkeypatch):
    """Where the program's config comes out other than the file states
    (here: its top_k), arch_config refuses it rather than measure
    another model."""
    with_ = ArchConfig.with_

    def drop_top_k(self, **kw):
        arch = with_(self, **kw)
        moe = arch.moe.__class__(**{**arch.moe.__dict__, "top_k": 6})
        return with_(arch, moe=moe)

    monkeypatch.setattr(ArchConfig, "with_", drop_top_k)
    with pytest.raises(ValueError, match="differs"):
        program.arch_config(config("tiny-mla-moe"))


@pytest.mark.parametrize("change", [
    {"mla": {"kv_lora": 32, "latent": 8}},   # no such field
    {"xlstm": {"slstm_every": 2}},            # the arch has no such block
])
def test_a_block_the_arch_cannot_take_is_refused(change):
    with pytest.raises(ValueError):
        program.arch_config({**config("tiny-mla-moe"), **change})


def test_cross_attention_only_with_frames():
    assert program.arch_config(config("tiny")).frontend.cross_attention
    dec = program.arch_config(config("tiny-dec"))
    assert dec.frontend is None and not dec.tied_embeddings


def test_the_decoder_reference_refuses_what_it_does_not_model():
    with pytest.raises(ValueError, match="'moe' block"):
        harness.reference(config("tiny-mla-moe"))
    bad = dict(config("tiny-dec"), head_dim=64)
    with pytest.raises(ValueError, match="head_dim"):
        harness.reference(bad)
    assert harness.reference(config("tiny-dec")).FAULTS
