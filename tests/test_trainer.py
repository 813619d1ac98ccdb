"""``Trainer.run`` donates the train state to its step.

The step's outputs take the buffers of its inputs, so the device holds
one copy of params, optimizer state and codec residuals: the arrays a
caller passes in are consumed, and the result is the same, bit for bit,
as that of a loop that donates nothing.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import adamw
from repro.telemetry import trace as trace_lib
from repro.training import Trainer, TrainerConfig, make_train_step
from repro.training.gradients import abstract_grad_contributions

STEPS = 3
CODECS = {"identity": dict(codec="identity"),
          "int8+ef": dict(codec="int8", error_feedback=True)}


@pytest.fixture(scope="module")
def reduced():
    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    return cfg, model, make_pipeline(cfg, 2, 16, task="copy")


def _setup(reduced, codec):
    """(step, pipe, start): a fresh train state for ``codec``, with the
    exchange state only where the codec is stateful."""
    cfg, model, pipe = reduced
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, **CODECS[codec]))
    step = make_train_step(model, opt, sparse_embedding=True)
    start = (params, opt.init(params))
    if step.stateful_exchange:
        b0 = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
        start += (opt.init_exchange_state(abstract_grad_contributions(
            model, params, b0, sparse_embedding=True)),)
    return step, pipe, start


def _run(reduced, step, pipe, state, **config):
    tr = Trainer(reduced[1], step, pipe, TrainerConfig(
        total_steps=STEPS, log_every=STEPS, **config))
    ex = state[2] if len(state) == 3 else None
    res = tr.run(state[0], state[1], log=lambda s: None, exchange_state=ex)
    return (res["params"], res["opt_state"]) + (
        (res["exchange_state"],) if ex is not None else ())


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_run_consumes_state_and_matches_undonated_loop(reduced, codec):
    """The state passed to ``run`` is deleted (params, opt_state and,
    under a stateful codec, exchange_state), and the returned state
    equals that of a non-donating ``jax.jit(step)`` loop from copies."""
    step, pipe, start = _setup(reduced, codec)
    state = jax.tree_util.tree_map(jnp.copy, start)
    plain = jax.jit(step)
    for i in range(STEPS):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        state = plain(*state, batch)[:-1]

    got = _run(reduced, step, pipe, start)
    assert len(start) == (3 if codec == "int8+ef" else 2)
    for part in start:
        leaves = jax.tree_util.tree_leaves(part)
        assert leaves and all(x.is_deleted() for x in leaves)
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(state)):
        assert x.dtype == y.dtype and bool(jnp.array_equal(x, y))


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_profiled_step_reuses_every_state_buffer(reduced, codec, tmp_path):
    """The profile's ``donation.json``: the compiled step's outputs alias
    the whole train state, and JAX warns of no donated buffer it could
    not use."""
    step, pipe, start = _setup(reduced, codec)
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(start))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _run(reduced, step, pipe, start, profile_dir=str(tmp_path),
             profile_steps=1)
    assert not [w for w in caught if "donated" in str(w.message)], \
        [str(w.message) for w in caught]
    don = trace_lib.read_donation(str(tmp_path))
    assert don == json.loads((tmp_path / trace_lib.DONATION).read_text())
    assert don["state_bytes"] == state_bytes
    assert don["aliased_bytes"] == state_bytes and don["share"] == 1.0


def test_second_run_compiles_nothing(reduced):
    """A later ``run`` of the same step finds the donating step compiled:
    nothing is traced, lowered or compiled inside it."""
    step, pipe, start = _setup(reduced, "identity")
    state = _run(reduced, step, pipe, start)
    events = []
    listen = lambda event, secs, **kw: events.append(event)  # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        _run(reduced, step, pipe, state)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert not [e for e in events if e.startswith(
        ("/jax/core/compile/", "/jax/compilation_cache/"))], events


def test_donated_share_of_a_partly_donated_step():
    """``donated_share`` counts only the buffers the outputs alias."""
    p = {"w": jnp.ones((8, 4), jnp.bfloat16), "b": jnp.ones((4,))}
    f = jax.jit(lambda p, s: (p, s + 1), donate_argnums=(0,))
    compiled = f.lower(p, jnp.zeros((16,))).compile()
    don = trace_lib.donated_share(compiled, (p, jnp.zeros((16,))))
    assert don == {"aliased_bytes": 80, "state_bytes": 144,
                   "share": 80 / 144}
