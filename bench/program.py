"""The system under test, built as ``repro.launch.train.train`` builds it.

The launcher has no seam that returns the built step without training,
so this module calls its public helpers in the launcher's order for
``--dist horovod``: ``build_optimizer`` -> ``make_train_step(
sparse_embedding=True)`` -> ``shard_map`` over the ``("data",)`` mesh,
the state placed with ``place_on_mesh``, and the program's ``Trainer``
with the batch sharded over the mesh.  Nothing else of the program is
used: the weights and the batches come from the benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch import train as launcher
from repro.models import build_model
from repro.training import Trainer, TrainerConfig, make_train_step

# launcher defaults (``repro.launch.train`` argparse) that a traffic
# file's "launcher" block may override
LAUNCHER_DEFAULTS = dict(
    dist="horovod", grad_accum="dense_reduce", algorithm="tf_algorithm1",
    fusion_threshold=None, reduce_scatter=False, wire_dtype=None,
    codec="identity", backend="jax", error_feedback=False, overlap=None,
    zero1=False, param_codec="identity", warmup=400)

# config-file keys that name the file's own configuration, not a field
# of the program's ArchConfig that happens to share the name
_FILE_KEYS = ("name", "source")


def arch_config(cfg: Dict):
    """The program's ArchConfig for a config file: the registered arch
    (``cfg["arch"]``) with every key of the file that names one of its
    fields set on it (a nested block such as ``moe``, ``mla`` or
    ``frontend``, given as an object, replaces those keys of the
    registered block), and ``frontend_frames``, where the file has it,
    as the cross-attention frontend's frames; then checked field by
    field against the file."""
    base = get_config(cfg["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {}
    for k, v in cfg.items():
        if k not in fields or k in _FILE_KEYS:
            continue
        if isinstance(v, dict):
            block = getattr(base, k)
            if block is None:
                raise ValueError(f"{cfg['arch']} has no {k!r} block to set")
            try:
                v = dataclasses.replace(block, **v)
            except TypeError as e:
                raise ValueError(f"{k}: {e}") from None
        over[k] = v
    if "frontend_frames" in cfg:
        frontend = over.get("frontend", base.frontend)
        if frontend is None:
            raise ValueError(f"{cfg['arch']} has no frontend for "
                             f"frontend_frames")
        over["frontend"] = dataclasses.replace(
            frontend, n_embeds=cfg["frontend_frames"])
    arch = base.with_(**over)

    def as_file(x, like):
        if isinstance(like, dict):
            return {k: getattr(x, k, None) for k in like}
        return x

    want = {k: cfg[k] for k in over if k in cfg}
    got = {k: as_file(getattr(arch, k), want[k]) for k in want}
    if "frontend_frames" in cfg:
        want["frontend_frames"] = cfg["frontend_frames"]
        got["frontend_frames"] = arch.frontend.n_embeds
        want["cross_attention"], got["cross_attention"] = \
            True, arch.frontend.cross_attention
    if got != want:
        raise ValueError(f"program config {got} differs from {want}")
    return arch


@dataclasses.dataclass
class Program:
    arch: object
    model: object
    opt: object
    mesh: Mesh
    axes: tuple
    trainer: Trainer
    jitted: Dict = dataclasses.field(default_factory=dict)


def build(cfg: Dict, traffic: Dict, devices, feed) -> Program:
    args = argparse.Namespace(**{**LAUNCHER_DEFAULTS,
                                 **traffic.get("launcher", {})})
    if args.dist != "horovod":
        raise ValueError("the benchmark drives --dist horovod only")
    arch = arch_config(cfg)
    model = build_model(arch)
    opt = launcher.build_optimizer(args, arch)
    step = make_train_step(model, opt, sparse_embedding=True)
    if step.stateful_exchange or opt.zero1:
        raise NotImplementedError("stateful codecs and zero1 carry extra "
                                  "train state the harness does not build")
    axes = launcher.dist_axes(args, backend=opt.exchange_config.backend)
    if len(axes) != 1:
        raise NotImplementedError(f"mesh axes {axes}: flat meshes only")
    mesh = Mesh(np.array(devices).reshape(len(devices)), axes)
    step = shard_map(step, mesh=mesh, in_specs=(P(), P(), P(axes)),
                     out_specs=(P(), P(), P()), check_vma=False)
    trainer = Trainer(model, step, feed, TrainerConfig(total_steps=1),
                      batch_sharding=NamedSharding(mesh, P(axes)))
    return Program(arch, model, opt, mesh, axes, trainer)


def check_layout(prog: Program, params) -> None:
    """The benchmark's weights must match the program's own layout."""
    want = jax.eval_shape(prog.model.init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree_util.tree_leaves(want),
                jax.tree_util.tree_leaves(got))):
        raise ValueError("the program's parameter layout differs from "
                         "the reference's")


def init_state(prog: Program, params):
    """Place params and the optimizer state on the mesh, as the launcher."""
    params = launcher.place_on_mesh(params, prog.mesh, P())
    opt_state = launcher.place_on_mesh(prog.opt.init(params), prog.mesh,
                                       P())
    return params, opt_state


def run(prog: Program, params, opt_state, steps: int, log_every: int,
        log) -> Dict:
    """One call of the program's ``Trainer.run`` over ``steps`` steps."""
    prog.trainer.config = TrainerConfig(total_steps=steps,
                                        log_every=log_every)
    return prog.trainer.run(params, opt_state, log=log)


def first_moment(opt_state):
    """AdamW's first moment: after one step it is (1 - b1) x gradient."""
    return opt_state.mu


def per_device_norms(prog: Program, a, b=None) -> np.ndarray:
    """(devices, leaves) L2 norms of each leaf (or of ``a - b``) as each
    device holds it: a replicated array that a device got wrong shows."""
    key = ("norms", b is None)
    if key not in prog.jitted:
        def norms(x, y):
            xs = jax.tree_util.tree_leaves(x)
            ys = (jax.tree_util.tree_leaves(y) if y is not None
                  else [None] * len(xs))
            return jnp.stack([jnp.linalg.norm((u.astype(jnp.float32) - (
                0 if v is None else v.astype(jnp.float32))).reshape(-1))
                for u, v in zip(xs, ys)])[None]

        prog.jitted[key] = jax.jit(shard_map(
            norms, mesh=prog.mesh, in_specs=(P(), P()),
            out_specs=P(prog.axes), check_vma=False))
    return np.asarray(prog.jitted[key](a, b))


def step_hlo(prog: Program, params, opt_state, batch) -> str:
    """The compiled step's HLO text (from the compile cache), whose
    metadata names each op's scope."""
    placed = {k: jax.device_put(v, prog.trainer.batch_sharding)
              for k, v in batch.items()}
    return jax.jit(prog.trainer.step_fn).lower(
        params, opt_state, placed).compile().as_text()
