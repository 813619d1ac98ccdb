import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh).

Proves the distribution config is coherent without hardware: 512
placeholder host devices form the production mesh; params/batches/caches
are ShapeDtypeStructs (no allocation); ``jit(...).lower().compile()``
must succeed, and its memory/cost analysis feeds EXPERIMENTS.md §Dry-run
and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k [--multi-pod] [--out out.json] [--print-hlo]
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config
from repro.core import DistributedOptimizer, ExchangeConfig, comm, exchange
from repro.launch import flops as flops_lib
from repro.launch import hlo as hlo_lib
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.launch import specs as specs_lib
from repro.models import build_model
from repro.models.activation_sharding import activation_sharding
from repro.optim import adamw, noam_schedule
from repro.training import make_train_step
from repro.tuning import cost as tuning_cost
from repro.tuning import profile as profile_lib
# note: repro.tuning re-exports the search() FUNCTION, which shadows
# the submodule attribute on the package — resolve the module itself
import importlib
search_lib = importlib.import_module("repro.tuning.search")


def lower_step(arch: str, shape_name: str, multi_pod: bool,
               mode: str = "gspmd", fsdp: bool = True, pure_dp: bool = False,
               zero1: bool = False,
               attn_impl: str = "xla_chunked",
               mesh_override=None,
               ssm_chunk: int = None,
               moe_decode: str = "dropless",
               loss_chunk: int = 512):
    """Build + lower the appropriate step.  Returns (lowered, meta, fn_args)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if ssm_chunk and cfg.ssm is not None:
        cfg = cfg.with_(ssm=_dc.replace(cfg.ssm, chunk=ssm_chunk))
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg)
    mesh = (mesh_override if mesh_override is not None
            else mesh_lib.make_production_mesh(multi_pod=multi_pod))

    p_structs = specs_lib.params_structs(cfg)
    # ZeRO-1 by default: weights sharded over `model` only (Megatron
    # col/row rules); optimizer state additionally over `data`.  Weights
    # get data-sharding (full FSDP) only when a model-only shard would
    # not fit HBM (>8 GB/device) — FSDP'd weights cost per-layer
    # activation-grad gathers in backward (EXPERIMENTS.md §Perf H2.6).
    import numpy as _np
    n_model_axis = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        "model", 1)
    param_bytes = sum(
        _np.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(p_structs))
    weights_fsdp = fsdp and (shape.kind == "train"
                             or param_bytes / n_model_axis > 8e9)
    if pure_dp:
        # paper-faithful Horovod layout: weights REPLICATED on every
        # worker, batch sharded across all chips, gradients all-reduced.
        p_shard = shard_lib.replicated(p_structs, mesh)
    else:
        p_shard = shard_lib.params_shardings(p_structs, mesh,
                                             fsdp=weights_fsdp)

    meta: Dict[str, Any] = dict(arch=arch, shape=shape_name,
                                mesh=list(mesh.devices.shape),
                                axes=list(mesh.axis_names), mode=mode)
    dp_axes = (tuple(mesh.axis_names) if pure_dp else
               tuple(a for a in mesh.axis_names if a != "model"))
    meta["pure_dp"] = pure_dp
    import contextlib
    act_ctx = lambda: activation_sharding(dp_axes)

    if shape.kind == "train":
        opt = DistributedOptimizer(
            adamw(noam_schedule(cfg.d_model)),
            exchange=ExchangeConfig(sparse_as_dense=True,
                                    algorithm="proposed_algorithm2"),
            axis_name=None)
        step = make_train_step(model, opt, sparse_embedding=False,
                               attn_impl=attn_impl, loss_chunk=loss_chunk,
                               remat=True)
        o_structs = jax.eval_shape(opt.init, p_structs)
        o_shard = (shard_lib.replicated(o_structs, mesh)
                   if (pure_dp and not zero1)
                   else shard_lib.params_shardings(
                       jax.tree_util.tree_map(lambda x: x, o_structs),
                       mesh, fsdp=fsdp))
        batch = specs_lib.input_specs(cfg, shape)
        b_shard = shard_lib.batch_shardings(batch, mesh,
                                            dp_axes=dp_axes)
        with mesh, act_ctx():
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None))
            lowered = jitted.lower(p_structs, o_structs, batch)
        return lowered, meta, (step, (p_structs, o_structs, batch))

    if shape.kind == "prefill":
        batch = specs_lib.input_specs(cfg, shape)
        b_shard = shard_lib.batch_shardings(batch, mesh)

        def prefill_step(params, batch):
            h, _ = model.forward(params, batch, attn_impl=attn_impl)
            return model.head(params, h[:, -1:])

        with mesh, act_ctx():
            jitted = jax.jit(prefill_step,
                             in_shardings=(p_shard, b_shard),
                             out_shardings=None)
            lowered = jitted.lower(p_structs, batch)
        return lowered, meta, (prefill_step, (p_structs, batch))

    # decode
    toks, cache, window, ring = specs_lib.decode_specs(cfg, shape)
    enc_spec = toks.pop("enc", None)
    c_shard = shard_lib.cache_shardings(cache, mesh, shape.global_batch)
    t_shard = shard_lib.batch_shardings(toks, mesh)
    meta.update(window=window, ring=ring)

    def serve_step(params, cache, toks, enc=None):
        return model.decode_step(params, cache, toks["tokens"], enc=enc,
                                 window=window, attn_impl=attn_impl,
                                 ring=ring, moe_mode=moe_decode)

    with mesh, act_ctx():
        if enc_spec is not None:
            e_shard = shard_lib.batch_shardings(enc_spec, mesh)
            jitted = jax.jit(serve_step,
                             in_shardings=(p_shard, c_shard, t_shard,
                                           e_shard),
                             out_shardings=(None, c_shard))
            lowered = jitted.lower(p_structs, cache, toks, enc_spec)
            fa = (serve_step, (p_structs, cache, toks, enc_spec))
        else:
            jitted = jax.jit(serve_step,
                             in_shardings=(p_shard, c_shard, t_shard),
                             out_shardings=(None, c_shard))
            lowered = jitted.lower(p_structs, cache, toks)
            fa = (serve_step, (p_structs, cache, toks))
    return lowered, meta, fa


def analyse(lowered, meta: Dict[str, Any], n_chips: int,
            fn_args=None) -> Dict[str, Any]:
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):      # older jax returns [dict]
        cost = cost[0] if cost else {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    hlo_stats = hlo_lib.analyze_collectives(hlo)
    hbm_bytes = hlo_stats.pop("__bytes__", 0.0) * 2.0   # read + write
    coll = hlo_stats
    coll_total = float(sum(coll.values()))

    # scan-aware GLOBAL flop count from the jaxpr (XLA's cost_analysis
    # counts while bodies once; see flops.py)
    jx = {"flops": 0.0, "bytes": 0.0}
    if fn_args is not None:
        fn, args = fn_args
        jx = flops_lib.count_fn_flops(fn, *args)
    flops_dev = jx["flops"] / n_chips

    # the roofline terms come from the shared library cost model (TPU
    # preset: the interconnect this lowering targets)
    from repro.tuning.cost import roofline_terms
    terms = roofline_terms(flops_dev, hbm_bytes, coll_total, "tpu")
    dominant = terms.pop("dominant")

    out = dict(meta)
    out.update(
        compile_s=compile_s,
        flops_global_jaxpr=jx["flops"],
        flops_per_device=flops_dev,
        hbm_bytes_per_device=hbm_bytes,
        xla_cost_flops_scan_once=float(cost.get("flops", 0.0)),
        xla_cost_bytes_scan_once=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=coll,
        collective_total_bytes=coll_total,
        **terms,
        dominant=dominant,
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            generated_code_bytes=getattr(mem, "generated_code_size_in_bytes",
                                         None),
        ),
        n_chips=n_chips,
    )
    return out


def _audit_grads(arch: str, reduced: bool, batch_per_worker: int,
                 seq_len: int):
    """Real gradient-contribution tree for the audit (shared by the
    shard_map and GSPMD audit paths).  Also returns the model, params
    and batch so the wait-free audit can lower the REAL in-backward
    exchange, not a standalone collective."""
    from repro.data import make_pipeline
    from repro.training.gradients import grad_contributions

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = make_pipeline(cfg, batch_per_host=batch_per_worker,
                         seq_len=seq_len)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    grads, _, _ = grad_contributions(model, params, batch,
                                     sparse_embedding=True)
    return cfg, grads, model, params, batch


def _require_devices(n_workers: int) -> None:
    if len(jax.devices()) < n_workers:
        # the module-top XLA_FLAGS override only helps if jax was not
        # initialised before this module was imported
        raise RuntimeError(
            f"exchange audit needs >= {n_workers} devices, found "
            f"{len(jax.devices())}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_workers} before "
            f"jax initialises")


def audit_exchange_plan(arch: str = "transformer-big", n_workers: int = 8,
                        reduced: bool = True,
                        sparse_as_dense: bool = True,
                        algorithm: str = "tf_algorithm1",
                        fusion_threshold: Optional[int] = None,
                        reduce_scatter: bool = False,
                        wire_dtype: Optional[str] = None,
                        codec: str = "identity",
                        backend: str = "jax",
                        overlap=False,
                        error_feedback: bool = False,
                        zero1: bool = False,
                        param_codec: str = "identity",
                        batch_per_worker: int = 2,
                        seq_len: int = 32,
                        profile: str = "ib",
                        trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Check the static ExchangePlan against lowered HLO.

    Lowers the plan-scheduled exchange under ``shard_map`` on
    ``n_workers`` devices and compares the plan's ``hlo_collectives`` /
    ``wire_bytes`` with the collective ops actually present in the
    compiled HLO (the same audit ``analyse`` applies to full steps).
    The expected op count comes from the plan itself: one gather bucket
    lowers to one all-gather per exchanged tensor (indices + values
    [+ codec scales], exactly like Horovod's IndexedSlices allgather);
    hierarchical buckets lower to one psum per mesh axis; the ring-sim
    backend lowers to its 2(P-1) collective-permute hops.  With
    ``backend="hierarchical"`` the mesh is folded to
    ``("pod", "data") = (2, n_workers//2)``.

    With ``overlap=True`` the STAGED path is lowered instead (every
    stage's collective launched before any unpack); the audit
    additionally checks that the schedule's per-stage collective counts
    sum to the fused plan's ``n_collectives`` — overlap must reorder,
    never add or drop, collectives.

    Non-linear codecs on ``backend="hierarchical"`` lower the per-hop
    requantizing reduction (one gather + decode-sum + re-encode per
    mesh axis, never a full-mesh gather); the per-hop wire is billed by
    ``plan.stage_hop_wire_bytes`` and must stay exact against the HLO.
    Stateful codecs (``error_feedback=True`` or a ``+ef`` codec name)
    lower with their ExchangeState threaded through the jitted exchange
    — residual feedback must add ZERO collectives and ZERO wire bytes.

    With ``zero1=True`` the FUSED ZeRO-1 step is lowered instead
    (grad reduce-scatter, flat-shard optimizer update on the sharded
    Zero1State, updated-param allgather): the plan's per-stage counts
    and wire must stay exact INCLUDING the param-allgather halves.
    """
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from repro.optim import adamw as adamw_opt

    cfg, grads, model, params, batch = _audit_grads(
        arch, reduced, batch_per_worker, seq_len)
    _require_devices(n_workers)
    if backend == "hierarchical":
        if n_workers % 2:
            raise ValueError("hierarchical audit needs even n_workers")
        workers = (2, n_workers // 2)
        axis_name = ("pod", "data")
        mesh = Mesh(np.array(jax.devices()[:n_workers]).reshape(workers),
                    axis_name)
    else:
        workers = n_workers
        axis_name = ("data",)
        mesh = Mesh(np.array(jax.devices()[:n_workers]), axis_name)

    opt = DistributedOptimizer(
        adamw_opt(noam_schedule(cfg.d_model)),
        exchange=ExchangeConfig(
            sparse_as_dense=sparse_as_dense, algorithm=algorithm,
            fusion_threshold=fusion_threshold,
            reduce_scatter=reduce_scatter, wire_dtype=wire_dtype,
            codec=codec, backend=backend, overlap=overlap,
            error_feedback=error_feedback, zero1=zero1,
            param_codec=param_codec),
        axis_name=axis_name)
    plan = opt.plan(grads)

    # opt.exchange honours overlap: fused serial order, or the staged
    # launch-all-then-unpack schedule.  Stateful codecs lower with the
    # ExchangeState threaded through (sharded over dim 0, one residual
    # slice per worker) — exactly the train step's calling convention.
    # overlap="backward" lowers the REAL wait-free gradient step — loss,
    # backward pass, and the custom_vjp-tapped in-backward collectives —
    # so the audited HLO is what training runs; the model compute adds
    # zero collectives under the replicated in_specs, so the plan's
    # counts and wire stay exact.
    if plan.config.zero1:
        # lower the fused zero1 step: collectives are the grad RS (or
        # quantised AG + decode-sum + slice) PLUS the updated-param
        # allgather — the optimizer math itself must add none
        from repro.optim import zero1 as zero1_lib

        z0 = opt.init_zero1_state(grads, params, n_workers=n_workers)
        zspec = zero1_lib.state_specs(plan, z0, axis_name)
        if plan.config.codec_obj.stateful:
            state0 = plan.init_state(n_workers=n_workers)

            def z_fn(g, p_, z, s):
                return opt.zero1_step(g, p_, z, exchange_state=s)

            ex = shard_map(z_fn, mesh=mesh,
                           in_specs=(P(), P(), zspec, P(axis_name)),
                           out_specs=(P(), zspec, P(axis_name)),
                           check_vma=False)
            lower_args = (grads, params, z0, state0)
        else:
            def z_fn(g, p_, z):
                new_p, new_z, _ = opt.zero1_step(g, p_, z)
                return new_p, new_z

            ex = shard_map(z_fn, mesh=mesh,
                           in_specs=(P(), P(), zspec),
                           out_specs=(P(), zspec), check_vma=False)
            lower_args = (grads, params, z0)
    elif plan.config.overlap_backward:
        from repro.training.gradients import wait_free_grad_exchange

        if plan.config.codec_obj.stateful:
            state0 = plan.init_state(n_workers=n_workers)

            def wf_fn(p_, b_, s):
                dense, ns, _, _ = wait_free_grad_exchange(
                    model, opt, p_, b_, state=s, sparse_embedding=True)
                return dense, ns

            ex = shard_map(wf_fn, mesh=mesh,
                           in_specs=(P(), P(), P(axis_name)),
                           out_specs=(P(), P(axis_name)), check_vma=False)
            lower_args = (params, batch, state0)
        else:
            def wf_fn(p_, b_):
                return wait_free_grad_exchange(
                    model, opt, p_, b_, sparse_embedding=True)[0]

            ex = shard_map(wf_fn, mesh=mesh, in_specs=(P(), P()),
                           out_specs=P(), check_vma=False)
            lower_args = (params, batch)
    elif plan.config.codec_obj.stateful:
        state0 = plan.init_state(n_workers=n_workers)

        def ex_fn(g, s):
            return opt.exchange(g, state=s)

        ex = shard_map(ex_fn, mesh=mesh,
                       in_specs=(P(), P(axis_name)),
                       out_specs=(P(), P(axis_name)), check_vma=False)
        lower_args = (grads, state0)
    else:
        ex = shard_map(opt.exchange, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
        lower_args = (grads,)
    hlo = jax.jit(ex).lower(*lower_args).compile().as_text()

    trace_info: Dict[str, Any] = {}
    if trace_dir:
        # runtime wire leg of the audit: one abstract evaluation bills
        # each collective call site's wire bytes to its stage, diffed
        # against the same plan accounting the static HLO check verifies
        from repro.telemetry import report as report_lib
        from repro.telemetry import trace as trace_lib

        meta = trace_lib.plan_trace_meta(
            plan, workers, profile=profile,
            measured=trace_lib.measure_wire(ex, *lower_args))
        meta.update(arch=arch, source="dryrun")
        rows = report_lib.stage_rows(meta)
        trace_info = dict(
            trace_path=trace_lib.write_meta(meta, trace_dir),
            runtime_wire_exact=report_lib.wire_exact(rows),
            trace_table=report_lib.render_table(rows))

    counts = hlo_lib.count_collectives(hlo)
    coll_bytes = {k: v for k, v in hlo_lib.analyze_collectives(hlo).items()
                  if k != "__bytes__"}

    # per-op ring wire bytes implied by the HLO result sizes, under the
    # configured backend's lowering (codec-aware: per-hop requantize
    # gathers bill a different all-gather factor than telescoping ones)
    p = n_workers
    levels = workers if isinstance(workers, tuple) else (workers,)
    hlo_wire = plan.config.backend_obj.hlo_wire_estimate(
        coll_bytes, levels, codec=plan.config.codec_obj,
        ag_factor=plan.hlo_allgather_factor(workers))

    expected_hlo_ops = plan.hlo_collectives(workers)
    hlo_ops = sum(counts.values())
    planned_wire = plan.wire_bytes(workers)
    note = None
    wire_dt = plan.config.codec_obj.wire_dtype("float32")
    if plan.config.codec_obj.linear and wire_dt != "float32" \
            and jax.default_backend() == "cpu":
        # the CPU backend upcasts narrow float collectives to f32 (see
        # hlo.analyze_collectives); the TPU wire stays at wire_dtype, so
        # the planned/HLO ratio is itemsize(wire)/4 here, 1.0 on TPU
        note = ("cpu backend computes %s collectives in f32; expect "
                "wire_ratio %.2f" % (wire_dt,
                                     comm.dtype_bytes(wire_dt) / 4))
    # the staged schedule must be a pure reordering of the fused plan:
    # per-stage collective counts sum to the fused config's
    # n_collectives (the ISSUE acceptance contract).  overlap="backward"
    # re-buckets (block-aligned so each collective has an in-backward
    # trigger), so its contract is launch coverage: the per-stage sums
    # must cover exactly its own plan's collectives, no dupes/misses.
    import dataclasses as _dc
    fused_plan = exchange.compile_plan(
        grads, _dc.replace(plan.config, overlap=False))
    stage_coll = [plan.stage_collectives(s) for s in plan.schedule.stages]
    stage_hlo = [plan.stage_hlo_collectives(s, workers)
                 for s in plan.schedule.stages]
    ref_n_collectives = (plan.n_collectives if plan.config.overlap_backward
                         else fused_plan.n_collectives)
    schedule_info = dict(
        n_stages=plan.schedule.n_stages,
        overlap=plan.config.overlap,
        stage_collectives=stage_coll,
        stage_hlo_ops=stage_hlo,
        stage_collectives_sum=sum(stage_coll),
        fused_n_collectives=fused_plan.n_collectives,
        stage_sum_matches_fused=(sum(stage_coll) == ref_n_collectives),
    )
    return dict(
        note=note,
        arch=arch, reduced=reduced, n_workers=p, audit_mode="shard_map",
        codec=plan.config.codec, backend=plan.config.backend,
        overlap=plan.config.overlap,
        stateful=plan.config.codec_obj.stateful,
        strategy=opt.exchange_stats(grads, workers).strategy,
        planned_n_collectives=plan.n_collectives,
        planned_hlo_ops=expected_hlo_ops,
        hlo_ops=hlo_ops,
        hlo_counts=counts,
        counts_match=(hlo_ops == expected_hlo_ops
                      and schedule_info["stage_sum_matches_fused"]),
        planned_wire_bytes=planned_wire,
        planned_hop_wire_bytes=list(plan.hop_wire_bytes(workers)),
        codec_state_bytes=plan.state_bytes(),
        hlo_wire_bytes=hlo_wire,
        wire_ratio=(planned_wire / hlo_wire if hlo_wire else None),
        # cost-model prediction from the SAME per-stage/per-hop
        # accounting the wire audit above just verified
        predicted_comm_us=tuning_cost.predict_comm_us(plan, workers,
                                                      profile),
        cost_profile=profile_lib.get_profile(profile).name,
        schedule=schedule_info,
        schedule_table=plan.describe_schedule(workers),
        plan_table=plan.describe(),
        **trace_info,
    )


def audit_exchange_gspmd(arch: str = "transformer-big", n_workers: int = 8,
                         reduced: bool = True,
                         fusion_threshold: Optional[int] = None,
                         codec: str = "identity",
                         backend: str = "jax",
                         batch_per_worker: int = 2,
                         seq_len: int = 32,
                         profile: str = "ib") -> Dict[str, Any]:
    """Planned vs COMPILER-CHOSEN collectives on the GSPMD path.

    The shard_map audit checks the collectives we schedule explicitly;
    the GSPMD training path instead jits a replicated-output reduction
    over data-sharded per-worker gradients and lets the XLA SPMD
    partitioner pick the collectives.  This audit lowers exactly that —
    per-worker contribution trees (leading worker axis sharded over
    ``data``), vmapped plan-classified accumulation, mean over workers,
    replicated output — and reports the partitioner's collective
    ops/bytes next to the plan's schedule, so divergence (op fusion,
    all-gather-based reductions, dtype promotion) is visible per arch.

    Dense-destined plans only: the gather path's data-dependent row
    counts cannot round-trip through GSPMD without ragged support, which
    is precisely why the explicit shard_map path exists.
    """
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.optim import adamw as adamw_opt

    cfg, grads, _, _, _ = _audit_grads(arch, reduced, batch_per_worker,
                                       seq_len)
    _require_devices(n_workers)

    opt = DistributedOptimizer(
        adamw_opt(noam_schedule(cfg.d_model)),
        exchange=ExchangeConfig(
            sparse_as_dense=True, fusion_threshold=fusion_threshold,
            codec=codec, backend=backend),
        axis_name=None)
    plan = opt.plan(grads)
    if plan.gather_leaf_ids:
        raise ValueError("GSPMD audit supports dense-destined plans only "
                         "(use the shard_map audit for gather plans)")

    # stack every contribution n_workers times along a leading axis —
    # the per-worker gradient copies the data-parallel backward would
    # produce (values are irrelevant to the collective audit)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape),
        grads)

    def gspmd_exchange(g):
        acc = jax.vmap(plan.accumulate_tree)(g)
        return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), acc)

    mesh = Mesh(np.array(jax.devices()[:n_workers]), ("data",))
    # prefix shardings: every input leaf worker-sharded on its leading
    # axis, every output leaf fully replicated — replication is what
    # forces the partitioner to materialise cross-worker collectives
    hlo = jax.jit(gspmd_exchange,
                  in_shardings=(NamedSharding(mesh, P("data")),),
                  out_shardings=NamedSharding(mesh, P())
                  ).lower(stacked).compile().as_text()
    counts = hlo_lib.count_collectives(hlo)
    coll_bytes = {k: v for k, v in hlo_lib.analyze_collectives(hlo).items()
                  if k != "__bytes__"}
    p = n_workers
    hlo_wire = plan.config.backend_obj.hlo_wire_estimate(
        coll_bytes, (p,), codec=plan.config.codec_obj,
        ag_factor=plan.hlo_allgather_factor(p))
    planned_wire = plan.wire_bytes(p)
    hlo_ops = sum(counts.values())
    return dict(
        arch=arch, reduced=reduced, n_workers=p, audit_mode="gspmd",
        codec=plan.config.codec, backend=plan.config.backend,
        strategy=opt.exchange_stats(grads, p).strategy,
        planned_n_collectives=plan.n_collectives,
        planned_hlo_ops=plan.hlo_collectives(p),
        hlo_ops=hlo_ops,
        hlo_counts=counts,
        # counts_match keeps its shard_map meaning (exact op-count
        # agreement); GSPMD may legally fuse/split differently, so the
        # CLI success criterion is collectives_found and the delta is
        # reported for comparison
        counts_match=hlo_ops == plan.hlo_collectives(p),
        collectives_found=hlo_ops > 0,
        collective_delta=hlo_ops - plan.hlo_collectives(p),
        planned_wire_bytes=planned_wire,
        hlo_wire_bytes=hlo_wire,
        wire_ratio=(planned_wire / hlo_wire if hlo_wire else None),
        predicted_comm_us=tuning_cost.predict_comm_us(plan, p, profile),
        cost_profile=profile_lib.get_profile(profile).name,
        plan_table=plan.describe(),
    )


def run_tune(arch: str = "transformer-big", n_workers: int = 8,
             reduced: bool = True, profile: str = "ethernet",
             trials: int = 0, top_k: int = 5,
             cache_dir: str = search_lib.DEFAULT_CACHE_DIR,
             batch_per_worker: int = 2,
             seq_len: int = 32) -> Dict[str, Any]:
    """Search the ExchangeConfig space for this (model, P, profile) and
    cache the winner.  ``trials=0`` is purely analytic (no devices
    beyond plan compilation); ``trials>0`` times the analytic top-k
    end-to-end on the live (emulated) workers before picking."""
    _, grads, model, params, batch = _audit_grads(
        arch, reduced, batch_per_worker, seq_len)
    if trials > 0:
        _require_devices(n_workers)
    res = search_lib.search(grads, n_workers, profile=profile,
                            trials=trials, top_k=top_k,
                            model=model, params=params, batch=batch)
    path = search_lib.save_artifact(res, cache_dir)
    return dict(
        arch=arch, reduced=reduced, n_workers=n_workers,
        profile=res.profile, trials=trials,
        key=res.key, tree_fingerprint=res.tree_fingerprint,
        artifact=path,
        winner=res.winner.label,
        winner_config=search_lib.config_to_dict(res.winner.config),
        n_candidates=len(res.candidates),
        table=res.table(),
        ranking=[
            {"label": c.label, "predicted_us": c.predicted_us,
             "measured_us": c.measured_us, "error": c.error}
            for c in res.candidates],
    )


def model_flops(arch: str, shape_name: str) -> Dict[str, float]:
    """6*N*D (dense) / 6*N_active*D (MoE) reference FLOPs."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    n_params, n_active = param_counts(cfg)
    d_tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                     else 1)
    if shape.kind == "prefill":
        d_tokens = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.kind == "train" else 2.0
    return {"n_params": n_params, "n_active": n_active,
            "model_flops": mult * n_active * d_tokens}


def param_counts(cfg) -> tuple:
    """(total params, activated params) from the config arithmetic."""
    d, v = cfg.d_model, cfg.vocab
    emb = v * d * (1 if cfg.tied_embeddings else 2)
    hd = cfg.resolved_head_dim
    per_layer_attn = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd \
        + cfg.n_heads * hd * d
    if cfg.mla is not None:
        m = cfg.mla
        per_layer_attn = (d * cfg.n_heads * (m.nope_dim + m.rope_dim)
                          + d * m.kv_lora + d * m.rope_dim
                          + m.kv_lora * cfg.n_heads * (m.nope_dim + m.v_dim)
                          + cfg.n_heads * m.v_dim * d)
    if cfg.family == "ssm":
        x = cfg.xlstm
        di = x.mlstm_expand * d
        per_layer = (d * 2 * di + 3 * di * di + di * d      # mlstm
                     + 4 * d * d + int(d * x.slstm_ff_mult) * 2 * d)
        total = emb + cfg.n_layers * per_layer
        return float(total), float(total)
    if cfg.family == "hybrid":
        s = cfg.ssm
        di = s.expand * d
        h = di // s.head_dim
        per_mamba = d * (2 * di + 2 * s.state_dim + h) + di * d
        shared = per_layer_attn + 3 * d * cfg.d_ff
        total = emb + cfg.n_layers * per_mamba + shared
        return float(total), float(total)
    if cfg.moe is not None:
        mo = cfg.moe
        expert = 3 * d * mo.d_ff_expert
        shared = mo.n_shared * expert
        per_layer_total = per_layer_attn + mo.n_experts * expert + shared \
            + d * mo.n_experts
        per_layer_active = per_layer_attn + mo.top_k * expert + shared \
            + d * mo.n_experts
        return (float(emb + cfg.n_layers * per_layer_total),
                float(emb + cfg.n_layers * per_layer_active))
    per_layer = per_layer_attn + 3 * d * cfg.d_ff
    if cfg.frontend is not None and cfg.frontend.cross_attention:
        per_layer += 4 * d * cfg.n_heads * hd
    total = emb + cfg.n_layers * per_layer
    return float(total), float(total)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--audit-exchange", action="store_true",
                    help="audit the static ExchangePlan against lowered "
                         "HLO collectives instead of running a dry-run")
    ap.add_argument("--audit-workers", type=int, default=8)
    ap.add_argument("--audit-mode", default="shard_map",
                    choices=["shard_map", "gspmd"],
                    help="shard_map: explicitly-scheduled collectives "
                         "must match the plan exactly; gspmd: lower the "
                         "non-shard_map training path and report the "
                         "compiler-chosen collectives next to the plan")
    from repro.core import available_backends, available_codecs
    ap.add_argument("--codec", default="identity",
                    help="WireCodec registry name (registered: "
                         f"{', '.join(available_codecs())}; append "
                         "'+ef' for error feedback)")
    ap.add_argument("--backend", default="jax",
                    help="CollectiveBackend registry name (registered: "
                         f"{', '.join(available_backends())})")
    ap.add_argument("--error-feedback", action="store_true",
                    help="with --audit-exchange (shard_map mode): lower "
                         "the stateful error-feedback path (ExchangeState "
                         "threaded through the jitted exchange) and "
                         "verify it adds zero collectives / wire bytes")
    ap.add_argument("--overlap", nargs="?", const="staged", default=None,
                    choices=["staged", "backward"],
                    help="with --audit-exchange (shard_map mode): lower "
                         "the staged BucketSchedule path ('staged', the "
                         "bare-flag default) or the wait-free in-backward "
                         "path ('backward' — lowers the full gradient "
                         "step with its custom_vjp-launched collectives) "
                         "and verify the per-stage collective counts sum "
                         "to the fused plan's n_collectives")
    ap.add_argument("--full-size", action="store_true",
                    help="with --audit-exchange: use the full (not "
                         "reduced) config")
    ap.add_argument("--tune", action="store_true",
                    help="search the ExchangeConfig space for this "
                         "model / --audit-workers / --profile, print "
                         "the ranked table and cache the winner under "
                         "--tune-cache (consumed by train.py --tuned)")
    ap.add_argument("--trials", type=int, default=0,
                    help="with --tune: measured refinement trials for "
                         "the analytic top-k (0 = analytic only)")
    ap.add_argument("--top-k", type=int, default=5,
                    help="with --tune --trials N: how many analytic "
                         "leaders to measure")
    from repro.tuning import available_profiles
    ap.add_argument("--profile", default="ethernet",
                    help="BandwidthProfile preset name or JSON path "
                         f"(presets: {', '.join(available_profiles())})")
    ap.add_argument("--tune-cache", default=search_lib.DEFAULT_CACHE_DIR,
                    help="tuning artifact directory")
    ap.add_argument("--grad-accum", default="dense_reduce",
                    choices=["sparse_gather", "dense_reduce"])
    ap.add_argument("--fusion-threshold", type=int, default=None)
    ap.add_argument("--reduce-scatter", action="store_true")
    ap.add_argument("--wire-dtype", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="gspmd", choices=["gspmd"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--zero1", action="store_true",
                    help="with --pure-dp: shard optimizer state (ZeRO-1). "
                         "With --audit-exchange (shard_map mode): lower "
                         "the fused ZeRO-1 step — grad reduce-scatter, "
                         "flat-shard optimizer update, updated-param "
                         "allgather — and verify the plan's counts and "
                         "wire stay exact including the param-AG stages")
    ap.add_argument("--param-codec", default="identity",
                    help="with --audit-exchange --zero1: WireCodec for "
                         "the updated-param allgather")
    ap.add_argument("--pure-dp", action="store_true",
                    help="paper-faithful Horovod layout: replicated "
                         "weights, batch over all axes, grads allreduced")
    ap.add_argument("--attn-impl", default="xla_chunked")
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--moe-decode", default="dropless",
                    choices=["dropless", "capacity"])
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="with --audit-exchange (shard_map mode): also "
                         "bill the wire bytes of one abstract evaluation "
                         "of the exchange per stage, write them with the "
                         "plan's accounting to DIR/exchange.json, and "
                         "report runtime-measured wire vs the plan")
    ap.add_argument("--out", default=None)
    ap.add_argument("--print-hlo", action="store_true")
    args = ap.parse_args(argv)

    if args.tune:
        result = run_tune(
            arch=args.arch, n_workers=args.audit_workers,
            reduced=not args.full_size, profile=args.profile,
            trials=args.trials, top_k=args.top_k,
            cache_dir=args.tune_cache)
        print(result["table"])
        print(f"\nwinner: {result['winner']}")
        print(f"artifact: {result['artifact']}")
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=2, default=str)
        return 0

    if args.audit_exchange:
        if args.audit_mode == "gspmd":
            result = audit_exchange_gspmd(
                arch=args.arch, n_workers=args.audit_workers,
                reduced=not args.full_size,
                fusion_threshold=args.fusion_threshold,
                codec=args.codec, backend=args.backend,
                profile=args.profile)
        else:
            result = audit_exchange_plan(
                arch=args.arch, n_workers=args.audit_workers,
                reduced=not args.full_size,
                sparse_as_dense=args.grad_accum == "dense_reduce",
                fusion_threshold=args.fusion_threshold,
                reduce_scatter=args.reduce_scatter,
                wire_dtype=args.wire_dtype,
                codec=args.codec, backend=args.backend,
                overlap=args.overlap or False,
                error_feedback=args.error_feedback,
                zero1=args.zero1,
                param_codec=args.param_codec,
                profile=args.profile,
                trace_dir=args.trace)
        table = result.pop("trace_table", None)
        print(json.dumps(result, indent=2, default=str))
        if table:
            print("\nplanned vs measured wire (runtime counters):")
            print(table)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=2, default=str)
        # gspmd mode is a comparison (the partitioner may legally fuse);
        # shard_map mode demands exact agreement
        ok = (result["collectives_found"] if args.audit_mode == "gspmd"
              else result["counts_match"])
        return 0 if ok else 1

    if args.shape is None:
        ap.error("--shape is required unless --audit-exchange is given")
    n_chips = 512 if args.multi_pod else 256
    lowered, meta, fn_args = lower_step(
        args.arch, args.shape, args.multi_pod, mode=args.mode,
        fsdp=not args.no_fsdp, pure_dp=args.pure_dp, zero1=args.zero1,
        attn_impl=args.attn_impl,
        ssm_chunk=args.ssm_chunk, moe_decode=args.moe_decode,
        loss_chunk=args.loss_chunk)
    meta.update(fsdp=not args.no_fsdp, ssm_chunk=args.ssm_chunk,
                moe_decode=args.moe_decode, loss_chunk=args.loss_chunk)
    if args.print_hlo:
        print(lowered.as_text()[:20000])
    result = analyse(lowered, meta, n_chips, fn_args=fn_args)
    result.update(model_flops(args.arch, args.shape))
    total_f = result["flops_global_jaxpr"]
    result["useful_flops_ratio"] = (result["model_flops"] / total_f
                                    if total_f else None)
    print(json.dumps(result, indent=2, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
