"""Paper Fig. 5 (time): measured wall-time of the accumulate+exchange
step, gather vs densify+reduce, on 8 emulated workers (a child process
pinned to 8 CPU devices — the same `mpirun -np 8` emulation the paper's
cluster would give on one node; its rows say ``cpuP8``), plus Pallas
densify kernel timings on the parent's own backend.

The paper reports 4320 ms -> 169 ms (25x) at 64 workers on Omni-Path.
CPU shared-memory "interconnect" compresses the gap; what must reproduce
is the direction and the growth trend with worker count and with the
vocab/token ratio.
"""
from __future__ import annotations

import functools
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import run_cpu_workers, time_fn
from repro.kernels import ops as kops

_DIST_CODE = textwrap.dedent("""
    import functools, time
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core import (ExchangeConfig, IndexedSlices,
                            DistributedOptimizer)
    from repro.optim import adamw

    V, D, N = 33708, 1024, 5000          # the paper's exact tensor shapes
    P_ = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ('data',))
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, V, (P_, N), dtype=np.int32))
    vals = jnp.asarray(rng.standard_normal((P_, N, D)), dtype=jnp.float32)
    dense = jnp.asarray(rng.standard_normal((P_, V, D)), dtype=jnp.float32)

    # each strategy is the SAME planned exchange, different schedule:
    # gather   -> Alg.1 gather bucket (allgather, the pathology)
    # reduce   -> sparse_as_dense dense bucket (allreduce, the fix)
    # rs_bf16  -> beyond-paper: reduce-scatter + allgather on a bf16 wire
    # int8     -> beyond-paper: quantised int8 wire + per-bucket scales
    STRATEGIES = {
        'gather': ExchangeConfig(sparse_as_dense=False),
        'reduce': ExchangeConfig(sparse_as_dense=True),
        'rs_bf16': ExchangeConfig(sparse_as_dense=True,
                                  reduce_scatter=True, codec='bf16'),
        'int8': ExchangeConfig(sparse_as_dense=True, codec='int8'),
    }

    def step(i, v, d, opt):
        g = {'emb': [IndexedSlices(i[0], v[0], (V, D)), d[0]]}
        return opt.exchange(g)['emb'][None]

    out, wire = {}, {}
    for name, cfg in STRATEGIES.items():
        opt = DistributedOptimizer(adamw(1e-3), exchange=cfg,
                                   axis_name=('data',))
        g0 = {'emb': [IndexedSlices(idx[0], vals[0], (V, D)), dense[0]]}
        wire[name] = opt.exchange_stats(g0, n_workers=P_).wire_bytes
        sm = jax.jit(shard_map(functools.partial(step, opt=opt),
                               mesh=mesh,
                               in_specs=(P('data'), P('data'), P('data')),
                               out_specs=P('data'), check_vma=False))
        r = sm(idx, vals, dense); jax.block_until_ready(r)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(sm(idx, vals, dense))
            ts.append(time.perf_counter() - t0)
        out[name] = sorted(ts)[1]
    print('GATHER_US', out['gather'] * 1e6)
    print('REDUCE_US', out['reduce'] * 1e6)
    print('RSBF16_US', out['rs_bf16'] * 1e6)
    print('INT8_US', out['int8'] * 1e6)
    print('WIRE_GATHER', wire['gather'])
    print('WIRE_REDUCE', wire['reduce'])
    print('WIRE_RSBF16', wire['rs_bf16'])
    print('WIRE_INT8', wire['int8'])

    # overlap column: the SAME dense-reduce exchange on a multi-bucket
    # tree (the embedding + 8 projection chunks), fused serial schedule
    # vs the staged BucketSchedule (launch-all-then-unpack)
    n_chunk = 8
    ws = jnp.asarray(rng.standard_normal((P_, n_chunk, 512, 256)),
                     jnp.float32)

    def step_multi(i, v, d, w, opt):
        g = {'emb': [IndexedSlices(i[0], v[0], (V, D)), d[0]]}
        for k in range(n_chunk):
            g['w%d' % k] = w[0, k]
        return opt.exchange(g)['emb'][None]

    for name, ov in (('fused_multi', False), ('overlap_multi', True)):
        opt = DistributedOptimizer(
            adamw(1e-3),
            exchange=ExchangeConfig(sparse_as_dense=True, overlap=ov),
            axis_name=('data',))
        sm = jax.jit(shard_map(functools.partial(step_multi, opt=opt),
                               mesh=mesh, in_specs=(P('data'),) * 4,
                               out_specs=P('data'), check_vma=False))
        r = sm(idx, vals, dense, ws); jax.block_until_ready(r)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(sm(idx, vals, dense, ws))
            ts.append(time.perf_counter() - t0)
        out[name] = sorted(ts)[1]
    print('FUSEDMULTI_US', out['fused_multi'] * 1e6)
    print('OVERLAPMULTI_US', out['overlap_multi'] * 1e6)
""")


def run(emit):
    out = run_cpu_workers(_DIST_CODE)

    def grab(tag):
        return float(out.split(tag)[1].split()[0])

    g, r, rs = grab("GATHER_US"), grab("REDUCE_US"), grab("RSBF16_US")
    q8 = grab("INT8_US")
    emit("fig5_time_gather_cpuP8_paper_shapes", g, "allgather+apply")
    emit("fig5_time_reduce_cpuP8_paper_shapes", r, "densify+allreduce")
    emit("fig5_time_rs_bf16_cpuP8", rs, "reduce_scatter+allgather_bf16wire")
    emit("fig5_time_int8_cpuP8", q8, "quantized_int8_wire+scales")
    emit("fig5_time_ratio_cpuP8", 0.0,
         f"{g/r:.1f}x_paper_25x_at_P64_on_OmniPath")
    emit("fig5_planned_wire_cpuP8", 0.0,
         f"gather{grab('WIRE_GATHER')/1e6:.0f}MB_"
         f"reduce{grab('WIRE_REDUCE')/1e6:.0f}MB_"
         f"rs_bf16{grab('WIRE_RSBF16')/1e6:.0f}MB_"
         f"int8{grab('WIRE_INT8')/1e6:.0f}MB")
    fm, om = grab("FUSEDMULTI_US"), grab("OVERLAPMULTI_US")
    emit("fig5_time_fused_multibucket_cpuP8", fm,
         "serial_schedule_9buckets")
    emit("fig5_time_overlap_multibucket_cpuP8", om,
         "staged_schedule_9buckets")
    emit("fig5_time_overlap_ratio_cpuP8", 0.0,
         f"{fm/max(om, 1e-9):.2f}x_fused_over_staged")

    # densify kernel: Pallas vs XLA scatter oracle, on this process's
    # backend (interpreted on the CPU, native on the TPU)
    rng = np.random.default_rng(0)
    n, v, d = 2048, 4096, 256
    i = jnp.asarray(rng.integers(0, v, n, dtype=np.int32))
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=jnp.float32)
    t_xla = time_fn(functools.partial(kops.densify, impl="xla"),
                    i, x, (v, d))
    t_pal = time_fn(functools.partial(kops.densify, impl="pallas"),
                    i, x, (v, d))
    emit("densify_xla_scatter", t_xla, f"n{n}_v{v}_d{d}")
    mode = "interpret" if kops.pallas_interpret() else "native"
    emit(f"densify_pallas_{mode}", t_pal, f"{jax.default_backend()}_{mode}")
