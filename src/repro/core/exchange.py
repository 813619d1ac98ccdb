"""ExchangePlan — one static collective scheduler for accumulation,
fusion, and cross-worker gradient exchange.

The paper's result is that the accumulation REPRESENTATION (dense reduce
vs. sparse gather) and the collective layout (Horovod's 128 MiB fusion
buffers) decide whether training scales.  Previously that choice was
re-derived eagerly, per leaf, in three places (``DistributedOptimizer.
exchange``, ``exchange_stats``, and each benchmark's hand-rolled byte
accounting).  Following Mesh-TensorFlow's lesson that communication
layout should be an explicit statically-compiled plan, this module
compiles the whole decision ONCE per gradient-tree structure:

  1. **classify** every leaf's contribution list through the configured
     accumulation algorithm (paper Alg. 1 / Alg. 2 / the sparse_as_dense
     Listing-1 pre-pass) to its post-accumulation representation;
  2. **bucket** dense leaves into Horovod-style fusion buffers
     (first-fit-decreasing) and sparse IndexedSlices leaves into their
     own gather buckets;
  3. **select a collective** per bucket — fused allreduce,
     reduce-scatter + allgather (ZeRO-style decomposition), or allgather
     (the pathological sparse path);
  4. run the wire through a registered **WireCodec**
     (``repro.core.codecs``): identity, bf16/f16 casts (Ott et al.
     2018), or int8 + per-bucket absmax scales — with densification (XLA
     scatter-add or the Pallas kernel) FUSED into packing so
     deferred-sparse leaves never materialise a dense f32 tensor before
     the narrowing;
  5. lower every bucket collective through a registered
     **CollectiveBackend** (``repro.core.backend``): flat jax
     collectives, the hierarchical per-mesh-axis psum, or the
     ppermute-based ring simulation;
  6. compile a **BucketSchedule**: one stage per bucket (``pack ->
     collective -> unpack``) carrying its readiness key (the leaf set
     it consumes), sorted reverse-layer so the bucket whose gradients
     finalise earliest in backward launches first.  ``execute`` runs
     the stages serially (fused); ``execute_scheduled`` /
     ``ExchangeConfig(overlap=True)`` launches every stage's collective
     before any unpack, interleaved with the remaining
     accumulation/pack compute, so collectives hide behind compute.

The plan is cached on (treedef, contribution shapes/dtypes, config) and
is the single source of truth for ``wire_bytes`` / ``buffer_bytes`` /
``n_collectives`` (sums of the schedule's per-stage accounting)
consumed by the optimizer, the launchers' collective audit, the
benchmarks, and the roofline/scaling models.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import accumulation, backend as backend_lib, codecs, comm, \
    fusion
from repro.core.backend import ALLGATHER, ALLREDUCE, REDUCE_SCATTER
from repro.core.codecs import ExchangeState, canonical_dtype
from repro.core.indexed_slices import IndexedSlices, concat_slices
from repro.telemetry import hooks as _telemetry

# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """Everything the planner needs to know, all static.

    The single public entry point for exchange behaviour:

        DistributedOptimizer(opt, exchange=ExchangeConfig(
            codec="int8", backend="hierarchical", reduce_scatter=False))

    ``codec`` / ``backend`` name entries in the ``repro.core.codecs`` /
    ``repro.core.backend`` registries.  The legacy ``wire_dtype`` and
    ``hierarchical`` fields are accepted as deprecated spellings and
    normalised onto ``codec`` / ``backend`` in ``__post_init__`` (so
    old- and new-style configs compare, hash, and cache identically).
    """
    algorithm: str = "tf_algorithm1"     # paper Alg. 1 (TF upstream)
    sparse_as_dense: bool = False        # Horovod Listing-1 pre-pass
    fusion_threshold: Optional[int] = None   # bytes; None = bucket/leaf
    reduce_scatter: bool = False         # RS+AG instead of allreduce
    codec: str = "identity"              # WireCodec registry name
    backend: str = "jax"                 # CollectiveBackend registry name
    hierarchy_levels: int = 2            # mesh axes a hierarchical plan spans
    use_kernel: bool = False             # Pallas densify/quantize kernels
    overlap: Union[bool, str] = False    # False | "staged" | "backward".
    #                                      "staged" (legacy True): launch
    #                                      every bucket collective before
    #                                      any unpack, interleaved with
    #                                      the remaining accumulation
    #                                      compute.  "backward" (wait-free
    #                                      backprop): buckets are snapped
    #                                      to model-block boundaries and
    #                                      each block's collectives launch
    #                                      from INSIDE the backward pass
    #                                      via per-block custom_vjp hooks
    #                                      (training.gradients.
    #                                      wait_free_grad_exchange)
    error_feedback: bool = False         # wrap codec in ErrorFeedbackCodec
    #                                      (normalised onto codec="<x>+ef")
    zero1: bool = False                  # ZeRO-1: reduce-scatter grads,
    #                                      run the optimizer update on the
    #                                      1/P flat shard, allgather the
    #                                      UPDATED PARAMS back through the
    #                                      same BucketSchedule.  The first
    #                                      strategy where the exchange and
    #                                      the optimizer update are one
    #                                      fused schedule (see docs/zero.md)
    param_codec: str = "identity"        # WireCodec for the zero1 param
    #                                      allgather wire (stateless only;
    #                                      "identity" keeps zero1 bitwise-
    #                                      identical to the replicated path)
    # -- deprecated spellings, folded into codec/backend ---------------------
    wire_dtype: Optional[str] = None     # -> codec=<cast codec>
    hierarchical: bool = False           # -> backend="hierarchical"

    def __post_init__(self):
        if self.algorithm not in ("tf_algorithm1", "proposed_algorithm2"):
            raise ValueError(
                f"unknown accumulation algorithm: {self.algorithm}")
        # normalise overlap onto False | "staged" | "backward" so legacy
        # bool configs compare, hash, and cache identically to the
        # string spellings (and every `if cfg.overlap:` keeps working)
        ov = self.overlap
        if ov in (False, None, "none", "off"):
            ov = False
        elif ov in (True, "staged", "on"):
            ov = "staged"
        elif ov != "backward":
            raise ValueError(f"unknown overlap mode: {self.overlap!r} "
                             f"(expected False, 'staged' or 'backward')")
        object.__setattr__(self, "overlap", ov)
        if self.wire_dtype is not None:
            mapped = codecs.codec_name_for_wire_dtype(self.wire_dtype)
            if self.codec not in ("identity", mapped):
                raise ValueError(
                    f"conflicting wire_dtype={self.wire_dtype!r} and "
                    f"codec={self.codec!r}")
            object.__setattr__(self, "codec", mapped)
            object.__setattr__(self, "wire_dtype", None)
        if self.error_feedback:
            name = codecs.get_codec(self.codec).name
            if not name.endswith(codecs.EF_SUFFIX):
                name += codecs.EF_SUFFIX
            object.__setattr__(self, "codec", name)
            object.__setattr__(self, "error_feedback", False)
        if self.hierarchical:
            if self.backend not in ("jax", "hierarchical"):
                raise ValueError(
                    f"conflicting hierarchical=True and "
                    f"backend={self.backend!r}")
            object.__setattr__(self, "backend", "hierarchical")
            object.__setattr__(self, "hierarchical", False)
        # resolve + normalise registry names (raises on unknown ones)
        object.__setattr__(self, "codec", codecs.get_codec(self.codec).name)
        backend_lib.get_backend(self.backend)
        if self.reduce_scatter:
            if not self.codec_obj.linear:
                raise ValueError(
                    f"codec {self.codec!r} is non-linear (quantised wires "
                    f"cannot be reduced in flight) and has no "
                    f"reduce_scatter path; use the default allreduce")
            if self.codec_obj.stateful:
                raise ValueError(
                    f"codec {self.codec!r} is stateful; the RS+AG "
                    f"decomposition has no stateful encode hook — use "
                    f"the default allreduce")
            if self.backend == "hierarchical":
                raise ValueError("hierarchical backend has no RS+AG path; "
                                 "use backend='jax' or 'ringsim'")
        # resolve + normalise the zero1 param-allgather codec
        object.__setattr__(self, "param_codec",
                           codecs.get_codec(self.param_codec).name)
        if self.zero1:
            if self.reduce_scatter:
                raise ValueError(
                    "zero1 subsumes reduce_scatter: the grad "
                    "reduce-scatter and the updated-param allgather ARE "
                    "the RS+AG decomposition with the optimizer update "
                    "in between — drop reduce_scatter=True")
            if self.backend == "hierarchical":
                raise ValueError("hierarchical backend has no "
                                 "reduce-scatter path; zero1 needs "
                                 "backend='jax' or 'ringsim'")
            if self.overlap == "backward":
                raise ValueError(
                    "zero1 does not compose with overlap='backward': the "
                    "updated-param allgather needs the sharded optimizer "
                    "update, which runs AFTER the backward pass — use "
                    "overlap='staged' (grad reduce-scatters still launch "
                    "before any param allgather)")
            if self.param_codec_obj.stateful:
                raise ValueError(
                    f"param_codec {self.param_codec!r} is stateful; the "
                    f"param allgather broadcasts state (the updated "
                    f"params), so error-feedback residuals would "
                    f"double-apply — use a stateless codec")
        elif self.param_codec != "identity":
            raise ValueError("param_codec configures the zero1 param "
                             "allgather; set zero1=True")

    @property
    def codec_obj(self) -> codecs.WireCodec:
        return codecs.get_codec(self.codec)

    @property
    def param_codec_obj(self) -> codecs.WireCodec:
        return codecs.get_codec(self.param_codec)

    @property
    def backend_obj(self) -> backend_lib.CollectiveBackend:
        return backend_lib.get_backend(self.backend)

    @property
    def is_hierarchical(self) -> bool:
        return self.backend == "hierarchical"

    @property
    def overlap_backward(self) -> bool:
        """Wait-free backprop: collectives launch mid-backward."""
        return self.overlap == "backward"

    @property
    def dense_collective(self) -> str:
        if self.zero1 or self.reduce_scatter:
            return REDUCE_SCATTER
        return ALLREDUCE


# ---------------------------------------------------------------------------
# Static leaf specs + classification (Alg. 1 / Alg. 2, shapes only)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseSpec:
    shape: Tuple[int, ...]
    dtype: str

    @property
    def size(self) -> int:
        return math.prod(self.shape)


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    rows: int
    dense_shape: Tuple[int, ...]
    dtype: str
    index_dtype: str = "int32"

    @property
    def row_elems(self) -> int:
        return math.prod(self.dense_shape[1:])


LeafSpec = Union[DenseSpec, SparseSpec]


def _is_leaf(x) -> bool:
    """Grad-tree leaves: dense arrays, IndexedSlices, or contribution
    lists (variables with multiple uses, e.g. tied embeddings)."""
    return isinstance(x, (IndexedSlices, list)) or hasattr(x, "shape")


def contribution_spec(g) -> LeafSpec:
    if isinstance(g, IndexedSlices):
        return SparseSpec(rows=int(g.indices.shape[0]),
                          dense_shape=tuple(g.dense_shape),
                          dtype=jnp.dtype(g.values.dtype).name,
                          index_dtype=jnp.dtype(g.indices.dtype).name)
    return DenseSpec(shape=tuple(g.shape), dtype=jnp.dtype(g.dtype).name)


def classify(contribs: Tuple[LeafSpec, ...],
             config: ExchangeConfig) -> LeafSpec:
    """Static mirror of ``accumulation.accumulate_gradients``: the
    post-accumulation representation of one variable's contributions."""
    def result_dtype() -> str:
        out = jnp.dtype(contribs[0].dtype)
        for c in contribs[1:]:
            out = jnp.promote_types(out, c.dtype)
        return out.name

    def dense_result() -> DenseSpec:
        shape = next((c.shape for c in contribs
                      if isinstance(c, DenseSpec)), None)
        if shape is None:                # all-sparse: densified shape
            shape = contribs[0].dense_shape
        return DenseSpec(shape=tuple(shape), dtype=result_dtype())

    def gather_result(specs: Sequence[LeafSpec]) -> SparseSpec:
        # dense contributions downgrade to all-rows slices (Alg. 1)
        rows = sum(c.rows if isinstance(c, SparseSpec) else c.shape[0]
                   for c in specs)
        shape = next(c.dense_shape for c in specs
                     if isinstance(c, SparseSpec))
        idx = next((c.index_dtype for c in specs
                    if isinstance(c, SparseSpec)), "int32")
        return SparseSpec(rows=rows, dense_shape=tuple(shape),
                          dtype=result_dtype(), index_dtype=idx)

    any_sparse = any(isinstance(c, SparseSpec) for c in contribs)
    any_dense = any(isinstance(c, DenseSpec) for c in contribs)

    if config.sparse_as_dense:               # Listing-1 pre-pass: all dense
        return dense_result()
    if len(contribs) < 2:                    # pass-through
        return contribs[0]
    if not any_sparse:
        return dense_result()                # dense reduce
    if config.algorithm == "tf_algorithm1":
        return gather_result(contribs)       # ANY sparse => gather
    if config.algorithm == "proposed_algorithm2":
        if any_dense:
            return dense_result()            # Alg. 2 lines 5-7: densify
        return gather_result(contribs)       # all-sparse stays sparse
    raise ValueError(f"unknown accumulation algorithm: {config.algorithm}")


# ---------------------------------------------------------------------------
# Runtime accumulation matching the classification
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Pending:
    """A dense-destined leaf whose densification is deferred to pack time
    (so the scatter-add fuses with the wire-dtype downcast)."""
    slices: Optional[IndexedSlices]
    dense: Optional[jax.Array]


def _accumulate_leaf(leaf, spec: LeafSpec, config: ExchangeConfig):
    """Accumulate one variable's contributions to the representation the
    plan classified.  Dense-destined leaves with sparse contributions
    come back as ``_Pending`` — densified later, inside pack."""
    contribs = leaf if isinstance(leaf, list) else [leaf]
    sparse = [c for c in contribs if isinstance(c, IndexedSlices)]
    dense = [c for c in contribs if not isinstance(c, IndexedSlices)]

    if isinstance(spec, SparseSpec):         # gather path
        if len(contribs) == 1:
            return contribs[0]
        slices = [c if isinstance(c, IndexedSlices)
                  else accumulation.dense_to_slices(c) for c in contribs]
        return concat_slices(tuple(slices))

    # dense path
    dense_sum = None
    if dense:
        dense_sum = dense[0]
        for g in dense[1:]:
            dense_sum = dense_sum + g
    if not sparse:
        return dense_sum
    merged = sparse[0] if len(sparse) == 1 else concat_slices(tuple(sparse))
    return _Pending(slices=merged, dense=dense_sum)


def _materialise(x, config: ExchangeConfig) -> jax.Array:
    """Densify a pending leaf (XLA scatter-add or Pallas kernel)."""
    if isinstance(x, _Pending):
        out = None
        if x.slices is not None:
            out = accumulation.densify(x.slices,
                                       use_kernel=config.use_kernel)
        if x.dense is not None:
            out = x.dense if out is None else out + x.dense
        return out
    if isinstance(x, IndexedSlices):
        return accumulation.densify(x, use_kernel=config.use_kernel)
    return x


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseBucket:
    """One fusion buffer: contiguous slots over the dense-leaf list.

    Buckets are wire-dtype-homogeneous by construction (leaves are
    grouped before bucketing), so the packed buffer never promotes.
    """
    slots: Tuple[fusion._Slot, ...]     # leaf_idx indexes dense_leaf_ids
    collective: str
    n_elems: int
    wire_dtype: str


@dataclasses.dataclass(frozen=True)
class BucketStage:
    """One independently launchable schedule unit: ``pack -> collective
    -> unpack`` for a single bucket.

    ``leaf_ids`` is the stage's READINESS KEY: the set of grad-tree
    leaves this bucket consumes.  Backward produces leaves in reverse
    flatten order (output head first), so the stage becomes launchable
    once its *smallest* leaf id has been emitted — ``ready_key`` orders
    the schedule accordingly.
    """
    kind: str                    # "dense" | "gather"
    bucket_id: int               # index into plan.dense_buckets, or the
    #                              gathered leaf id itself
    leaf_ids: Tuple[int, ...]    # readiness key: leaves this stage needs
    trigger: str = ""            # top-level model block whose backward
    #                              emission makes this stage launchable
    #                              (the block of the ready_key leaf)

    @property
    def ready_key(self) -> int:
        return min(self.leaf_ids)


@dataclasses.dataclass(frozen=True)
class BucketSchedule:
    """Dependency-ordered stage list for one plan.

    Stages are sorted reverse-layer (descending ``ready_key``): the
    bucket whose leaves finalise earliest in the backward pass launches
    first, so its collective is in flight while later stages are still
    accumulating/packing.  Every bucket is exactly one stage; leaf sets
    partition the grad tree.
    """
    stages: Tuple[BucketStage, ...]

    @property
    def n_stages(self) -> int:
        return len(self.stages)


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Static schedule for one gradient-tree structure."""
    treedef: Any
    contrib_specs: Tuple[Tuple[LeafSpec, ...], ...]
    leaf_specs: Tuple[LeafSpec, ...]     # post-accumulation, per leaf
    dense_leaf_ids: Tuple[int, ...]
    dense_buckets: Tuple[DenseBucket, ...]
    gather_leaf_ids: Tuple[int, ...]
    config: ExchangeConfig
    schedule: BucketSchedule
    leaf_blocks: Tuple[str, ...] = ()    # per-leaf top-level block label
    #                                      (from the grad tree's key
    #                                      paths; "" when unlabelled)

    # -- static accounting ---------------------------------------------------
    @property
    def n_leaves(self) -> int:
        return len(self.leaf_specs)

    @property
    def fingerprint(self) -> str:
        """Stable digest of the gradient-tree structure this plan was
        compiled for (see ``tree_fingerprint``) — the plan-cache key
        component and, in structural form, the tuning-artifact key."""
        return tree_fingerprint(self.treedef, self.contrib_specs)

    @property
    def n_buckets(self) -> int:
        return len(self.dense_buckets) + len(self.gather_leaf_ids)

    @property
    def n_collectives(self) -> int:
        """Logical collective launches (P-independent) — the sum of the
        schedule's per-stage counts, so staged and fused execution bill
        identically by construction."""
        return sum(self.stage_collectives(s) for s in self.schedule.stages)

    # -- per-stage accounting (the BucketSchedule contract) ------------------
    @property
    def _zero1_param_tensors(self) -> int:
        """Tensors the zero1 param allgather moves per dense stage:
        the encoded shard, plus per-worker scales for sided codecs."""
        return 1 + (0 if self.config.param_codec_obj.linear else 1)

    def zero1_shard_elems(self, stage: BucketStage,
                          n_workers: Union[int, Sequence[int]]) -> int:
        """Per-worker flat shard length of one dense stage's bucket
        under ZeRO-1 (bucket elements padded to a multiple of P) — the
        slice of (params, EMA buffers) this worker owns and updates."""
        p = math.prod(self._levels(n_workers))
        b = self.dense_buckets[stage.bucket_id]
        return codecs.padded_elems(b.n_elems, p) // p

    def _zero1_param_hop_wire_bytes(self, stage: BucketStage,
                                    n_workers: Union[int, Sequence[int]]
                                    ) -> Tuple[int, ...]:
        """Per-hop wire bytes of one dense stage's updated-param
        allgather: every worker receives the other P-1 encoded shards
        (+ their scales), i.e. (P-1)/P of the padded bucket in the
        param codec's wire dtype."""
        levels = self._levels(n_workers)
        if math.prod(levels) <= 1:
            return tuple(0 for _ in levels)
        payload = self.config.param_codec_obj.wire_bytes(
            self.zero1_shard_elems(stage, n_workers), "float32")
        return self.config.backend_obj.gather_hop_wire_bytes(payload,
                                                             levels)

    def stage_collectives(self, stage: BucketStage) -> int:
        """Logical collectives one stage launches (P-independent)."""
        if stage.kind == "dense" and self.config.zero1:
            # grad half (RS for linear wires, values+scales gather for
            # quantised ones) + the updated-param allgather half
            grad = 1 if self.config.codec_obj.linear else 2
            return grad + self._zero1_param_tensors
        if not self.config.codec_obj.linear:
            # non-linear codecs never reduce in flight: every bucket is
            # one values allgather + one scales allgather, whatever its
            # nominal kind (same convention that bills RS+AG as 2).  On
            # the hierarchical backend DENSE buckets run one such
            # (gather, reduce, requantize) round per mesh level.
            if stage.kind == "dense" and self.config.is_hierarchical:
                return 2 * self.config.hierarchy_levels
            return 2
        be = self.config.backend_obj
        nl = self.config.hierarchy_levels
        if stage.kind == "dense":
            return be.logical_collectives(
                self.dense_buckets[stage.bucket_id].collective, nl)
        return be.logical_collectives(ALLGATHER, nl)

    def stage_wire_bytes(self, stage: BucketStage,
                         n_workers: Union[int, Sequence[int]]) -> int:
        """Bytes one stage moves per worker (sum over mesh-level hops)."""
        return sum(self.stage_hop_wire_bytes(stage, n_workers))

    def stage_hop_wire_bytes(self, stage: BucketStage,
                             n_workers: Union[int, Sequence[int]]
                             ) -> Tuple[int, ...]:
        """Per-mesh-level wire bytes for one stage, in ``levels`` order
        (outermost first, matching the hierarchical ``n_workers``
        tuple).  Flat backends report a single hop; the hierarchical
        backend bills each level's collective separately — for
        non-linear codecs that is the per-hop requantized payload, NOT
        a full-mesh gather."""
        levels = self._levels(n_workers)
        be = self.config.backend_obj
        if stage.kind == "dense":
            b = self.dense_buckets[stage.bucket_id]
            if self.config.zero1:
                codec = self.config.codec_obj
                if codec.linear:
                    p = math.prod(levels)
                    grad = (int(comm.reduce_scatter_wire_bytes(
                        b.n_elems, b.wire_dtype, p)) if p > 1 else 0,)
                else:
                    # quantised grads still move as the replicated
                    # path's (values, scales) allgather — the shard is
                    # sliced AFTER decode-sum, so the wire is unchanged
                    grad = be.dense_hop_wire_bytes(
                        b.collective, b.n_elems, b.wire_dtype, codec,
                        levels)
                param = self._zero1_param_hop_wire_bytes(stage, n_workers)
                return tuple(g + q for g, q in zip(grad, param))
            return be.dense_hop_wire_bytes(b.collective, b.n_elems,
                                           b.wire_dtype,
                                           self.config.codec_obj, levels)
        return be.gather_hop_wire_bytes(
            self._gather_payload_bytes(self.leaf_specs[stage.bucket_id]),
            levels)

    def stage_hlo_collectives(self, stage: BucketStage,
                              n_workers: Union[int, Sequence[int]]) -> int:
        """Collective ops one stage lowers to in the compiled HLO."""
        levels = self._levels(n_workers)
        be = self.config.backend_obj
        codec = self.config.codec_obj
        if stage.kind == "dense":
            b = self.dense_buckets[stage.bucket_id]
            if self.config.zero1:
                grad = (be.hlo_ops_reduce_scatter(levels) if codec.linear
                        else be.hlo_ops_dense(b.collective, codec, levels))
                return grad + be.hlo_ops_gather(self._zero1_param_tensors,
                                                levels)
            return be.hlo_ops_dense(b.collective, codec, levels)
        n_tensors = 2 + (0 if codec.linear else 1)
        return be.hlo_ops_gather(n_tensors, levels)

    def stage_hop_ops(self, stage: BucketStage,
                      n_workers: Union[int, Sequence[int]]
                      ) -> Tuple[int, ...]:
        """Per-mesh-level collective-op counts for one stage — the α
        (launch latency) companion of ``stage_hop_wire_bytes``, split
        the same way so the cost model can bill each level's launches
        at that level's latency.  Sums to ``stage_hlo_collectives``."""
        levels = self._levels(n_workers)
        be = self.config.backend_obj
        codec = self.config.codec_obj
        if stage.kind == "dense":
            b = self.dense_buckets[stage.bucket_id]
            if self.config.zero1:
                grad = ((be.hlo_ops_reduce_scatter(levels),)
                        if codec.linear
                        else be.dense_hop_ops(b.collective, codec, levels))
                param = be.gather_hop_ops(self._zero1_param_tensors,
                                          levels)
                return tuple(g + q for g, q in zip(grad, param))
            return be.dense_hop_ops(b.collective, codec, levels)
        n_tensors = 2 + (0 if codec.linear else 1)
        return be.gather_hop_ops(n_tensors, levels)

    def _wire_dtype_for(self, spec: LeafSpec) -> str:
        return self.config.codec_obj.wire_dtype(spec.dtype)

    def _levels(self, n_workers: Union[int, Sequence[int]]
                ) -> Tuple[int, ...]:
        levels = (tuple(n_workers) if not isinstance(n_workers, int)
                  else (n_workers,))
        if self.config.is_hierarchical \
                and len(levels) != self.config.hierarchy_levels:
            raise ValueError(
                f"hierarchical plan with {self.config.hierarchy_levels} "
                f"levels needs per-level worker counts, got {n_workers!r}")
        return levels

    def _gather_payload_bytes(self, spec: SparseSpec) -> int:
        """Per-worker encoded IndexedSlices payload (values in the wire
        dtype + native-width indices + codec side scales)."""
        codec = self.config.codec_obj
        return (codec.wire_bytes(spec.rows * spec.row_elems, spec.dtype)
                + spec.rows * comm.dtype_bytes(spec.index_dtype))

    def wire_bytes(self, n_workers: Union[int, Sequence[int]]) -> int:
        """Bytes moved per worker per step — the single source of truth
        shared by the benchmarks, the roofline model and the dry-run
        collective audit.  The sum of the schedule's per-stage bytes
        (each stage delegates to the configured backend's accounting
        with the configured codec's payload sizes).  Hierarchical plans
        require ``n_workers`` as a per-level tuple (e.g.
        ``(n_pods, workers_per_pod)``) matching
        ``config.hierarchy_levels``."""
        return sum(self.stage_wire_bytes(s, n_workers)
                   for s in self.schedule.stages)

    def hlo_collectives(self, n_workers: Union[int, Sequence[int]]) -> int:
        """Exact collective-op count in the lowered HLO (the dry-run
        audit contract): backends may lower one logical collective to
        several ops (per-axis psums, ring ppermute hops) and one gather
        bucket lowers to one all-gather per exchanged tensor (indices +
        values [+ codec scales])."""
        return sum(self.stage_hlo_collectives(s, n_workers)
                   for s in self.schedule.stages)

    def hlo_allgather_factor(self, n_workers: Union[int, Sequence[int]]
                             ) -> Optional[float]:
        """Predicted wire/result-bytes ratio over every hop that lowers
        to an HLO all-gather: gather buckets at every mesh level plus,
        for non-linear codecs, the dense buckets' per-hop requantize
        gathers.  Each such hop's result is ``p_k`` group payloads for
        ``(p_k - 1)`` on the wire, so the aggregate is the wire-weighted
        mix of ``(p_k - 1)/p_k`` — NOT uniform when requantize hops
        (constant payload per hop) and telescoping gather hops (payload
        grows with the prefix product) coexist in one plan.  ``None``
        when nothing lowers to an all-gather; backends fall back to
        their uniform single-kind factor."""
        levels = self._levels(n_workers)
        codec = self.config.codec_obj
        wire = result = 0.0
        for s in self.schedule.stages:
            if s.kind == "dense" and codec.linear:
                if not self.config.zero1:
                    continue               # psum / RS+AG, not a pure gather
                # zero1 + linear wire: the stage's only all-gather hop
                # is the updated-param broadcast (the grad half is a
                # bare reduce-scatter)
                hops = self._zero1_param_hop_wire_bytes(s, n_workers)
            else:
                # gather stages and quantised dense stages; under zero1
                # the latter's hop bytes already include the param
                # allgather — every hop is a pure gather at the same
                # per-level factor, so the mix stays exact
                hops = self.stage_hop_wire_bytes(s, n_workers)
            for wk, pk in zip(hops, levels):
                if pk > 1:
                    wire += wk
                    result += wk * pk / (pk - 1)
        return wire / result if result else None

    def buffer_bytes(self, n_workers: Union[int, Sequence[int]]) -> int:
        """Size of the accumulated representation each worker holds after
        exchange (paper Fig. 3 / Fig. 5): gather buffers grow linearly in
        P, dense buffers are constant."""
        p = (n_workers if isinstance(n_workers, int)
             else math.prod(n_workers))
        codec = self.config.codec_obj
        total = self.dense_bytes
        for i in self.gather_leaf_ids:
            s = self.leaf_specs[i]
            # the gathered buffer holds WIRE-dtype values (execute
            # encodes before the allgather) plus native-width indices
            # and, for sided codecs, one scale per worker
            total += comm.gathered_buffer_bytes(
                s.rows, s.row_elems, self._wire_dtype_for(s), p,
                index_dtype=s.index_dtype)
            total += p * codec.scale_bytes
        return total

    @property
    def dense_bytes(self) -> int:
        """Total dense accumulated gradient bytes (P-independent)."""
        return sum(comm.dense_buffer_bytes(self.leaf_specs[i].shape,
                                           self.leaf_specs[i].dtype)
                   for i in self.dense_leaf_ids)

    def param_bytes(self) -> int:
        """Per-worker parameter memory (params are replicated under
        every strategy, zero1 included — only the MASTER copy shards):
        every leaf's dense shape at its native dtype.  Sparse grad
        leaves still correspond to dense param tensors."""
        total = 0
        for s in self.leaf_specs:
            shape = s.shape if isinstance(s, DenseSpec) else s.dense_shape
            total += math.prod(shape) * comm.dtype_bytes(s.dtype)
        return total

    @property
    def sparse_bytes_per_worker(self) -> int:
        """Per-worker IndexedSlices bytes entering the gather collectives
        (the paper model's S term)."""
        total = 0
        for i in self.gather_leaf_ids:
            s = self.leaf_specs[i]
            total += s.rows * (
                s.row_elems * comm.dtype_bytes(s.dtype)
                + comm.dtype_bytes(s.index_dtype))
        return total

    def describe(self) -> str:
        """Human-readable bucket/collective table (docs + dry-run),
        naming the active codec and backend per bucket so benchmark CSVs
        distinguish bf16 from int8 runs."""
        codec, be = self.config.codec, self.config.backend
        lines = ["| bucket | kind | collective | codec | backend | elems "
                 "| wire dtype |",
                 "|---|---|---|---|---|---|---|"]
        for k, b in enumerate(self.dense_buckets):
            lines.append(f"| {k} | dense x{len(b.slots)} | {b.collective} "
                         f"| {codec} | {be} | {b.n_elems} "
                         f"| {b.wire_dtype} |")
        for k, i in enumerate(self.gather_leaf_ids):
            s = self.leaf_specs[i]
            lines.append(f"| g{k} | sparse rows={s.rows} | allgather "
                         f"| {codec} | {be} | {s.rows * s.row_elems} "
                         f"| {self._wire_dtype_for(s)} |")
        return "\n".join(lines)

    def describe_schedule(self, n_workers: Union[int, Sequence[int], None]
                          = None) -> str:
        """Human-readable BucketSchedule: stage launch order, readiness
        keys, per-stage collectives (and wire bytes when ``n_workers``
        is given) — what a dry-run / trainer will actually run."""
        sch = self.schedule
        ov = self.config.overlap
        mode = ("wait-free backward" if ov == "backward"
                else "overlap" if ov else "fused")
        launch = ("each stage launches from inside the backward pass, "
                  "the moment its trigger block's cotangents are emitted"
                  if ov == "backward"
                  else "launch order reverse-layer (descending readiness "
                  "key)")
        lines = [f"schedule: {sch.n_stages} stages ({mode}), {launch}"]
        state_per_stage = self.state_bytes_per_stage()
        for k, st in enumerate(sch.stages):
            wire = ""
            if n_workers is not None:
                wire = f", {self.stage_wire_bytes(st, n_workers)} wire B"
            state = (f", {state_per_stage[k]} state B"
                     if state_per_stage[k] else "")
            trig = f", trigger={st.trigger}" if st.trigger else ""
            lines.append(
                f"  stage {k}: {st.kind} bucket {st.bucket_id}, "
                f"{len(st.leaf_ids)} leaves (ready@{st.ready_key}"
                f"{trig}), "
                f"{self.stage_collectives(st)} collectives{wire}{state}")
        if n_workers is not None and self.config.is_hierarchical:
            hops = self.hop_wire_bytes(n_workers)
            lines.append("  per-hop wire B (outermost level first): "
                         + ", ".join(f"L{k}={b}"
                                     for k, b in enumerate(hops)))
        return "\n".join(lines)

    # -- telemetry naming ----------------------------------------------------
    def stage_name(self, stage: BucketStage,
                   index: Optional[int] = None) -> str:
        """Structured annotation name for one stage — the identity the
        telemetry subsystem keys everything on (``jax.named_scope``
        paths in lowered HLO, wire-recorder stage attribution, trace
        rows, and the predicted-vs-measured report):

            exchange/s03/allreduce/bucket=dense2[/trigger=block5]
        """
        k = (self.schedule.stages.index(stage) if index is None
             else index)
        if stage.kind == "dense":
            coll = self.dense_buckets[stage.bucket_id].collective
            bucket = f"dense{stage.bucket_id}"
        else:
            coll = ALLGATHER
            bucket = f"leaf{stage.bucket_id}"
        name = f"exchange/s{k:02d}/{coll}/bucket={bucket}"
        if stage.trigger:
            name += f"/trigger={stage.trigger}"
        return name

    def stage_names(self) -> Tuple[str, ...]:
        """Annotation names in schedule order (one per stage)."""
        return tuple(self.stage_name(s, k)
                     for k, s in enumerate(self.schedule.stages))

    # -- execution -----------------------------------------------------------
    def accumulate(self, grads) -> List[Any]:
        """Step 1 at runtime: per-leaf accumulation to the classified
        representation (dense leaves may come back ``_Pending``)."""
        return [_accumulate_leaf(leaf, spec, self.config)
                for leaf, spec in zip(self._flatten_checked(grads),
                                      self.leaf_specs)]

    def accumulate_tree(self, grads):
        """Step 1 as a public pytree: dense-destined leaves fully
        densified (no deferred ``_Pending``), gather-destined leaves
        still IndexedSlices — the paper's per-variable accumulation
        result before any collective."""
        out = [_materialise(x, self.config) if isinstance(x, _Pending)
               else x for x in self.accumulate(grads)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def pack_bucket(self, bucket: DenseBucket, leaves: List[Any]
                    ) -> jax.Array:
        """Fuse a bucket into one 1-D buffer.  Densification of
        deferred-sparse slots happens HERE (Pallas kernel if configured),
        fused with the codec's narrowing cast.  Stateless linear codecs
        pack straight into the wire dtype; non-linear and stateful
        codecs pack f32 and encode afterwards (``codec.encode`` needs
        the full-precision buffer for its absmax scale, and stateful
        encodes add the f32 residual before narrowing)."""
        codec = self.config.codec_obj
        pack_dtype = (bucket.wire_dtype
                      if codec.linear and not codec.stateful
                      else "float32")
        with jax.named_scope("pack"):
            parts = []
            for slot in bucket.slots:
                leaf_id = self.dense_leaf_ids[slot.leaf_idx]
                x = _materialise(leaves[leaf_id], self.config)
                parts.append(x.reshape(-1).astype(pack_dtype))
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def unpack_bucket(self, bucket: DenseBucket, buf: jax.Array,
                      out: List[Any], inv_scale) -> None:
        """Invert ``pack_bucket``: split, reshape, upcast to each leaf's
        original dtype, apply gradient averaging."""
        with jax.named_scope("unpack"):
            for slot in bucket.slots:
                leaf_id = self.dense_leaf_ids[slot.leaf_idx]
                spec = self.leaf_specs[leaf_id]
                x = jax.lax.dynamic_slice_in_dim(buf, slot.offset,
                                                 slot.size)
                x = x.reshape(spec.shape).astype(spec.dtype)
                if inv_scale is not None:
                    x = x * inv_scale
                out[leaf_id] = x

    def _check_axes(self, axis_name: comm.AxisNames) -> Tuple[str, ...]:
        axes = tuple(a for a in ([axis_name] if isinstance(axis_name, str)
                                 else (axis_name or ())))
        if self.config.is_hierarchical and axes \
                and len(axes) != self.config.hierarchy_levels:
            raise ValueError(
                f"hierarchical plan spans {self.config.hierarchy_levels} "
                f"mesh axes but got axis_name={axis_name!r}")
        return axes

    def backward_block_stages(self, hooked_blocks=None
                              ) -> Tuple[Dict[str, Tuple[int, ...]],
                                         Tuple[int, ...]]:
        """Split the schedule for wait-free (in-backward) launch.

        Returns ``(block -> stage indices, tail stage indices)``.  A
        stage is HOOKABLE — launchable from inside a block's
        ``custom_vjp`` boundary — when it is dense and every leaf it
        consumes lives in one top-level block (guaranteed by the
        block-aligned bucketing of ``overlap='backward'``) that is in
        ``hooked_blocks`` (``None`` = every labelled block).  Gather
        stages and stages of unhooked blocks form the TAIL, executed
        after ``jax.grad`` returns — sparse embedding contributions are
        assembled outside autodiff, so they can never launch
        mid-backward.  Stage indices stay in schedule order, so codec
        state entries map 1:1 onto ``ExchangeState.bucket_states``."""
        hooked: Dict[str, List[int]] = {}
        tail: List[int] = []
        for k, st in enumerate(self.schedule.stages):
            blocks = ({self.leaf_blocks[i] for i in st.leaf_ids}
                      if self.leaf_blocks else {""})
            b = blocks.pop() if len(blocks) == 1 else None
            if (st.kind == "dense" and b
                    and (hooked_blocks is None or b in hooked_blocks)):
                hooked.setdefault(b, []).append(k)
            else:
                tail.append(k)
        return ({k: tuple(v) for k, v in hooked.items()}, tuple(tail))

    # -- staged execution primitives -----------------------------------------
    def _launch_gather(self, stage: BucketStage, leaves: List[Any],
                       axes: Tuple[str, ...]) -> Tuple:
        """Issue one gather stage's collectives: encode the accumulated
        IndexedSlices leaf and allgather (indices, wire [, scales]).
        Only the WIRE is narrow — decode back to the leaf dtype happens
        at finish, before the scatter-add, so duplicate rows accumulate
        at full precision."""
        s = leaves[stage.bucket_id]
        codec = self.config.codec_obj
        be = self.config.backend_obj
        with jax.named_scope("quantize"):
            wire, scale = codec.encode(s.values,
                                       use_kernel=self.config.use_kernel)
        rows = s.values.shape[0]
        if not axes:
            return (s.indices, wire, scale, rows)
        g_idx = be.all_gather(s.indices, axes)
        g_wire = be.all_gather(wire, axes)            # (p*rows, ...)
        g_scales = (be.all_gather(scale, axes)        # (p,)
                    if scale is not None else None)
        return (g_idx, g_wire, g_scales, rows)

    def _finish_gather(self, stage: BucketStage, inflight: Tuple,
                       out: List[Any], inv_scale, axes: Tuple[str, ...],
                       p: int) -> None:
        """Decode + densify one gathered leaf into ``out``."""
        spec = self.leaf_specs[stage.bucket_id]
        codec = self.config.codec_obj
        g_idx, g_wire, g_scales, rows = inflight
        if codec.linear or not axes:
            g_vals = codec.decode(g_wire, g_scales, spec.dtype)
        else:
            # per-worker scales: decode each worker's chunk against its
            # own absmax scale before the scatter-add
            per = g_wire.astype(jnp.float32).reshape(
                (p, rows) + g_wire.shape[1:])
            per = per * g_scales.astype(jnp.float32).reshape(
                (p,) + (1,) * (per.ndim - 1))
            g_vals = per.reshape(g_wire.shape).astype(spec.dtype)
        g = IndexedSlices(g_idx, g_vals, spec.dense_shape)
        x = accumulation.densify(g, use_kernel=self.config.use_kernel)
        x = x.astype(spec.dtype)
        if inv_scale is not None:
            x = x * inv_scale
        out[stage.bucket_id] = x

    def _hop_reduce_dense(self, buf: jax.Array, bstate,
                          axes: Tuple[str, ...]) -> Tuple[jax.Array, Any]:
        """Per-hop requantizing hierarchical reduction of one packed f32
        bucket: innermost axis first, each level runs encode -> gather
        -> decode-sum, and the partial sum is RE-ENCODED (``requantize``)
        before the next level — so no full-mesh gather ever happens and
        every hop moves the quantised payload.  Hop 0 is the only
        stateful encode (error feedback compensates the worker-local
        quantisation; later hops' error is group-replicated)."""
        codec = self.config.codec_obj
        be = self.config.backend_obj
        for level, ax in enumerate(reversed(axes)):
            with jax.named_scope(f"hop{level}"):
                with jax.named_scope("quantize"):
                    wire, scale, bstate = codec.encode_hop(
                        buf, bstate, level,
                        use_kernel=self.config.use_kernel)
                p_ax = comm.axis_size((ax,))
                g_wire = be.all_gather(wire, (ax,))
                g_scale = (be.all_gather(scale, (ax,))
                           if scale is not None else None)
                buf = codec.reduce_hop(g_wire, g_scale, p_ax,
                                       jnp.float32)
        return buf, bstate

    def _launch_dense(self, stage: BucketStage, leaves: List[Any],
                      axes: Tuple[str, ...], p: int, bstate
                      ) -> Tuple[Tuple, Any]:
        """Pack one dense bucket (densify fused) and issue its
        collective(s).  Linear codecs return the fully reduced buffer;
        non-linear codecs on flat backends return the gathered (wire,
        scales) pair whose decode-reduction happens at finish; on the
        hierarchical backend they run the per-hop requantizing
        reduction and return the already-reduced f32 buffer.  ``bstate``
        is this stage's codec state; returns (inflight, new state)."""
        bucket = self.dense_buckets[stage.bucket_id]
        codec = self.config.codec_obj
        be = self.config.backend_obj
        buf = self.pack_bucket(bucket, leaves)
        if codec.linear and not codec.stateful:
            if not axes:
                return (buf,), bstate
            if bucket.collective == REDUCE_SCATTER:
                pad = -len(buf) % p
                if pad:
                    buf = jnp.pad(buf, (0, pad))
                shard = be.reduce_scatter(buf, axes)
                return (be.all_gather(shard, axes)[:bucket.n_elems],), \
                    bstate
            return (be.all_reduce(buf, axes),), bstate
        if not codec.linear and self.config.is_hierarchical and axes \
                and len(axes) > 1:
            red, bstate = self._hop_reduce_dense(buf, bstate, axes)
            return (red,), bstate
        with jax.named_scope("quantize"):
            wire, scale, bstate = codec.encode_stateful(
                buf, bstate, use_kernel=self.config.use_kernel)
        if codec.linear:
            # stateful linear (e.g. bf16+ef): the compensated wire still
            # sums in flight; decode is the unpack upcast
            if scale is not None:
                raise ValueError(f"linear codec {codec.name!r} returned "
                                 f"side scales; scales cannot be summed "
                                 f"in flight")
            if not axes:
                return (wire,), bstate
            return (be.all_reduce(wire, axes),), bstate
        # non-linear (quantised) codec on a flat backend: workers
        # quantise against their own absmax scale, so the wire cannot be
        # reduced in flight — allgather (values, scales) and reduce
        # after decode (at finish)
        if not axes:
            return (codec.decode(wire, scale, jnp.float32),), bstate
        return (be.all_gather(wire, axes), be.all_gather(scale, axes)), \
            bstate

    def _finish_dense(self, stage: BucketStage, inflight: Tuple,
                      out: List[Any], inv_scale, axes: Tuple[str, ...],
                      p: int) -> None:
        """Reduce-after-decode (non-linear) + unpack one dense bucket.
        Single-element payloads are already reduced (linear collectives,
        the local path, and the hierarchical per-hop reduction)."""
        bucket = self.dense_buckets[stage.bucket_id]
        codec = self.config.codec_obj
        if len(inflight) == 1:
            buf = inflight[0]
        else:
            buf = codecs.sum_decoded(codec, inflight[0], inflight[1], p,
                                     jnp.float32)
        self.unpack_bucket(bucket, buf, out, inv_scale)

    def launch_stage(self, stage: BucketStage, leaves: List[Any],
                     axes: Tuple[str, ...], p: int, bstate: Any = ()
                     ) -> Tuple[Tuple, Any]:
        """Pack + issue one stage's collective(s); returns ``(inflight,
        new bucket state)`` — the payload ``finish_stage`` consumes plus
        this stage's updated codec state (passed through untouched for
        zero-state codecs).  ``leaves`` must hold the accumulated
        representation for every id in ``stage.leaf_ids``."""
        name = self.stage_name(stage)
        with jax.named_scope(name), _telemetry.stage_scope(name):
            if stage.kind == "dense":
                inflight, bstate = self._launch_dense(stage, leaves,
                                                      axes, p, bstate)
            else:
                inflight = self._launch_gather(stage, leaves, axes)
            return inflight, bstate

    def finish_stage(self, stage: BucketStage, inflight: Tuple,
                     out: List[Any], inv_scale, axes: Tuple[str, ...],
                     p: int) -> None:
        """Unpack one launched stage's results into ``out`` (decode,
        densify gathers, upcast, apply gradient averaging)."""
        name = self.stage_name(stage)
        with jax.named_scope(name), _telemetry.stage_scope(name):
            if stage.kind == "dense":
                self._finish_dense(stage, inflight, out, inv_scale,
                                   axes, p)
            else:
                self._finish_gather(stage, inflight, out, inv_scale,
                                    axes, p)

    def _flatten_checked(self, grads) -> List[Any]:
        leaves, treedef = jax.tree_util.tree_flatten(grads,
                                                     is_leaf=_is_leaf)
        if treedef != self.treedef:
            raise ValueError(f"grad tree structure changed: {treedef} "
                             f"!= planned {self.treedef}")
        return leaves

    def _exchange_setup(self, grads, axis_name: comm.AxisNames,
                        average: bool):
        leaves = self._flatten_checked(grads)
        axes = self._check_axes(axis_name)
        p = comm.axis_size(axes) if axes else 1
        inv_scale = (1.0 / p) if average and axes else None
        return leaves, axes, p, inv_scale

    def _accumulate_stage(self, stage: BucketStage, raw: List[Any],
                          acc: List[Any]) -> None:
        """Per-stage accumulation: fold only this stage's leaves to
        their classified representation (the deferred part of the
        paper's step 1, interleaved with earlier stages' collectives
        under the scheduled execution)."""
        name = self.stage_name(stage)
        with jax.named_scope(name), _telemetry.stage_scope(name):
            for i in stage.leaf_ids:
                acc[i] = _accumulate_leaf(raw[i], self.leaf_specs[i],
                                          self.config)

    # -- codec state ---------------------------------------------------------
    def init_state(self, n_workers: int = 1) -> ExchangeState:
        """Initial codec state: one entry per schedule stage (the empty
        tuple for zero-state codecs — no pytree leaves — so stateless
        configs see no new arrays anywhere).  ``n_workers`` builds the
        GLOBAL view for ``shard_map``: leaves are flat arrays of
        ``n_workers * n_elems`` to be sharded over dim 0, giving every
        worker its own residual slice."""
        return self.config.codec_obj.init_state(self, n_workers=n_workers)

    def stage_n_elems(self, stage: BucketStage) -> int:
        """Per-worker element count of one stage's payload — the size
        codec state (``WireCodec.init_state``) and its byte accounting
        are both keyed on, so the two cannot drift."""
        if stage.kind == "dense":
            return self.dense_buckets[stage.bucket_id].n_elems
        spec = self.leaf_specs[stage.bucket_id]
        return spec.rows * spec.row_elems

    def state_bytes_per_stage(self) -> Tuple[int, ...]:
        """Per-worker codec-state memory, stage by stage (ExchangeStats
        accounting: residual bytes per bucket)."""
        codec = self.config.codec_obj
        return tuple(codec.state_bytes(self.stage_n_elems(s), kind=s.kind)
                     for s in self.schedule.stages)

    def state_bytes(self) -> int:
        """Total per-worker codec-state memory (0 for stateless)."""
        return sum(self.state_bytes_per_stage())

    def hop_wire_bytes(self, n_workers: Union[int, Sequence[int]]
                       ) -> Tuple[int, ...]:
        """Per-mesh-level wire bytes (``levels`` order, outermost
        first), summed over stages — sums to ``wire_bytes``.  Flat
        backends report one hop; hierarchical runs expose where the
        per-hop requantize saves its bytes."""
        levels = self._levels(n_workers)
        out = [0] * len(levels)
        for stage in self.schedule.stages:
            for k, b in enumerate(self.stage_hop_wire_bytes(stage,
                                                            n_workers)):
                out[k] += b
        return tuple(out)

    def _check_not_zero1(self) -> None:
        if self.config.zero1:
            raise ValueError(
                "zero1 plans fuse the exchange with the optimizer "
                "update (grad reduce-scatter -> shard update -> param "
                "allgather); there is no grads-only execute path — "
                "drive the plan through DistributedOptimizer.zero1_step "
                "(see docs/zero.md)")

    def _check_state(self, state) -> Optional[ExchangeState]:
        codec = self.config.codec_obj
        if state is None:
            if codec.stateful:
                raise ValueError(
                    f"codec {codec.name!r} is stateful: pass "
                    f"state=plan.init_state() and thread the returned "
                    f"state into the next step (see docs/exchange.md)")
            return None
        if not isinstance(state, ExchangeState):
            raise TypeError(f"state must be an ExchangeState, got "
                            f"{type(state).__name__}")
        if state.n_stages != self.schedule.n_stages:
            raise ValueError(
                f"ExchangeState has {state.n_stages} stage entries but "
                f"the plan schedules {self.schedule.n_stages} — state "
                f"from a different plan?")
        return state

    def execute(self, grads, axis_name: comm.AxisNames,
                average: bool = True,
                state: Optional[ExchangeState] = None):
        """Steps 1-3: accumulate, exchange per the BucketSchedule,
        densify.  Honours ``config.overlap``: the staged path launches
        every stage's collective before any unpack so collectives
        overlap the remaining accumulation/pack compute; the fused path
        finishes each stage immediately (the classic serial order).
        Both are the SAME per-stage ops, so results are bitwise
        identical for linear codecs.

        With ``state=`` (an ``ExchangeState``) returns ``(tree, new
        state)`` — required for stateful codecs, a bitwise no-op pass-
        through for stateless ones.  Without it, stateless codecs keep
        the legacy tree-only return and stateful codecs raise.

        Must be called under ``shard_map``/``pjit`` with the mesh axes
        bound (or with ``axis_name=None`` for the local path — the codec
        round-trip still runs so single-device tests see the same wire
        precision, but every collective degrades to a no-op).
        """
        if self.config.overlap:
            return self.execute_scheduled(grads, axis_name,
                                          average=average, state=state)
        return self.execute_fused(grads, axis_name, average=average,
                                  state=state)

    def _stage_states(self, state: Optional[ExchangeState]) -> Tuple:
        if state is None:
            return ((),) * self.schedule.n_stages
        return state.bucket_states

    def execute_fused(self, grads, axis_name: comm.AxisNames,
                      average: bool = True,
                      state: Optional[ExchangeState] = None):
        """Serial reference path: each stage is accumulated, launched,
        and finished before the next stage starts."""
        self._check_not_zero1()
        state = self._check_state(state)
        raw, axes, p, inv_scale = self._exchange_setup(grads, axis_name,
                                                       average)
        acc: List[Any] = [None] * self.n_leaves
        out: List[Any] = [None] * self.n_leaves
        new_states: List[Any] = []
        for stage, bs in zip(self.schedule.stages,
                             self._stage_states(state)):
            self._accumulate_stage(stage, raw, acc)
            inflight, nb = self.launch_stage(stage, acc, axes, p, bs)
            new_states.append(nb)
            self.finish_stage(stage, inflight, out, inv_scale, axes, p)
        # every leaf is exactly one stage's output: nothing pending here
        tree = jax.tree_util.tree_unflatten(self.treedef, out)
        if state is None:
            return tree
        return tree, ExchangeState(new_states)

    def execute_scheduled(self, grads, axis_name: comm.AxisNames,
                          average: bool = True,
                          state: Optional[ExchangeState] = None):
        """Overlap path: stages launch in reverse-layer readiness order,
        each stage's accumulate+pack interleaved AFTER the previous
        stage's collective is already in flight; unpacks run once every
        collective has been issued.  XLA's latency-hiding scheduler can
        then hide stage k's collective behind stage k+1's
        densify/pack compute."""
        self._check_not_zero1()
        state = self._check_state(state)
        raw, axes, p, inv_scale = self._exchange_setup(grads, axis_name,
                                                       average)
        acc: List[Any] = [None] * self.n_leaves
        inflight: List[Tuple] = []
        new_states: List[Any] = []
        for stage, bs in zip(self.schedule.stages,
                             self._stage_states(state)):
            self._accumulate_stage(stage, raw, acc)
            fl, nb = self.launch_stage(stage, acc, axes, p, bs)
            inflight.append(fl)
            new_states.append(nb)
        out: List[Any] = [None] * self.n_leaves
        for stage, fl in zip(self.schedule.stages, inflight):
            self.finish_stage(stage, fl, out, inv_scale, axes, p)
        tree = jax.tree_util.tree_unflatten(self.treedef, out)
        if state is None:
            return tree
        return tree, ExchangeState(new_states)

    def broadcast(self, tree, axis_name: comm.AxisNames, root: int = 0):
        """Broadcast a pytree (e.g. refreshed serving weights) from
        worker ``root`` through the SAME bucketing/codec/backend the
        gradient exchange uses — the serving-side weight hot-swap.

        Requires an all-dense plan (params trees are; compile with
        ``sparse_as_dense=True``)."""
        if self.gather_leaf_ids:
            raise ValueError("broadcast needs an all-dense plan; compile "
                             "with sparse_as_dense=True")
        leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_leaf)
        if treedef != self.treedef:
            raise ValueError(f"tree structure changed: {treedef} "
                             f"!= planned {self.treedef}")
        axes = self._check_axes(axis_name)
        out: List[Any] = list(leaves)
        for b_id in range(len(self.dense_buckets)):
            self.broadcast_bucket(b_id, leaves, out, axes, root=root)
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def broadcast_bucket(self, b_id: int, leaves: List[Any],
                         out: List[Any], axes: Tuple[str, ...],
                         root: int = 0) -> None:
        """One bucket of ``broadcast``: pack -> codec-narrowed wire ->
        (broadcast under ``axes``) -> decode -> unpack into ``out``.

        The streaming unit of the serving hot-swap
        (``serving.engine.HotSwapStream``): refreshed weights ride
        bucket-by-bucket between decode steps, staged into a double
        buffer, and flip atomically once every bucket has landed —
        in-flight requests keep decoding on the old version throughout.
        """
        bucket = self.dense_buckets[b_id]
        codec = self.config.codec_obj
        be = self.config.backend_obj
        name = f"exchange/broadcast/bucket=dense{b_id}"
        with jax.named_scope(name), _telemetry.stage_scope(name):
            buf = self.pack_bucket(bucket, leaves)
            if codec.linear:
                if axes:
                    buf = be.broadcast(buf, axes, root=root)
            else:
                wire, scale = codec.encode(
                    buf, use_kernel=self.config.use_kernel)
                if axes:
                    wire = be.broadcast(wire, axes, root=root)
                    scale = be.broadcast(scale, axes, root=root)
                buf = codec.decode(wire, scale, jnp.float32)
            self.unpack_bucket(bucket, buf, out, None)

    # -- ZeRO-1 execution (the fused exchange+update schedule) ---------------
    @staticmethod
    def _flat_worker_index(axes: Tuple[str, ...]):
        """This worker's flat rank over the mesh axes (the dim-0 chunk
        order of tiled reduce_scatter / all_gather)."""
        flat = None
        for a in axes:
            idx = jax.lax.axis_index(a)
            flat = idx if flat is None else flat * comm.axis_size(a) + idx
        return flat

    def zero1_grad_shard(self, stage: BucketStage, leaves: List[Any],
                         axes: Tuple[str, ...], p: int, bstate
                         ) -> Tuple[jax.Array, Any]:
        """Reduce one dense stage's packed grads down to this worker's
        flat f32 shard (``zero1_shard_elems`` long, zero-padded tail).
        Linear codecs reduce-scatter the wire — no grad allgather ever
        happens; the updated params ride back instead.  Non-linear
        codecs run the replicated path's (values, scales) allgather +
        decode-sum and slice this worker's shard of the full sum, so
        gradients (and error-feedback residuals) match the replicated
        path bit for bit.  Returns ``(shard, new codec state)``."""
        name = self.stage_name(stage)
        with jax.named_scope(name), _telemetry.stage_scope(name):
            return self._zero1_grad_shard(stage, leaves, axes, p, bstate)

    def _zero1_grad_shard(self, stage: BucketStage, leaves: List[Any],
                          axes: Tuple[str, ...], p: int, bstate
                          ) -> Tuple[jax.Array, Any]:
        bucket = self.dense_buckets[stage.bucket_id]
        codec = self.config.codec_obj
        be = self.config.backend_obj
        shard_elems = self.zero1_shard_elems(stage, p)
        buf = self.pack_bucket(bucket, leaves)
        if codec.linear:
            if codec.stateful:
                # e.g. bf16+ef: the compensated wire still sums in flight
                buf, scale, bstate = codec.encode_stateful(
                    buf, bstate, use_kernel=self.config.use_kernel)
                if scale is not None:
                    raise ValueError(
                        f"linear codec {codec.name!r} returned side "
                        f"scales; scales cannot be reduce-scattered")
            pad = shard_elems * p - bucket.n_elems
            if pad:
                buf = jnp.pad(buf, (0, pad))
            shard = be.reduce_scatter(buf, axes) if axes else buf
            return shard.astype(jnp.float32), bstate
        # non-linear: decode-sum the full bucket, then slice own shard
        wire, scale, bstate = codec.encode_stateful(
            buf, bstate, use_kernel=self.config.use_kernel)
        if not axes:
            red = codec.decode(wire, scale, jnp.float32)
        else:
            red = codecs.sum_decoded(codec, be.all_gather(wire, axes),
                                     be.all_gather(scale, axes), p,
                                     jnp.float32)
        pad = shard_elems * p - bucket.n_elems
        if pad:
            red = jnp.pad(red, (0, pad))
        if not axes:
            return red, bstate            # p == 1: the shard IS the bucket
        start = self._flat_worker_index(axes) * shard_elems
        return jax.lax.dynamic_slice_in_dim(red, start, shard_elems), \
            bstate

    def zero1_allgather_params(self, stage: BucketStage,
                               shard: jax.Array, out: List[Any],
                               axes: Tuple[str, ...], p: int) -> None:
        """Broadcast one dense stage's UPDATED param shard to every
        worker through the (stateless) param codec — the ZeRO-1 half
        that replaces the grads' trailing allgather — and unpack the
        reassembled bucket into ``out``'s param leaves.  Quantised
        param wires decode each worker's chunk against that worker's
        own absmax scale, exactly like the sparse gather path."""
        bucket = self.dense_buckets[stage.bucket_id]
        pc = self.config.param_codec_obj
        be = self.config.backend_obj
        shard_elems = shard.shape[0]
        # the param half bills to the SAME stage name as the grad half,
        # so a stage's recorded wire totals its RS + param-AG schedule
        name = self.stage_name(stage)
        with jax.named_scope(name), _telemetry.stage_scope(name):
            with jax.named_scope("quantize"):
                wire, scale = pc.encode(shard.astype(jnp.float32),
                                        use_kernel=self.config.use_kernel)
            if not axes:
                buf = pc.decode(wire, scale, jnp.float32)
            elif pc.linear:
                buf = pc.decode(be.all_gather(wire, axes), None,
                                jnp.float32)
            else:
                g_wire = be.all_gather(wire, axes)
                g_scale = be.all_gather(scale, axes)
                per = g_wire.astype(jnp.float32).reshape(p, shard_elems)
                per = per * g_scale.astype(jnp.float32).reshape(p, 1)
                buf = per.reshape(-1)
            self.unpack_bucket(bucket, buf[:bucket.n_elems], out, None)


# ---------------------------------------------------------------------------
# Compilation + cache
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[Any, ExchangePlan] = {}
_PLAN_CACHE_MAX = 256      # specs include sparse row counts, which vary
_CACHE_STATS = {"hits": 0, "misses": 0}

_FINGERPRINT_VERSION = "fp1"


def tree_fingerprint(treedef, contrib_specs, exact: bool = True) -> str:
    """Stable hex digest of a gradient-tree structure: treedef + every
    contribution's shape/dtype specs.  Deterministic across process
    restarts (sha256 of the canonical repr — NOT Python's salted
    ``hash``), so it can key on-disk artifacts; equal-but-reconstructed
    treedefs digest identically, so it also keys the in-process plan
    cache without aliasing distinct structures.

    ``exact=False`` elides sparse row counts (which scale with the
    microbatch token count): the STRUCTURAL fingerprint the tuning
    artifact is keyed by, so one tuned config covers every batch size
    of the same model.  The plan cache always uses ``exact=True`` —
    plans bill wire bytes per row and must not alias."""
    if not exact:
        contrib_specs = tuple(
            tuple(dataclasses.replace(c, rows=0)
                  if isinstance(c, SparseSpec) else c for c in contribs)
            for contribs in contrib_specs)
    payload = repr((_FINGERPRINT_VERSION, exact, str(treedef),
                    contrib_specs))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def fingerprint(grads, exact: bool = True) -> str:
    """``tree_fingerprint`` of a gradient tree (concrete arrays,
    tracers, or ShapeDtypeStructs — only structure matters)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads, is_leaf=_is_leaf)
    return tree_fingerprint(treedef, _contrib_specs(leaves), exact=exact)


def _contrib_specs(leaves) -> Tuple[Tuple[LeafSpec, ...], ...]:
    return tuple(
        tuple(contribution_spec(c)
              for c in (leaf if isinstance(leaf, list) else [leaf]))
        for leaf in leaves)


def _build_plan(treedef, contrib_specs: Tuple[Tuple[LeafSpec, ...], ...],
                config: ExchangeConfig,
                leaf_blocks: Optional[Tuple[str, ...]] = None
                ) -> ExchangePlan:
    leaf_specs = tuple(classify(c, config) for c in contrib_specs)
    if leaf_blocks is None:
        leaf_blocks = ("",) * len(leaf_specs)
    dense_ids = tuple(i for i, s in enumerate(leaf_specs)
                      if isinstance(s, DenseSpec))
    gather_ids = tuple(i for i, s in enumerate(leaf_specs)
                       if isinstance(s, SparseSpec))

    # bucket dense leaves with the Horovod fusion planner, one group per
    # wire dtype (so packed buffers never promote and byte accounting is
    # exact); thresholds are measured in WIRE bytes so bf16 wires pack
    # twice — and int8 wires four times — the elements per bucket.
    # Under overlap="backward" the partition is additionally snapped to
    # model-block boundaries (one group per (block, wire dtype)): a
    # bucket crossing blocks could not launch until BOTH blocks'
    # cotangents were emitted, which defeats wait-free launch and would
    # split codec state across custom_vjp boundaries.
    codec = config.codec_obj
    groups: Dict[Tuple[str, str], List[int]] = {}
    for i in dense_ids:
        dt = codec.wire_dtype(leaf_specs[i].dtype)
        block = leaf_blocks[i] if config.overlap_backward else ""
        groups.setdefault((block, dt), []).append(i)
    threshold = (config.fusion_threshold
                 if config.fusion_threshold is not None else 0)
    dense_ids = tuple(i for ids in groups.values() for i in ids)
    buckets = []
    base = 0
    for (_, dt), ids in groups.items():
        structs = [jax.ShapeDtypeStruct(leaf_specs[i].shape, dt)
                   for i in ids]
        fplan = fusion.plan_fusion(structs, threshold_bytes=threshold)
        for bucket in fplan.buckets:
            slots = tuple(dataclasses.replace(s, leaf_idx=s.leaf_idx + base)
                          for s in bucket)
            buckets.append(DenseBucket(
                slots=slots, collective=config.dense_collective,
                n_elems=sum(s.size for s in slots), wire_dtype=dt))
        base += len(ids)
    buckets = tuple(buckets)

    # compile the BucketSchedule: one stage per bucket, each carrying
    # its readiness key (the leaf set it consumes) and its TRIGGER (the
    # block whose backward emission completes that leaf set).  Launch
    # order is reverse-layer — backward emits leaves in reverse flatten
    # order, so the stage with the LARGEST minimum leaf id is ready
    # first and its collective can be in flight while earlier-layer
    # stages are still accumulating.
    stages = []
    for bi, b in enumerate(buckets):
        ids = tuple(dense_ids[s.leaf_idx] for s in b.slots)
        stages.append(BucketStage(
            kind="dense", bucket_id=bi, leaf_ids=ids,
            trigger=leaf_blocks[min(ids)]))
    for gi in gather_ids:
        stages.append(BucketStage(kind="gather", bucket_id=gi,
                                  leaf_ids=(gi,),
                                  trigger=leaf_blocks[gi]))
    stages.sort(key=lambda s: -s.ready_key)
    schedule = BucketSchedule(stages=tuple(stages))

    return ExchangePlan(treedef=treedef, contrib_specs=contrib_specs,
                        leaf_specs=leaf_specs, dense_leaf_ids=dense_ids,
                        dense_buckets=buckets, gather_leaf_ids=gather_ids,
                        config=config, schedule=schedule,
                        leaf_blocks=leaf_blocks)


def _path_block(path) -> str:
    """Top-level block label of one key path: the first dict key /
    sequence index / attribute name on the way to the leaf."""
    if not path:
        return ""
    k = path[0]
    for attr in ("key", "idx", "name"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def leaf_block_labels(grads) -> Tuple[str, ...]:
    """Per-leaf top-level block labels (flatten order, contribution
    lists as single leaves) — the block partition wait-free backprop
    snaps its buckets to."""
    flat, _ = jax.tree_util.tree_flatten_with_path(grads, is_leaf=_is_leaf)
    return tuple(_path_block(path) for path, _ in flat)


def compile_plan(grads, config: ExchangeConfig) -> ExchangePlan:
    """Compile (or fetch from cache) the ExchangePlan for a gradient
    tree.  Works on concrete arrays, tracers, and ShapeDtypeStructs —
    only treedef + shapes/dtypes matter."""
    leaves, treedef = jax.tree_util.tree_flatten(grads, is_leaf=_is_leaf)
    contrib_specs = _contrib_specs(leaves)
    # keyed on the stable structural digest, not the treedef object:
    # equal-but-reconstructed treedefs (a fresh dict of the same params
    # every step) hit the same entry
    key = (tree_fingerprint(treedef, contrib_specs), config)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _CACHE_STATS["hits"] += 1
        return cached
    _CACHE_STATS["misses"] += 1
    plan = _build_plan(treedef, contrib_specs, config,
                       leaf_blocks=leaf_block_labels(grads))
    if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:       # FIFO bound: variable
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))  # token counts would
    _PLAN_CACHE[key] = plan                       # otherwise grow forever
    return plan


def plan_cache_info() -> Dict[str, int]:
    return dict(_CACHE_STATS, size=len(_PLAN_CACHE))


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0
