"""Chunked, overlap-scheduled ZeRO-3 collectives.

The monolithic stage-3 data path relies on GSPMD alone: the fused step's
layer ``lax.scan`` carries the whole stacked parameter tree, so XLA emits
one whole-model param all-gather ahead of the forward and one whole-model
grad reduce-scatter behind the backward — both serialize against compute
(the comm term PR 5's roofline isolates). This module decomposes those
collectives into layer-bucket *chunks* and orders the HLO so XLA's
latency-hiding scheduler can pipeline them against adjacent-chunk compute
(T3, arXiv:2401.16677; "The Big Send-off", arXiv:2504.18658):

* **Bucketing** — layers are grouped into byte-bounded chunks
  (``zero_optimization.overlap_bucket_bytes``; 0 = one layer per chunk).
* **Forward** — chunk *k+d*'s param all-gather (a sharding-constraint
  reshard to the spec with the DP axes removed — GSPMD emits the actual
  all-gather) is issued while chunk *k* computes. An
  ``optimization_barrier`` ties chunk *k+d*'s *sharded* slice to chunk
  *k*'s input activation, so XLA can neither hoist every gather to step
  start (which would materialize the whole gathered model and blow the
  HBM budget) nor sink them behind the compute they must hide under.
  ``d`` is ``zero_optimization.overlap_prefetch``.
* **Backward** — a ``custom_vjp`` around the per-chunk gather constrains
  each chunk's cotangent to the sharded grad spec *inside* the backward,
  so chunk *k*'s grad reduce-scatter is emitted while chunk *k-1*'s
  backward compute runs, instead of one fused whole-model scatter at the
  end.
* **Lifetime** — the gather sits inside a ``jax.checkpoint`` whose policy
  saves everything *except* the gathered chunk
  (``save_anything_except_these_names``), so gathered weights are never
  held as residuals from forward to backward: the backward re-gathers,
  and at most ``prefetch+1`` gathered chunks are live at any instant.
  :meth:`OverlapPlan.transient_bytes` reports that footprint to the
  static HBM budget (telemetry/explain.py) so the budget check stays
  honest. ``zero_optimization.overlap_regather=false`` flips the
  trade: gathered chunks are kept as residuals and reused by the
  backward (reference ``stage3_max_reuse_distance`` semantics) —
  gather traffic halves, but the whole gathered stack is live at the
  forward→backward turnaround, and the budget accounts for it.

Composition fences are checked where the information lives: the model
factory requires stage 3 + a decoder model; :func:`build_overlap_plan`
(mesh in hand) additionally rejects expert parallelism (the 'expert'
mesh axis doubles as an FSDP axis on dense weights but is the EP shard
axis on expert weights — stripping it indiscriminately would replicate
experts).
"""

import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import ZERO_AXES
from deepspeed_tpu.utils.logging import logger, warning_once

Pytree = Any

#: residual name for gathered chunks — the checkpoint policy excludes it
#: so backward re-gathers instead of holding gathered weights across the
#: forward→backward gap
GATHERED_NAME = "zero3_gathered_chunk"

@jax.custom_vjp
def _opt_barrier(tup):
    """Differentiable ``lax.optimization_barrier`` (jax 0.4.x defines no
    VJP for the primitive). The backward barriers the cotangents too,
    which is exactly what the overlap schedule wants: tying chunk k+1's
    param cotangent to chunk k's activation cotangent keeps the backward
    chunk order pinned the same way the forward is."""
    return lax.optimization_barrier(tup)


def _opt_barrier_fwd(tup):
    return lax.optimization_barrier(tup), None


def _opt_barrier_bwd(_, ct):
    return (lax.optimization_barrier(ct),)


_opt_barrier.defvjp(_opt_barrier_fwd, _opt_barrier_bwd)


#: XLA scheduler flags that let the compiler interleave the per-chunk
#: collectives with compute (TPU backends; harmless no-ops elsewhere).
#: Probed before use — never assumed (conftest ``_flags_ok`` pattern).
LATENCY_HIDING_FLAGS: Tuple[str, ...] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
)


# ---------------------------------------------------------------------------
# spec surgery
# ---------------------------------------------------------------------------

def dense_spec(spec: P, dp_axes: Sequence[str] = ZERO_AXES) -> P:
    """The gathered-for-compute layout: ``spec`` with every DP-family
    axis removed (what the leaf would look like under stage < 3 with the
    same TP layout). ``P(None, ('data','data_inner','expert'), 'model')``
    → ``P(None, None, 'model')``."""
    entries = []
    for e in spec:
        if e is None:
            entries.append(None)
            continue
        cur = tuple(e) if isinstance(e, (tuple, list)) else (e,)
        kept = tuple(a for a in cur if a not in dp_axes)
        entries.append(None if not kept else
                       (kept if len(kept) > 1 else kept[0]))
    return P(*entries)


def _spec_axes(spec: P) -> Tuple[str, ...]:
    axes: List[str] = []
    for e in spec:
        if e is None:
            continue
        axes.extend(e if isinstance(e, (tuple, list)) else (e,))
    return tuple(axes)


def _leaf_bytes_per_layer(leaf) -> int:
    """Global bytes of ONE layer of a stacked ``[L, ...]`` leaf."""
    shape = tuple(leaf.shape)[1:]
    return int(np.prod(shape, dtype=np.int64) *
               np.dtype(leaf.dtype).itemsize) if shape else \
        int(np.dtype(leaf.dtype).itemsize)


def chunk_bounds(num_layers: int, per_layer_bytes: int,
                 bucket_bytes: int) -> List[Tuple[int, int]]:
    """Greedy layer bucketing: consecutive layers accumulate into one
    chunk until adding the next would exceed ``bucket_bytes`` (always at
    least one layer per chunk). ``bucket_bytes=0`` → one chunk per layer
    (the default: matches the reference's per-module fetch granularity
    and gives the scheduler the most interleaving freedom)."""
    if num_layers <= 0:
        return []
    if bucket_bytes <= 0 or per_layer_bytes <= 0:
        return [(i, i + 1) for i in range(num_layers)]
    layers_per = max(1, bucket_bytes // per_layer_bytes)
    return [(lo, min(lo + layers_per, num_layers))
            for lo in range(0, num_layers, layers_per)]


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

class OverlapPlan:
    """Chunk schedule + shardings for one (model, mesh, knobs) triple.

    ``layer_specs``: PartitionSpec pytree of the stacked ``layers``
    subtree (leading layer dim unsharded). ``abstract_layers``: matching
    ShapeDtypeStructs ``[L, ...]`` in the engine's compute dtype."""

    def __init__(self, mesh: Mesh, layer_specs: Pytree,
                 abstract_layers: Pytree, bucket_bytes: int = 0,
                 prefetch: int = 1, regather: bool = True,
                 dp_axes: Sequence[str] = ZERO_AXES):
        self.mesh = mesh
        self.dp_axes = tuple(dp_axes)
        self.prefetch = max(0, int(prefetch))
        self.regather = bool(regather)
        self.layer_specs = layer_specs
        is_p = lambda x: isinstance(x, P)          # noqa: E731
        self.gather_specs = jax.tree.map(
            lambda s: dense_spec(s, self.dp_axes), layer_specs,
            is_leaf=is_p)
        self._gather_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.gather_specs,
            is_leaf=is_p)
        self._shard_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), layer_specs, is_leaf=is_p)
        leaves = jax.tree.leaves(abstract_layers)
        self.num_layers = int(leaves[0].shape[0]) if leaves else 0
        self.per_layer_bytes = sum(_leaf_bytes_per_layer(x) for x in leaves)
        # per-device gathered bytes of one layer: each leaf divided by the
        # mesh extent of the axes its gathered spec STILL uses (TP stays
        # sharded; only the DP shard is materialized by the gather)
        gspecs = jax.tree.leaves(self.gather_specs, is_leaf=is_p)
        per_dev = 0.0
        for leaf, gs in zip(leaves, gspecs):
            denom = 1
            for a in _spec_axes(gs):
                denom *= mesh.shape.get(a, 1)
            per_dev += _leaf_bytes_per_layer(leaf) / max(1, denom)
        self.per_layer_gathered_device_bytes = per_dev
        self.bucket_bytes = int(bucket_bytes)
        self.bounds = chunk_bounds(self.num_layers, self.per_layer_bytes,
                                   self.bucket_bytes)
        self._stream = self._make_stream()

    # ------------------------------------------------------------ accounting

    @property
    def n_chunks(self) -> int:
        return len(self.bounds)

    def chunk_layers(self, k: int) -> int:
        lo, hi = self.bounds[k]
        return hi - lo

    def chunk_global_bytes(self, k: int) -> int:
        return self.chunk_layers(k) * self.per_layer_bytes

    def max_chunk_bytes(self) -> int:
        return max((self.chunk_global_bytes(k)
                    for k in range(self.n_chunks)), default=0)

    def transient_bytes(self) -> float:
        """Per-device HBM transiently held by gathered chunks. With
        ``regather`` (default): the worst sliding window of
        ``prefetch+1`` consecutive chunks (the chunk in use plus the
        ones in flight). Without: every gathered chunk survives as a
        backward residual, so the whole gathered stack is live at the
        forward→backward turnaround. Either way this is what the static
        HBM budget must add on top of the sharded resident params."""
        if not self.bounds:
            return 0.0
        if not self.regather:
            return self.num_layers * self.per_layer_gathered_device_bytes
        w = min(self.prefetch + 1, self.n_chunks)
        worst = 0
        for k in range(self.n_chunks - w + 1):
            worst = max(worst, sum(self.chunk_layers(j)
                                   for j in range(k, k + w)))
        return worst * self.per_layer_gathered_device_bytes

    def describe(self) -> str:
        return (f"zero-3 overlap: {self.n_chunks} chunk(s) over "
                f"{self.num_layers} layers (bucket "
                f"{self.bucket_bytes or 'per-layer'}, prefetch "
                f"{self.prefetch}, "
                f"{'re-gather' if self.regather else 'reuse'} backward), "
                f"~{self.max_chunk_bytes() / 2**20:.1f} "
                f"MiB/chunk global, transient "
                f"{self.transient_bytes() / 2**20:.1f} MiB/device gathered")

    def publish_static_gauges(self) -> None:
        """Static ``overlap/*`` gauges (the measured fraction gauge is
        published per step by the engine)."""
        from deepspeed_tpu.telemetry import registry
        registry.gauge("overlap/chunks",
                       help="ZeRO-3 overlap chunk count").set(self.n_chunks)
        registry.gauge("overlap/prefetch_depth",
                       help="chunks gathered ahead of compute").set(
            self.prefetch)
        registry.gauge("overlap/bucket_bytes",
                       help="largest chunk, global param bytes").set(
            self.max_chunk_bytes())
        registry.gauge(
            "overlap/transient_hbm_bytes",
            help="per-device HBM held by in-flight gathered chunks").set(
            self.transient_bytes())

    # ----------------------------------------------------------- the stream

    def _make_stream(self) -> Callable[[Pytree], Pytree]:
        """Per-chunk gather with an explicit reduce-scatter on the way
        back. Forward: reshard the sharded chunk slice to the DP-free
        spec (GSPMD emits the all-gather). Backward: constrain the
        cotangent to the sharded spec *at this point of the backward* —
        GSPMD fuses the cross-replica sum with the reshard into a
        reduce-scatter, interleaved with the neighbouring chunk's
        backward compute instead of coalesced at the step's end."""
        gather_sh, shard_sh = self._gather_sh, self._shard_sh

        def _constrain(tree: Pytree, sh: Pytree) -> Pytree:
            # shardings were built over full stacked leaves; chunk slices
            # only differ in the (unsharded) leading dim, so they apply
            # to every chunk length unchanged
            return jax.tree.map(
                lax.with_sharding_constraint, tree, sh)

        @jax.custom_vjp
        def stream(chunk):
            return _constrain(chunk, gather_sh)

        def stream_fwd(chunk):
            return _constrain(chunk, gather_sh), None

        def stream_bwd(_, ct):
            return (_constrain(ct, shard_sh),)

        stream.defvjp(stream_fwd, stream_bwd)
        return stream

    # -------------------------------------------------------- the layer loop

    def layer_loop(self, body: Callable, x: jax.Array, xs: Pytree
                   ) -> Tuple[jax.Array, jax.Array]:
        """Drop-in for ``lax.scan(body, x, xs)`` over the stacked layers
        (``xs`` is the layers pytree, or ``(layers, per_layer_extras)``
        when the model scans auxiliary per-layer data alongside — e.g.
        GPT-Neo's attention windows)."""
        layers, extra = (xs if isinstance(xs, tuple) else (xs, None))
        n, d = self.n_chunks, self.prefetch
        if n <= 0:
            return lax.scan(body, x, xs)

        def slice_tree(tree, k):
            lo, hi = self.bounds[k]
            return jax.tree.map(lambda a: a[lo:hi], tree)

        self._record_trace_comms()

        policy = getattr(jax.checkpoint_policies,
                         "save_anything_except_these_names", None)

        def chunk_fn(x, chunk, extra_chunk):
            g = self._stream(chunk)
            g = jax.tree.map(
                lambda a: checkpoint_name(a, GATHERED_NAME), g)
            cxs = (g, extra_chunk) if extra_chunk is not None else g
            return lax.scan(body, x, cxs)

        if self.regather and policy is not None:
            # everything else stays saveable (per-layer remat, if any, is
            # already applied inside ``body``); only the gathered chunk is
            # recomputed — i.e. re-gathered — during backward
            chunk_fn = jax.checkpoint(
                chunk_fn, policy=policy(GATHERED_NAME),
                static_argnums=())
        elif self.regather:                          # pragma: no cover
            warning_once(
                "jax.checkpoint_policies.save_anything_except_these_names "
                "unavailable — gathered ZeRO-3 chunks will be held as "
                "backward residuals (higher transient HBM than reported); "
                "set overlap_regather=False to make the budget match")
        # not self.regather: gathered chunks are KEPT as residuals — the
        # backward reuses them (reference stage3_max_reuse_distance>0
        # semantics): gather traffic halves, transient_bytes() reports
        # the full gathered stack instead of the prefetch window

        window: List[Pytree] = []
        pending: List[int] = []
        for k in range(min(d + 1, n)):
            window.append(slice_tree(layers, k))
            pending.append(k)
        aux_parts: List[Pytree] = []
        for k in range(n):
            chunk = window.pop(0)
            pending.pop(0)
            ek = slice_tree(extra, k) if extra is not None else None
            x, aux = chunk_fn(x, chunk, ek)
            aux_parts.append(jax.tree.map(jnp.atleast_1d, aux))
            nxt = k + d + 1
            if nxt < n:
                # tie the NEXT prefetch slice to the activation just
                # produced: its gather can't issue before chunk k is
                # done, bounding live gathered chunks to prefetch+1
                nchunk, x = _opt_barrier((slice_tree(layers, nxt), x))
                window.append(nchunk)
                pending.append(nxt)
        # aux may be a pytree (health taps' per-layer stats dict), so
        # concatenate leaf-wise along the stacked layer axis
        return x, jax.tree.map(
            lambda *parts: jnp.concatenate(parts), *aux_parts)

    def _record_trace_comms(self) -> None:
        """Trace-time comm accounting for the chunked collectives: the
        per-chunk all-gathers (forward) and reduce-scatters (backward)
        this loop will emit, coalesced by (op, size) so the tracer ring
        sees a handful of markers per traced step instead of 2×chunks
        (comms_logger.append_chunked keeps byte totals exact)."""
        from deepspeed_tpu.comm.comms_logger import comms_logger
        if not comms_logger.enabled:
            return
        sizes: Dict[int, int] = {}
        for k in range(self.n_chunks):
            b = self.chunk_global_bytes(k)
            sizes[b] = sizes.get(b, 0) + 1
        axis = tuple(a for a in self.dp_axes
                     if self.mesh.shape.get(a, 1) > 1) or self.dp_axes
        for size, count in sorted(sizes.items()):
            comms_logger.append_chunked("all_gather", size, axis,
                                        chunks=count)
            comms_logger.append_chunked("reduce_scatter", size, axis,
                                        chunks=count)


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def build_overlap_plan(mesh: Mesh, layer_specs: Pytree,
                       abstract_layers: Pytree, zero_config,
                       num_experts: int = 0) -> Optional["OverlapPlan"]:
    """Validated construction from the config knobs; returns ``None``
    (with a loud warning) for meshes the chunked path cannot serve yet.
    Raises only on contradictory explicit configuration."""
    ep = mesh.shape.get("expert", 1)
    if num_experts and ep > 1:
        warning_once(
            "zero_optimization.overlap_comm: expert parallelism "
            f"(expert axis={ep}) is not supported by the chunked overlap "
            "path — the 'expert' axis shards experts, not FSDP, on MoE "
            "weights; falling back to the monolithic ZeRO-3 collectives")
        return None
    if mesh.shape.get("pipe", 1) > 1:
        warning_once(
            "zero_optimization.overlap_comm: pipeline meshes run the "
            "pipe schedule, not the chunked overlap loop; ignoring")
        return None
    prefetch = int(getattr(zero_config, "overlap_prefetch", 1))
    bucket = int(getattr(zero_config, "overlap_bucket_bytes", 0) or 0)
    regather = bool(getattr(zero_config, "overlap_regather", True))
    plan = OverlapPlan(mesh, layer_specs, abstract_layers,
                       bucket_bytes=bucket, prefetch=prefetch,
                       regather=regather)
    if plan.n_chunks <= 1:
        logger.info(
            "zero-3 overlap: bucket covers the whole model (1 chunk) — "
            "schedule degenerates to the monolithic gather; shrink "
            "overlap_bucket_bytes to pipeline collectives")
    return plan


# ---------------------------------------------------------------------------
# scheduler flags
# ---------------------------------------------------------------------------

def _flag_keys(flags: str) -> set:
    """Flag NAMES present in an ``XLA_FLAGS`` string — exact tokens, not
    substrings (``..._async_collective_fusion`` is a prefix of
    ``..._fusion_fuse_all_gather``; substring matching would report the
    former present whenever the latter is)."""
    return {tok.split("=")[0] for tok in flags.split()}


def scheduler_flag_status(env: Optional[Dict[str, str]] = None
                          ) -> Dict[str, bool]:
    """Which latency-hiding flags are present in ``XLA_FLAGS``."""
    flags = (env if env is not None else os.environ).get("XLA_FLAGS", "")
    keys = _flag_keys(flags)
    return {f: f.split("=")[0] in keys for f in LATENCY_HIDING_FLAGS}


def ensure_scheduler_flags(probe: Optional[Callable[[str], bool]] = None,
                           env: Optional[Dict[str, str]] = None) -> str:
    """Append the latency-hiding scheduler flags to ``XLA_FLAGS`` —
    BEFORE backend init only (XLA reads the env once). Each candidate is
    validated through ``probe`` (the conftest ``_flags_ok`` subprocess
    pattern: a flag this jaxlib doesn't know would CHECK-abort the
    process) and silently dropped when rejected. Returns the resulting
    flag string; ``env`` defaults to ``os.environ`` and is mutated."""
    env = os.environ if env is None else env
    flags = env.get("XLA_FLAGS", "")
    for f in LATENCY_HIDING_FLAGS:
        if f.split("=")[0] in _flag_keys(flags):
            continue
        cand = (flags + " " + f).strip()
        if probe is None or probe(cand):
            flags = cand
    env["XLA_FLAGS"] = flags
    return flags


def verify_scheduler_flags() -> None:
    """Engine-side report (no mutation — the backend is already up by
    engine init): on TPU, warn when the latency-hiding scheduler flags
    are absent from the environment; elsewhere this is informational
    (the CPU thunk runtime has no latency-hiding scheduler — the
    dp-mesh CPU tests validate ordering/numerics, not wall clock)."""
    status = scheduler_flag_status()
    missing = [f for f, ok in status.items() if not ok]
    try:
        backend = jax.default_backend()
    except Exception:                                 # pragma: no cover
        backend = "unknown"
    if backend == "tpu" and missing:
        logger.warning(
            "zero-3 overlap: latency-hiding scheduler flags missing from "
            f"XLA_FLAGS ({' '.join(missing)}) — the per-chunk collectives "
            "will be emitted in overlap order but the scheduler may not "
            "interleave them; export them before process start "
            "(overlap.ensure_scheduler_flags)")
    elif missing:
        logger.debug("zero-3 overlap: scheduler flags not set "
                     f"(backend={backend}; only meaningful on TPU)")
