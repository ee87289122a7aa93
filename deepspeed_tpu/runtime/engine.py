"""The deepspeed_tpu training engine.

TPU-native re-design of the reference's ``DeepSpeedEngine``
(runtime/engine.py:206) and ``deepspeed.initialize``
(deepspeed/__init__.py:78). The reference wraps a torch module and drives
training through gradient hooks, flat fp16 partitions, and a hand-built
collective schedule. Here the engine owns:

- a **functional model spec** (init/loss pair over a params pytree),
- a **ZeRO sharding plan** (runtime/zero/sharding.py) mapping stage 0–3 to
  param/grad/optimizer-state shardings over the mesh,
- **one jitted train step** — forward, backward, (fp16 unscale/overflow),
  global-norm clip, optimizer update, LR schedule — donated in-place; XLA
  emits the reduce-scatter / allgather pattern of the corresponding ZeRO
  stage from the sharding annotations alone,
- GAS accounting (`forward`/`backward`/`step` parity API plus the fused
  `train_batch` fast path with a `lax.scan` over microbatches),
- checkpointing, monitoring, throughput timing.

API parity map (reference runtime/engine.py):
  forward:2222  backward:2478  step:2653  train_batch (pipe engine:337)
  save_checkpoint:3621  load_checkpoint:3273
"""

import os
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm, telemetry
from deepspeed_tpu.config import DeepSpeedTPUConfig
from deepspeed_tpu.ops.optimizers import Optimizer, build_optimizer
from deepspeed_tpu.parallel.mesh import (ZERO_AXES, build_mesh,
                                         get_data_parallel_world_size,
                                         has_mesh, get_mesh, mesh_from_config)
from deepspeed_tpu.runtime.loss_scaler import (LossScaleState, check_overflow,
                                               init_loss_scale, update_scale)
from deepspeed_tpu.runtime.lr_schedules import Schedule, build_schedule
from deepspeed_tpu.resilience.faults import fault_injector, record_recovery
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer

Pytree = Any
Batch = Dict[str, jax.Array]
#: loss_fn(params, batch, rng) -> loss | (loss, metrics-dict)
LossFn = Callable[[Pytree, Batch, jax.Array], Any]


def _sample_difficulty(sample) -> int:
    """Fallback curriculum difficulty = sequence length of the first sized
    leaf. ``len(sample)`` on a dict sample would count its KEYS — a constant
    that silently disables difficulty gating. 0-d array leaves (scalar ids
    etc.) are skipped: they pass ``hasattr(__len__)`` but ``len()`` raises."""
    for leaf in jax.tree.leaves(sample):
        if hasattr(leaf, "ndim"):          # numpy / jax array
            if leaf.ndim:
                return int(np.shape(leaf)[0])
            continue
        if hasattr(leaf, "__len__"):       # list / str sample
            return len(leaf)
    return 0


@dataclass
class ModelSpec:
    """Functional model contract consumed by the engine.

    The TPU answer to "pass a torch.nn.Module": parameters are an explicit
    pytree; ``loss_fn`` is pure; ``partition_specs`` carries the model's
    tensor-parallel/FSDP layout (the AutoTP + zero.Init analogue)."""
    init_fn: Callable[[jax.Array], Pytree]
    loss_fn: LossFn
    #: base PartitionSpec pytree (TP and, for stage 3, FSDP axes); None →
    #: fully replicated base
    partition_specs: Optional[Pytree] = None
    #: approximate FLOPs per token for MFU reporting (6*N for dense decoders)
    flops_per_token: Optional[float] = None
    #: tokens per sample (seq len) for throughput accounting
    tokens_per_sample: Optional[int] = None
    #: pipeline-parallel loss over STACKED microbatches [M, B, ...] —
    #: set by the factory when pipeline.stages > 1; the engine then runs
    #: the whole microbatch set in one call (reference PipelineEngine
    #: train_batch:337 — forward()/backward() are not supported, matching
    #: the reference's restriction)
    pipeline_loss_fn: Optional[Callable[[Pytree, Batch, jax.Array], Any]] = None
    #: 1F1B path: (params, batch, rng, scale) -> (loss, grads) — explicit
    #: per-microbatch backward (runtime/pipe 1F1B schedule); preferred over
    #: pipeline_loss_fn's autodiff GPipe when set
    pipeline_grad_fn: Optional[Callable[..., Any]] = None
    #: the DecoderConfig this spec was built from (set by model_factory);
    #: lets the hybrid engine spin up an inference engine over the same
    #: params (reference runtime/hybrid_engine.py)
    decoder_config: Optional[Any] = None
    #: ZeRO-3 chunked-overlap hook: (mesh, abstract_params) ->
    #: Optional[OverlapPlan]. Set by the factory when
    #: zero_optimization.overlap_comm is on; the engine calls it from the
    #: standard fused-step path once mesh + abstract params exist, and
    #: the factory arms loss_fn with the returned plan's layer_loop
    #: (runtime/zero/overlap.py)
    configure_overlap: Optional[Callable[..., Any]] = None


@dataclass
class _ParkedShards:
    """Host copy of a multi-host array's LOCAL shards (offload_states)."""
    shape: Tuple[int, ...]
    dtype: Any
    shards: Dict[Any, np.ndarray]


class DeepSpeedTPUEngine:
    """See module docstring. Construct via :func:`initialize`.

    Construction is timed by part, always on (``telemetry.setup_part``):
    the mesh and the sharding plan (``setup/mesh``), the parameters' init
    and placement (``setup/params``), the optimizer's state
    (``setup/optimizer_state``), the rest (``setup/engine``). Each times
    the HOST: a jitted init returns once it is enqueued."""

    @telemetry.setup_part("engine")
    def __init__(self,
                 model: ModelSpec,
                 config: DeepSpeedTPUConfig,
                 mesh: Optional[Mesh] = None,
                 params: Optional[Pytree] = None,
                 rng: Optional[jax.Array] = None,
                 training_data=None):
        comm.init_distributed()
        self.model = model
        self.config = config
        with telemetry.setup_part("mesh"):
            self.mesh = mesh or (get_mesh() if has_mesh()
                                 else mesh_from_config(config))
        self.dp_world_size = get_data_parallel_world_size(self.mesh)
        config.resolve_batch_sizes(self.dp_world_size)

        self.zero_stage = config.zero_optimization.stage
        self.fp16_enabled = config.fp16.enabled is True
        self.bf16_enabled = (config.bf16.enabled is True or
                             (not self.fp16_enabled and
                              config.compute_dtype == "bfloat16"))
        self.compute_dtype = {"float16": jnp.float16,
                              "bfloat16": jnp.bfloat16,
                              "float32": jnp.float32}[config.compute_dtype]

        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.global_samples = 0

        # sanity checks (reference engine.py:1123 is_sanity_checks_enabled:
        # NaN/Inf guards + cross-rank dataloader consistency :520). Two
        # modes: True/"debug" → global jax_debug_nans (raises at the op
        # that produced the NaN, but de-optimizes every jitted fn);
        # "scoped" → keep full-speed jit and run loss_scaler.global_check
        # over the step's pytrees instead, naming the first bad leaf
        # through telemetry/anomaly.py (costs one scalar sync per step).
        self._scoped_nan_check = config.check_nan_inf == "scoped"
        self._scoped_check_jit = None
        if config.check_nan_inf and not self._scoped_nan_check:
            jax.config.update("jax_debug_nans", True)
            log_dist("sanity checks on: jax_debug_nans enabled")
        elif self._scoped_nan_check:
            log_dist("sanity checks on: scoped per-leaf finite check")

        # -- optimizer & schedule ------------------------------------------
        self.offload_enabled = (
            config.zero_optimization.offload_optimizer.device.value
            in ("cpu", "nvme"))
        self.offload_overlap = False
        self._host_future = None
        self._zenflow = None
        self._param_stream = None
        if config.zero_optimization.zenflow is not None \
                and config.zero_optimization.offload_optimizer.device.value \
                != "cpu":
            # 'nvme' must be rejected too: NVMeOffloadOptimizer keeps
            # master/moments on disk (master=None), which the ZenFlow
            # selection/tail sweep cannot address.
            raise ValueError(
                "zenflow requires offload_optimizer.device='cpu' (the tail "
                "optimizer lives on the host — reference zenflow engine)")
        if config.zero_optimization.zenflow is not None and \
                config.zero_optimization.offload_param.device.value != "none":
            raise ValueError(
                "zenflow and offload_param are mutually exclusive "
                "streaming schedules; enable one")
        from deepspeed_tpu.ops.onebit import ONEBIT_NAMES
        self._onebit_enabled = config.optimizer.type.lower() \
            .replace("-", "").replace("_", "") in \
            tuple(n.replace("_", "") for n in ONEBIT_NAMES)
        if self._onebit_enabled:
            # the Optimizer object only contributes base_lr/hyperparams;
            # the 1-bit step path (ops/onebit.py) owns the update, so the
            # 1-bit-only knobs must not reach the adam factory
            _onebit_only = ("freeze_step", "max_coeff", "min_coeff",
                            "coeff_beta", "var_freeze_step",
                            "var_update_scaler", "local_step_scaler",
                            "local_step_clipper")
            opt_params = {k: v for k, v in
                          (config.optimizer.params or {}).items()
                          if k not in _onebit_only}
            self.optimizer, base_lr = build_optimizer("adamw", opt_params)
        else:
            self.optimizer, base_lr = build_optimizer(
                config.optimizer.type, config.optimizer.params)
        self.lr_schedule: Schedule = build_schedule(
            config.scheduler.type, config.scheduler.params, base_lr)

        # -- params (sharded at init — the zero.Init analogue) -------------
        rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
        self._init_params_and_state(params, rng)

        # -- loss scaling ---------------------------------------------------
        self.loss_scale_state = init_loss_scale(
            config.fp16.loss_scale, config.fp16.initial_scale_power,
            config.fp16.hysteresis) if self.fp16_enabled else \
            LossScaleState(jnp.float32(1.0), jnp.zeros((), jnp.int32),
                           jnp.zeros((), jnp.int32))
        self.loss_scale_state = self._replicate(self.loss_scale_state)
        self.dynamic_loss_scale = self.fp16_enabled and config.fp16.loss_scale == 0

        # -- jitted functions ----------------------------------------------
        self._build_step_functions()

        # -- grad accumulation buffers -------------------------------------
        self._acc_grads: Optional[Pytree] = None
        self._acc_count = 0
        self._pending_loss = None

        # -- aux ------------------------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=int(self.config.train_batch_size),
            steps_per_output=config.steps_per_print)
        self.monitor = self._build_monitor()
        self._monitor_pending = []
        self.training_dataloader = self._build_dataloader(training_data)
        self.lr_scheduler = self.lr_schedule   # parity name
        self._init_telemetry()

        log_dist(
            f"engine ready: zero_stage={self.zero_stage} dtype="
            f"{config.compute_dtype} dp={self.dp_world_size} "
            f"micro_batch={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps} "
            f"train_batch={config.train_batch_size}")

    # ------------------------------------------------------------------ init

    def _base_specs(self) -> Pytree:
        if self.model.partition_specs is not None:
            return self.model.partition_specs
        # fully replicated base layout matching the params structure
        return jax.tree.map(lambda p: P(*([None] * np.ndim(p))),
                            self._abstract_params)

    def _init_params_and_state(self, params: Optional[Pytree],
                               rng: jax.Array) -> None:
        dtype = self.compute_dtype

        def cast_init(r):
            p = self.model.init_fn(r)
            if dtype == jnp.float32:
                return p
            # cast the whole model to the compute dtype (reference
            # engine.py:_configure_distributed_model half conversion)
            return jax.tree.map(
                lambda x: x.astype(dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)

        with telemetry.setup_part("mesh"):
            self._abstract_params = jax.eval_shape(cast_init, rng)
            self.plan = ZeroShardingPlan(
                self.mesh, self.zero_stage, self._base_specs(),
                self._abstract_params)
        zcfg = self.config.zero_optimization
        self._zeropp_enabled = bool(zcfg.zero_quantized_weights or
                                    zcfg.zero_quantized_gradients)
        if self._zeropp_enabled:
            # ZeRO++ swaps in flat sharded storage + explicit quantized
            # collectives (runtime/zero/zeropp.py)
            from deepspeed_tpu.runtime.zero.zeropp import (init_zeropp_state,
                                                           validate_zeropp)
            validate_zeropp(self)
            init_zeropp_state(self, params, rng)
            return
        if self._onebit_enabled:
            # validate HERE so an offload/pipeline config errors instead
            # of silently taking the offload init path below
            from deepspeed_tpu.ops.onebit import validate_onebit
            validate_onebit(self)
        param_sh = self.plan.param_shardings()
        with telemetry.setup_part("params"):
            if params is None:
                init_jit = jax.jit(cast_init, out_shardings=param_sh)
                self.params = init_jit(rng)
            else:
                self.params = jax.device_put(
                    jax.tree.map(
                        lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) and
                        dtype != jnp.float32 else x, params), param_sh)
        self._param_shardings = param_sh
        if self.offload_enabled:
            # ZeRO-Offload: optimizer state in host DRAM; ZeRO-Infinity:
            # on NVMe via the windowed aio sweep (runtime/zero/infinity.py)
            off_cfg = self.config.zero_optimization.offload_optimizer
            param_tier = self.config.zero_optimization.offload_param \
                .device.value
            if param_tier != "none" and off_cfg.device.value == "cpu" \
                    and not off_cfg.superoffload:
                # the param tier stores master/params/grads in ONE
                # file-backed tier; 'cpu' maps it onto /dev/shm (DRAM)
                import dataclasses as _dc
                from deepspeed_tpu.config.config import OffloadDeviceEnum
                off_cfg = off_cfg.model_copy(update={
                    "device": OffloadDeviceEnum.nvme,
                    "nvme_path": off_cfg.nvme_path or
                    f"/dev/shm/dstpu_tier_{os.getpid()}"})
            if off_cfg.device.value == "nvme":
                from deepspeed_tpu.runtime.zero.infinity import (
                    DEFAULT_WINDOW, NVMeOffloadOptimizer)
                if not off_cfg.nvme_path:
                    raise ValueError("offload_optimizer.device='nvme' "
                                     "requires nvme_path")
                self.host_optimizer = NVMeOffloadOptimizer(
                    self._abstract_params, self.config.optimizer.type,
                    self.config.optimizer.params, dtype,
                    nvme_path=off_cfg.nvme_path,
                    window=off_cfg.buffer_size or DEFAULT_WINDOW,
                    aio_threads=off_cfg.buffer_count)
            elif off_cfg.superoffload:
                from deepspeed_tpu.runtime.zero.superoffload import (
                    SuperOffloadOptimizer)
                self.host_optimizer = SuperOffloadOptimizer(
                    self._abstract_params, self.config.optimizer.type,
                    self.config.optimizer.params, dtype,
                    bucket_size=off_cfg.buffer_size or (1 << 22))
            else:
                from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
                self.host_optimizer = HostOffloadOptimizer(
                    self._abstract_params, self.config.optimizer.type,
                    self.config.optimizer.params, dtype)
            self.host_optimizer.init_from(self.params)
            self.opt_state = {}
            self._state_shardings = {}
            self._param_stream = None
            if param_tier != "none":
                from deepspeed_tpu.runtime.zero.param_stream import (
                    ParamStreamCoordinator)
                self._param_stream = ParamStreamCoordinator(self)
            return
        self.host_optimizer = None
        if self._onebit_enabled:
            from deepspeed_tpu.ops.onebit import (init_onebit_state,
                                                  validate_onebit)
            validate_onebit(self)
            init_onebit_state(self)
            return
        with telemetry.setup_part("optimizer_state"):
            abstract_state = jax.eval_shape(self.optimizer.init, self.params)
            state_sh = self.plan.opt_state_shardings(abstract_state)
            self.opt_state = jax.jit(self.optimizer.init,
                                     out_shardings=state_sh)(self.params)
        self._state_shardings = state_sh

    # ------------------------------------------------------------- jit build

    def _replicate(self, tree: Pytree) -> Pytree:
        """Commit a small state pytree to the mesh, replicated — the layout
        the step programs hand it back in. A first call with uncommitted
        leaves has a different jit cache key than every later call, and
        traces (and compiles) the whole step a second time."""
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _batch_sharding(self, batch_like) -> Pytree:
        """Shard batch dim over DP axes (and seq dim over 'seq' if SP>1)."""
        sp = self.mesh.shape["seq"] > 1

        def spec_for(x):
            nd = np.ndim(x)
            if nd == 0:
                return NamedSharding(self.mesh, P())
            entries = [ZERO_AXES] + [None] * (nd - 1)
            if sp and nd >= 2:
                entries[1] = "seq"
            return NamedSharding(self.mesh, P(*entries))
        return jax.tree.map(spec_for, batch_like)

    def _compute_loss_and_grads(self, params, batch, scale, rng):
        def scaled_loss(p):
            out = self.model.loss_fn(p, batch, rng)
            loss, metrics = (out if isinstance(out, tuple) else (out, {}))
            return loss * scale, (loss, metrics)
        grads, (loss, metrics) = jax.grad(scaled_loss, has_aux=True)(params)
        grads = jax.lax.with_sharding_constraint(
            grads, self.plan.grad_shardings())
        return loss, metrics, grads

    def _apply_update(self, params, opt_state, scaler, grads, step, gas,
                      fwd_metrics=None):
        # named scopes (telemetry/explain.SCOPE_VOCABULARY): the unscale,
        # the global norm and the clip are ``grad_clip``, the update is
        # ``optimizer``, the loss scaler and the health outputs
        # ``step_misc``
        cfg = self.config
        with jax.named_scope("grad_clip"):
            inv = 1.0 / (scaler.scale * gas)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv,
                                 grads)
            overflow = check_overflow(grads) if self.fp16_enabled else \
                jnp.zeros((), bool)
            # global grad norm (reference get_global_norm +
            # clip_grad_norm_)
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads))
            grad_norm = jnp.sqrt(sq)
            # per-layer health norms use the same pre-clip convention as
            # the global grad norm above
            unclipped = grads
            if cfg.gradient_clipping > 0:
                clip = jnp.minimum(1.0, cfg.gradient_clipping /
                                   (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * clip, grads)
        with jax.named_scope("optimizer"):
            lr = self.lr_schedule(step)
            new_params, new_opt = self.optimizer.update(
                grads, opt_state, params, lr)
        with jax.named_scope("step_misc"):
            if self.fp16_enabled:
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new_params,
                    params)
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new_opt,
                    opt_state)
                scaler = update_scale(
                    scaler, overflow, dynamic=self.dynamic_loss_scale,
                    scale_window=cfg.fp16.loss_scale_window,
                    min_scale=cfg.fp16.min_loss_scale,
                    delayed_shift=cfg.fp16.hysteresis,
                    consecutive_hysteresis=cfg.fp16.consecutive_hysteresis)
            new_params = jax.lax.with_sharding_constraint(
                new_params, self._param_shardings)
            metrics = {"lr": lr, "grad_norm": grad_norm,
                       "loss_scale": scaler.scale,
                       "overflow": overflow.astype(jnp.int32)}
            if fwd_metrics and "aux_loss" in fwd_metrics:
                metrics["aux_loss"] = fwd_metrics["aux_loss"]
            if getattr(self, "_health_enabled", False):
                health = self._per_layer_health(params, unclipped,
                                                new_params)
                fh = (fwd_metrics or {}).get("health")
                if fh:
                    health = {**health, **fh}
                if health:
                    metrics["health"] = health
        return new_params, new_opt, scaler, metrics

    @staticmethod
    def _per_layer_health(params, grads, new_params):
        """In-graph per-layer training dynamics over the stacked
        ``params['layers']`` subtree (under the scanned-decoder layout
        every leaf there carries a leading [L] layer axis): per-layer
        grad norm, param norm, and the update/param ratio — the classic
        divergence precursors. Pure [L]-vector reductions fused into the
        step program; models without a stacked ``layers`` subtree simply
        contribute no per-layer optimizer stats."""
        if not (isinstance(params, dict) and "layers" in params):
            return {}

        def per_layer_sq(tree):
            tot = None
            for leaf in jax.tree.leaves(tree):
                if leaf.ndim < 1:
                    continue
                s = jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                            axis=tuple(range(1, leaf.ndim)))
                tot = s if tot is None else tot + s
            return tot

        g = per_layer_sq(grads["layers"])
        if g is None:
            return {}
        p = per_layer_sq(params["layers"])
        u = per_layer_sq(jax.tree.map(
            lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
            new_params["layers"], params["layers"]))
        param_norm = jnp.sqrt(p)
        return {"grad_norm": jnp.sqrt(g), "param_norm": param_norm,
                "update_ratio": jnp.sqrt(u) / (param_norm + 1e-12)}

    def _accumulate_grads(self, params, batch, scale, rng):
        """Shared GAS scan: stacked microbatches [gas, ...] → (fp32 grad
        sum carrying the ZeRO grad shardings, per-micro losses, loss_fn
        metrics pytree stacked on a leading [gas] axis)."""
        def micro(carry, mb):
            acc, r = carry
            r, sub = jax.random.split(r)
            loss, m, grads = self._compute_loss_and_grads(
                params, mb, scale, sub)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return (acc, r), (loss, m)

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        zero = jax.lax.with_sharding_constraint(
            zero, self.plan.grad_shardings())
        (acc, _), (losses, fwd) = jax.lax.scan(micro, (zero, rng), batch)
        return acc, losses, fwd

    def _build_step_functions(self) -> None:
        gas = int(self.config.gradient_accumulation_steps)
        #: the fused step registers for the scope table at its first call
        self._fused_step_registered = False
        #: ZeRO-3 chunked-overlap plan; stays None on every path that
        #: doesn't run the standard fused step (zeropp/onebit/offload/
        #: pipeline fall through to monolithic collectives)
        self._overlap_plan = None

        if getattr(self, "_zeropp_enabled", False):
            from deepspeed_tpu.runtime.zero.zeropp import build_zeropp_step
            build_zeropp_step(self)
            return

        if getattr(self, "_onebit_enabled", False):
            from deepspeed_tpu.ops.onebit import build_onebit_step
            build_onebit_step(self)
            return

        if self.offload_enabled:
            if self.model.pipeline_loss_fn is not None:
                raise ValueError(
                    "pipeline parallelism with offload_optimizer.device="
                    "'cpu' is not supported yet — the host step would "
                    "bypass the pipeline schedule")
            self.offload_overlap = bool(
                self.config.zero_optimization.offload_optimizer.overlap)
            if self.offload_overlap and self.fp16_enabled:
                raise ValueError(
                    "offload_optimizer.overlap requires bf16/fp32 — fp16 "
                    "dynamic loss scaling needs the synchronous overflow "
                    "signal (ZenFlow has the same restriction)")
            layout = self.host_optimizer.layout
            # grads leave the device as ONE flat transfer-dtype array
            # (reference copies bit16 grads to pinned host buffers on a side
            # stream, stage_1_and_2.py:1332; here one D2H of the flat concat)
            transfer_dtype = self.compute_dtype

            def grads_only(params, batch, scale, rng):
                acc, losses, _fm = self._accumulate_grads(params, batch,
                                                          scale, rng)
                acc = jax.tree.map(lambda g: g * (1.0 / gas), acc)
                return layout.flatten_device(acc, transfer_dtype), \
                    jnp.mean(losses)

            self._offload_grad_step = jax.jit(grads_only)

            # flat compute-dtype master → params pytree with shardings
            self._offload_unflatten = jax.jit(
                lambda flat: layout.unflatten_device(
                    flat, [self.compute_dtype] * len(layout.shapes)),
                out_shardings=self._param_shardings)
            self._host_future = None
            self._fused_step = None
            zf_cfg = self.config.zero_optimization.zenflow
            if zf_cfg is not None:
                if self.fp16_enabled:
                    raise ValueError(
                        "zenflow requires bf16/fp32 (reference restriction:"
                        " fp16 loss scaling needs a synchronous overflow "
                        "signal)")
                if self.config.zero_optimization.offload_optimizer.superoffload:
                    raise ValueError(
                        "zenflow and superoffload are mutually exclusive "
                        "host-step pipelines; enable one")
                from deepspeed_tpu.runtime.zero.zenflow import (
                    ZenFlowCoordinator)
                self._zenflow = ZenFlowCoordinator(self)

            def single_grad(params, batch, scale, rng):
                loss, _m, grads = self._compute_loss_and_grads(
                    params, batch, scale, rng)
                return loss, grads

            self._grad_step = jax.jit(single_grad)
            self._acc_add = jax.jit(
                lambda acc, grads: jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads),
                donate_argnums=(0,))
            self._update_step = None
            self._rng = jax.random.PRNGKey(self.config.seed + 1)
            return

        if self.model.pipeline_loss_fn is not None:
            # pipeline path: the schedule consumes all M microbatches in
            # one traced program; loss is already the mean over them.
            # 1F1B (pipeline_grad_fn) computes grads explicitly per
            # microbatch; GPipe (pipeline_loss_fn) goes through autodiff.
            def pipe_step(params, opt_state, scaler, batch, step, rng):
                if self.model.pipeline_grad_fn is not None:
                    loss, grads = self.model.pipeline_grad_fn(
                        params, batch, rng, scaler.scale)
                else:
                    def scaled(p):
                        loss = self.model.pipeline_loss_fn(p, batch, rng)
                        return loss * scaler.scale, loss
                    grads, loss = jax.grad(scaled, has_aux=True)(params)
                grads = jax.lax.with_sharding_constraint(
                    grads, self.plan.grad_shardings())
                params, opt_state, scaler, metrics = self._apply_update(
                    params, opt_state, scaler, grads, step, 1)
                metrics["loss"] = loss
                return params, opt_state, scaler, metrics

            # the module's name in a device trace: the fused step's kind
            pipe_step.__name__ = pipe_step.__qualname__ = \
                "fused_step_pipeline"
            self._fused_step = jax.jit(pipe_step, donate_argnums=(0, 1, 2))
            self._grad_step = None
            self._acc_add = None
            self._update_step = None
            self._rng = jax.random.PRNGKey(self.config.seed + 1)
            return

        if self.model.configure_overlap is not None:
            # arm the chunked ZeRO-3 collective pipeline BEFORE tracing:
            # the hook stores the plan in the factory's loss_fn closure,
            # so every step function traced below picks up the chunked
            # layer loop (runtime/zero/overlap.py)
            self._overlap_plan = self.model.configure_overlap(
                self.mesh, self._abstract_params)
            if self._overlap_plan is not None:
                from deepspeed_tpu.runtime.zero import overlap as _overlap
                _overlap.verify_scheduler_flags()
                self._overlap_plan.publish_static_gauges()

        # fused train_batch step: batch leaves have leading [gas, ...] dim
        def fused_step(params, opt_state, scaler, batch, step, rng):
            # runs at trace time only: the zero-retrace guarantee for the
            # health taps is asserted against this counter
            telemetry.compile_monitor.count_trace("engine/fused_step")
            if gas == 1:
                mb = jax.tree.map(lambda x: x[0], batch)
                rng, sub = jax.random.split(rng)
                loss, fwd, acc = self._compute_loss_and_grads(
                    params, mb, scaler.scale, sub)
                losses = loss[None]
            else:
                # accumulate in fp32 over microbatches (reference knob
                # gradient_accumulation_dtype); the accumulator carries the
                # grad shardings so ZeRO-2+ keeps it scattered across steps
                acc, losses, fwd = self._accumulate_grads(
                    params, batch, scaler.scale, rng)
                # collapse the [gas] axis: means throughout (act_absmax
                # becomes a mean-of-maxes across microbatches)
                fwd = jax.tree.map(lambda x: jnp.mean(x, axis=0), fwd)
            params, opt_state, scaler, metrics = self._apply_update(
                params, opt_state, scaler, acc, step, gas, fwd_metrics=fwd)
            metrics["loss"] = jnp.mean(losses)
            return params, opt_state, scaler, metrics

        self._fused_step = jax.jit(
            fused_step, donate_argnums=(0, 1, 2),
            static_argnames=())

        # parity API pieces
        def grad_step(params, batch, scale, rng):
            loss, metrics, grads = self._compute_loss_and_grads(
                params, batch, scale, rng)
            return loss, grads

        self._grad_step = jax.jit(grad_step)

        def acc_add(acc, grads):
            return jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)

        self._acc_add = jax.jit(acc_add, donate_argnums=(0,))

        def update_step(params, opt_state, scaler, grads, step):
            return self._apply_update(params, opt_state, scaler, grads,
                                      step, gas)

        self._update_step = jax.jit(update_step, donate_argnums=(0, 1, 2, 3))

        self._rng = jax.random.PRNGKey(self.config.seed + 1)

    # ----------------------------------------------------------- parity API

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference engine.py:is_gradient_accumulation_boundary."""
        gas = int(self.config.gradient_accumulation_steps)
        return (self.micro_steps + 1) % gas == 0

    def forward(self, batch: Batch) -> jax.Array:
        """Compute loss (+ cache grads for the following backward).

        Not supported under pipeline parallelism — use train_batch
        (reference: PipelineEngine raises the same way, pipe/engine.py).

        The reference runs autograd lazily; jax computes loss and grads in
        one fused call here — ``backward`` then folds the cached grads into
        the accumulator, preserving the 3-call API exactly."""
        if self._grad_step is None:
            raise RuntimeError(
                "forward()/backward()/step() are not supported with "
                "pipeline parallelism or the ZeRO++ quantized path; use "
                "train_batch() (reference pipe/engine.py restriction)")
        if self._param_stream is not None:
            raise RuntimeError(
                "forward()/backward()/step() are not supported under "
                "offload_param (layer-streamed schedule); use train_batch()")
        if self._step_t0 is None:           # first micro of the window
            self._step_t0 = telemetry.tracer.now()
            if self._watchdog is not None:
                self._watchdog.arm("forward", step=self.global_steps)
        self._rng, sub = jax.random.split(self._rng)
        batch = self._place_batch(batch)
        with telemetry.tracer.span("train/forward", step=self.global_steps):
            loss, grads = self._grad_step(self.params, batch,
                                          self.loss_scale_state.scale, sub)
        self._pending_grads = grads
        self._pending_loss = loss
        return loss

    def backward(self, loss: jax.Array) -> jax.Array:
        """Fold pending grads into the accumulator (reference engine.py:2478)."""
        if getattr(self, "_pending_grads", None) is None:
            raise RuntimeError("backward() called without forward()")
        with telemetry.tracer.span("train/backward", step=self.global_steps):
            if self._acc_grads is None:
                self._acc_grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32), self._pending_grads)
            else:
                self._acc_grads = self._acc_add(self._acc_grads,
                                                self._pending_grads)
        self._pending_grads = None
        self.micro_steps += 1
        return loss

    def step(self) -> None:
        """Optimizer step at GAS boundary (reference engine.py:2653)."""
        gas = int(self.config.gradient_accumulation_steps)
        if self.micro_steps % gas != 0:
            return
        if self._acc_grads is None:
            raise RuntimeError("step() called with no accumulated gradients")
        if self.offload_enabled:
            with telemetry.tracer.span("train/optimizer",
                                       step=self.global_steps):
                grads = jax.tree.map(lambda g: g / gas, self._acc_grads)
                metrics = self._host_step(grads)
            self._acc_grads = None
            self.global_steps += 1
            self.global_samples += int(self.config.train_batch_size)
            self._last_metrics = metrics
            self._end_step()
            self._write_monitor(metrics)
            return
        with telemetry.tracer.span("train/optimizer", step=self.global_steps):
            self.params, self.opt_state, self.loss_scale_state, metrics = \
                self._update_step(self.params, self.opt_state,
                                  self.loss_scale_state, self._acc_grads,
                                  jnp.int32(self.global_steps))
        self._acc_grads = None
        self.global_steps += 1
        self.global_samples += int(self.config.train_batch_size)
        if self.fp16_enabled and int(jax.device_get(metrics["overflow"])):
            self.skipped_steps += 1
        metrics = self._note_health(metrics)
        self._last_metrics = metrics
        self._end_step()
        self._write_monitor(metrics)

    def train_batch(self, data_iter: Optional[Iterator[Batch]] = None
                    ) -> jax.Array:
        """Fused whole-step path (reference PipelineEngine.train_batch:337 —
        here the non-pipeline fast path; pipeline engine overrides).

        One ``train/step`` span (a ``StepTraceAnnotation`` with the step
        number under ``jax_annotations``) from the top of the call, with
        children ``train/batch`` (fetch, stack, place), ``train/dispatch``
        (the fused step program's call) and ``train/bookkeeping`` (timer,
        step telemetry, monitor). The call returns before the device
        ends, so these are host times."""
        with telemetry.tracer.span("train/step", step=self.global_steps):
            return self._train_batch(data_iter)

    def _train_batch(self, data_iter: Optional[Iterator[Batch]]
                     ) -> jax.Array:
        gas = int(self.config.gradient_accumulation_steps)
        own_data = data_iter is None
        self._step_t0 = telemetry.tracer.now()
        with telemetry.tracer.span("train/batch"):
            it = data_iter if data_iter is not None \
                else self._own_data_iterator()
            # chaos hook (resilience/faults.py): a scheduled preempt
            # delivers SIGTERM here — this step completes and the elastic
            # agent commits at its boundary; a nonfinite_grad advisory
            # poisons THIS step (handled after the batch is consumed, like
            # an overflow skip)
            chaos = fault_injector.fire("train_step", step=self.global_steps)
            micros = [next(it) for _ in range(gas)]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *micros)
            if self.config.check_nan_inf:
                self._check_batch_consistency(micros, local=own_data)
            batch = self._place_stacked_batch(batch, local=own_data)
        if "nonfinite_grad" in chaos:
            return self._skip_poisoned_step(gas)
        self.tput_timer.start()
        if self._watchdog is not None:
            self._watchdog.arm("train_batch", step=self.global_steps)
        self._rng, sub = jax.random.split(self._rng)
        if self._param_stream is not None or self._zenflow is not None:
            runner = self._param_stream or self._zenflow
            loss = runner.train_step(batch, sub)
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += int(self.config.train_batch_size)
            if self.curriculum_scheduler is not None:
                self.curriculum_scheduler.update_difficulty(self.global_steps)
            self.tput_timer.stop(sync=loss)
            self._end_step()
            self._write_monitor(self._last_metrics)
            return loss
        if self.offload_enabled:
            # dispatch device fwd/bwd first (async); with overlap the host
            # Adam for the PREVIOUS step runs while this executes
            flat_g, loss = self._offload_grad_step(
                self.params, batch, self.loss_scale_state.scale, sub)
            lr = float(jax.device_get(
                self.lr_schedule(jnp.int32(self.global_steps))))
            scale = float(jax.device_get(self.loss_scale_state.scale)) \
                if self.fp16_enabled else 1.0
            # SuperOffload consumes the DEVICE array (bucketed fetch
            # pipelined against the sweep); the plain path fetches once.
            # Keyed off the optimizer actually built — the config flag
            # alone could disagree (e.g. device='nvme' wins over it)
            from deepspeed_tpu.runtime.zero.superoffload import (
                SuperOffloadOptimizer)
            superoffload = isinstance(self.host_optimizer,
                                      SuperOffloadOptimizer)
            g_arg = flat_g if superoffload else np.asarray(flat_g)
            if self.offload_overlap:
                self._drain_host_step()          # apply step t-1's update
                self._host_future = self.host_optimizer.step_flat_async(
                    g_arg, lr, grad_clip=self.config.gradient_clipping,
                    loss_scale=scale,
                    wait_on=getattr(self, "_last_upload", None))
                metrics = dict(getattr(self, "_last_host_metrics", None) or
                               {"grad_norm": 0.0, "overflow": 0, "lr": lr})
            else:
                metrics = self._apply_host_result(
                    self.host_optimizer.step_flat(
                        g_arg, lr, grad_clip=self.config.gradient_clipping,
                        loss_scale=scale))
            metrics["loss"] = loss
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += int(self.config.train_batch_size)
            if self.curriculum_scheduler is not None:
                self.curriculum_scheduler.update_difficulty(self.global_steps)
            self._last_metrics = metrics
            self.tput_timer.stop(sync=loss)
            self._end_step()
            self._write_monitor(metrics)
            return loss
        args = (self.params, self.opt_state, self.loss_scale_state, batch,
                jnp.int32(self.global_steps), sub)
        if not self._fused_step_registered:
            # for the scope table (compile_monitor.scopes): abstract
            # arguments only, before the call donates the real ones
            telemetry.compile_monitor.register_program(
                self._fused_step.__name__, self._fused_step, args)
            self._fused_step_registered = True
        with telemetry.tracer.span("train/dispatch"):
            self.params, self.opt_state, self.loss_scale_state, metrics = \
                self._fused_step(*args)
        del args
        with telemetry.tracer.span("train/bookkeeping"):
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += int(self.config.train_batch_size)
            if self.curriculum_scheduler is not None:
                self.curriculum_scheduler.update_difficulty(
                    self.global_steps)
            if self.fp16_enabled and \
                    int(jax.device_get(metrics["overflow"])):
                self.skipped_steps += 1
            metrics = self._note_health(metrics)
            self._last_metrics = metrics
            loss = metrics["loss"]
            self.tput_timer.stop(sync=loss)
            self._end_step()
            self._write_monitor(metrics)
        return loss

    def _skip_poisoned_step(self, gas: int) -> jax.Array:
        """Recovery path for an injected ``nonfinite_grad``: treat the
        step exactly like an fp16 overflow skip — the batch is consumed,
        the host rng advances, every counter moves, but params/opt_state
        stay untouched and the returned loss is NaN. Keeping the rng and
        counter discipline identical to a real step is what lets a
        chaos run keep bitwise resume parity with an uninterrupted one."""
        self._rng, _ = jax.random.split(self._rng)
        self.global_steps += 1
        self.micro_steps += gas
        self.global_samples += int(self.config.train_batch_size)
        self.skipped_steps += 1
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self.global_steps)
        metrics = {"loss": float("nan"), "grad_norm": float("nan"),
                   "overflow": 1}
        self._last_metrics = metrics
        record_recovery("skip_nonfinite", step=self.global_steps)
        self._end_step()
        self._write_monitor(metrics)
        return jnp.float32(float("nan"))

    def _check_batch_consistency(self, micros, local: bool = False) -> None:
        """Cross-process dataloader consistency (reference
        check_dataloader_inputs_same_across_ranks engine.py:520): every
        process must feed the same global batch or the SPMD step silently
        trains on garbage. Hash ALL microbatches, allgather, compare.

        ``local`` is the provenance flag from ``train_batch`` (own engine
        dataloader → per-process slices whose contents legitimately differ);
        a size heuristic alone can't distinguish a user iterator that merely
        happens to yield global-batch-sized leaves."""
        if jax.process_count() <= 1:
            return
        import hashlib
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(micros):
            leaf = np.asarray(leaf)
            if local and leaf.ndim:
                # per-process local slices: contents legitimately differ;
                # the invariant is structural (same shapes/dtypes) plus
                # identical loader schedule, checked via seed/epoch below
                h.update(repr((leaf.shape, str(leaf.dtype))).encode())
            else:
                h.update(np.ascontiguousarray(leaf).tobytes())
        if self.training_dataloader is not None:
            h.update(repr((self.training_dataloader.seed,
                           self.training_dataloader.epoch)).encode())
        if self.data_sampler is not None:
            # sampler position must agree or the per-process slices come
            # from different steps and assemble a garbage global batch
            h.update(repr(self.data_sampler.state_dict()).encode())
        digest = np.frombuffer(h.digest()[:8], np.int64)
        from jax.experimental import multihost_utils
        all_digests = multihost_utils.process_allgather(digest)
        if not np.all(all_digests == digest):
            raise RuntimeError(
                "sanity check failed: dataloader batches differ across "
                "processes (reference engine.py:520 check)")

    def eval_batch(self, data_iter: Optional[Iterator[Batch]] = None
                   ) -> jax.Array:
        """Forward-only loss over one global batch — no gradients, no
        state change (reference PipelineEngine.eval_batch / engine eval
        usage). Works in every engine mode, including ZeRO++ flat storage
        (params unflattened on the fly), pipeline (GPipe loss fn), and the
        offload_param tier (forward-only layer streaming)."""
        if self._param_stream is not None:
            if data_iter is None:
                raise ValueError("eval_batch needs an explicit data_iter")
            gas = int(self.config.gradient_accumulation_steps)
            losses = [self._param_stream.eval_step(next(data_iter))
                      for _ in range(gas)]
            return jnp.mean(jnp.stack(losses))
        if self.offload_enabled:
            self._drain_host_step()     # overlap mode: apply the pending
            #                             update or we'd eval stale weights
        if data_iter is None:
            raise ValueError(
                "eval_batch needs an explicit data_iter — consuming the "
                "engine's training iterator would silently skip training "
                "samples (reference eval_batch takes its own loader)")
        gas = int(self.config.gradient_accumulation_steps)
        it = data_iter
        micros = [next(it) for _ in range(gas)]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *micros)
        if self.config.check_nan_inf:
            self._check_batch_consistency(micros)
        batch = self._place_stacked_batch(batch)
        # derive an eval key WITHOUT advancing the training rng stream —
        # eval must not perturb training reproducibility
        sub = jax.random.fold_in(self._rng, self.global_steps)
        if getattr(self, "_eval_step", None) is None:
            if self.model.pipeline_loss_fn is not None:
                def eval_fn(params, batch, rng):
                    return self.model.pipeline_loss_fn(params, batch, rng)
            else:
                def eval_fn(params, batch, rng):
                    def micro(carry, mb):
                        r = carry
                        r, s = jax.random.split(r)
                        out = self.model.loss_fn(self._eval_params(params),
                                                 mb, s)
                        loss = out[0] if isinstance(out, tuple) else out
                        return r, loss
                    _, losses = jax.lax.scan(micro, rng, batch)
                    return jnp.mean(losses)
            self._eval_step = jax.jit(eval_fn)
        return self._eval_step(self.params, batch, sub)

    def _eval_params(self, params):
        """Engine-mode params view for evaluation (ZeRO++ stores flat)."""
        if getattr(self, "_zeropp_enabled", False):
            layout = self._zeropp_layout
            return layout.unflatten_device(params[:layout.total])
        return params

    def _apply_host_result(self, result) -> Dict[str, Any]:
        """Upload the host step's flat master (ONE device_put + jitted
        unflatten) and fold in overflow/loss-scale bookkeeping."""
        new_flat, metrics = result
        if new_flat is None:          # fp16 overflow: skip
            self.skipped_steps += 1
        else:
            # split transfer from compute: _last_upload tracks ONLY the H2D
            # DMA of the host buffer, so the next host step can block on it
            # (buffer-reuse hazard) without waiting on queued device work
            flat_dev = jnp.asarray(new_flat)
            self._last_upload = flat_dev
            self.params = self._offload_unflatten(flat_dev)
        if self.fp16_enabled:
            from deepspeed_tpu.runtime.loss_scaler import update_scale
            self.loss_scale_state = update_scale(
                self.loss_scale_state,
                jnp.asarray(bool(metrics["overflow"])),
                dynamic=self.dynamic_loss_scale,
                scale_window=self.config.fp16.loss_scale_window,
                min_scale=self.config.fp16.min_loss_scale,
                delayed_shift=self.config.fp16.hysteresis,
                consecutive_hysteresis=self.config.fp16.consecutive_hysteresis)
        self._last_host_metrics = dict(metrics)
        return dict(metrics)

    def _drain_host_step(self) -> None:
        """Wait for an in-flight overlapped host step and apply it."""
        if getattr(self, "_zenflow", None) is not None:
            self._zenflow.drain()
        if getattr(self, "_host_future", None) is not None:
            fut, self._host_future = self._host_future, None
            self._apply_host_result(fut.result())

    def _host_step(self, grads: Pytree) -> Dict[str, Any]:
        """ZeRO-Offload update from a grads pytree (3-call parity path)."""
        lr = float(jax.device_get(
            self.lr_schedule(jnp.int32(self.global_steps))))
        scale = float(jax.device_get(self.loss_scale_state.scale)) \
            if self.fp16_enabled else 1.0
        flat_g = self.host_optimizer.layout.flatten_np(grads)
        return self._apply_host_result(self.host_optimizer.step_flat(
            flat_g, lr, grad_clip=self.config.gradient_clipping,
            loss_scale=scale))

    def _own_data_iterator(self):
        """Persistent epoch-advancing iterator over the engine dataloader
        (reference: the engine owns training_dataloader, deepspeed_io:2035)."""
        if self.training_dataloader is None:
            raise RuntimeError(
                "train_batch() without data_iter requires training_data at "
                "initialize()")
        if getattr(self, "_data_iter", None) is None:
            from deepspeed_tpu.runtime.dataloader import RepeatingLoader
            self._data_iter = iter(RepeatingLoader(self.training_dataloader))
        return self._data_iter

    # -------------------------------------------------------------- batches

    def _put_global(self, x, sharding, batch_dim: int, local: bool):
        """Assemble a global array on ``sharding``. Two multi-host modes
        (reference DistributedSampler rank sharding vs replicated input):
        when the batch came from the engine's own dataloader (``local``),
        each leaf's batch dim is ``global/process_count`` — this process's
        slice, assembled zero-copy via
        ``jax.make_array_from_process_local_data``. User-supplied batches
        are identical on every process and device_put scatters them (the
        size check alone can't distinguish a slice from e.g. a broadcast
        [1, ...] mask leaf, so ``local`` is decided by provenance)."""
        x = jnp.asarray(x) if not isinstance(x, (np.ndarray, jax.Array)) \
            else x
        pc = jax.process_count()
        if local and pc > 1 and np.ndim(x) > batch_dim:
            global_b = int(self.config.train_micro_batch_size_per_gpu) \
                * self.dp_world_size
            if x.shape[batch_dim] * pc == global_b:
                gshape = list(x.shape)
                gshape[batch_dim] = global_b
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(x), tuple(gshape))
        return jax.device_put(jnp.asarray(x), sharding)

    def _place_batch(self, batch: Batch, local: bool = False) -> Batch:
        sh = self._batch_sharding(batch)
        return jax.tree.map(
            lambda x, s: self._put_global(x, s, 0, local), batch, sh)

    def _place_stacked_batch(self, batch: Batch, local: bool = False
                             ) -> Batch:
        """batch leaves: [gas, B, ...] — shard B (dim 1) over DP."""
        sp = self.mesh.shape["seq"] > 1

        def spec_for(x):
            nd = np.ndim(x)
            entries = [None, ZERO_AXES] + [None] * (nd - 2)
            if sp and nd >= 3:
                entries[2] = "seq"
            return NamedSharding(self.mesh, P(*entries))
        sh = jax.tree.map(spec_for, batch)
        return jax.tree.map(
            lambda x, s: self._put_global(x, s, 1, local), batch, sh)

    def _build_dataloader(self, training_data):
        self.curriculum_scheduler = None
        self.data_sampler = None
        if training_data is None:
            return None
        from deepspeed_tpu.runtime.dataloader import DeepSpeedTPUDataLoader
        micro = int(self.config.train_micro_batch_size_per_gpu)
        de = self.config.data_efficiency
        sampler = None
        if de.enabled and (de.curriculum_learning.get("enabled")
                           or de.data_sampling.get("enabled")):
            gas = int(self.config.gradient_accumulation_steps)
            # reference deepspeed_io:2035 builds DeepSpeedDataSampler when
            # data-efficiency sampling/curriculum is on; difficulty metric
            # comes from the analyzer output (here: config-provided values,
            # a .npy path, or per-sample len() as the fallback metric)
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler \
                import CurriculumScheduler
            from deepspeed_tpu.runtime.data_pipeline.data_sampler import (
                DeepSpeedDataSampler)
            if de.curriculum_learning.get("enabled"):
                cl = {k: v for k, v in de.curriculum_learning.items()
                      if k != "enabled"}
                self.curriculum_scheduler = CurriculumScheduler(cl)
            ds_cfg = de.data_sampling
            metric = ds_cfg.get("metric_values")
            if metric is None and ds_cfg.get("metric_path"):
                metric = np.load(ds_cfg["metric_path"])
            if metric is None:
                metric = [_sample_difficulty(training_data[i])
                          for i in range(len(training_data))]
                if len(set(metric)) <= 1:
                    msg = ("the fallback difficulty metric (first-array-leaf "
                           "length) is constant over this dataset, so "
                           "difficulty gating is a no-op — provide "
                           "'metric_values' or 'metric_path' (reference: "
                           "data_analyzer.py output files)")
                    if ds_cfg.get("enabled"):
                        # the user explicitly asked for metric-driven
                        # sampling: a silent no-op would be a lie
                        raise ValueError(f"data_sampling: {msg}")
                    # curriculum-only over fixed-length data: pacing by
                    # steps still works, difficulty gating just passes all
                    logger.warning(f"curriculum_learning: {msg}")
            if len(metric) != len(training_data):
                raise ValueError(
                    f"data_sampling metric has {len(metric)} entries but "
                    f"training_data has {len(training_data)} samples")
            sampler = DeepSpeedDataSampler(
                metric, batch_size=micro * self.dp_world_size,
                curriculum=self.curriculum_scheduler,
                dp_rank=jax.process_index(), dp_world=jax.process_count(),
                seed=de.seed, micro_steps_per_global_step=gas)
            self.data_sampler = sampler
        return DeepSpeedTPUDataLoader(
            training_data,
            micro_batch_size=micro,
            dp_world_size=self.dp_world_size,
            seed=self.config.seed,
            data_sampler=sampler)

    # ------------------------------------------------------------ telemetry

    def _init_telemetry(self) -> None:
        tcfg = self.config.telemetry
        telemetry.configure(tcfg)   # enable-only; never silences the tracer
        # arm the trace-time collective recorder from its config block
        # (jit is lazy — the step traces on the first train_batch, after
        # this runs)
        from deepspeed_tpu.comm.comms_logger import comms_logger
        comms_logger.configure(self.config)
        if tcfg.enabled and tcfg.trace_file:
            import atexit
            atexit.register(telemetry.tracer.dump, tcfg.trace_file)
        self._step_t0: Optional[float] = None
        self._mem_sampler = telemetry.MemorySampler() \
            if tcfg.sample_memory else None
        self._peak_flops = tcfg.peak_flops_override or \
            telemetry.peak_flops()
        fpt = getattr(self.model, "flops_per_token", None) or 0.0
        tps = getattr(self.model, "tokens_per_sample", None) or 0
        #: total model FLOPs per optimizer step across the whole batch
        #: (flops_per_token already counts fwd+bwd, the 6N convention)
        self._flops_per_step = fpt * tps * int(self.config.train_batch_size)
        # -- diagnostics layer (always-on flight recorder; opt-in watchdog)
        telemetry.flight_recorder.configure(
            max_steps=tcfg.flight_recorder_steps, path=tcfg.blackbox_path)
        telemetry.flight_recorder.set_meta(
            zero_stage=self.zero_stage, dtype=self.config.compute_dtype,
            dp_world_size=self.dp_world_size,
            train_batch_size=int(self.config.train_batch_size))
        telemetry.flight_recorder.install_excepthook()
        telemetry.compile_monitor.install(
            storm_threshold=tcfg.compile_storm_threshold)
        wcfg = tcfg.watchdog
        self._watchdog = telemetry.Watchdog(
            timeout_s=wcfg.step_timeout_s, action=wcfg.action,
            dump_dir=wcfg.dump_dir,
            heartbeat_file=wcfg.heartbeat_file or
            os.environ.get("DSTPU_HEARTBEAT_FILE") or None) \
            if wcfg.enabled else None
        # -- compile-time explain (PR 5): the static HBM budget is always
        # logged (pure metadata, no compile); the full roofline explain —
        # one extra XLA compile of the step — is opt-in
        self._roofline_predicted_s = 0.0
        from deepspeed_tpu.telemetry import explain as _explain
        try:
            _explain.startup_budget(self)
        except Exception as e:                       # noqa: BLE001
            logger.debug(f"startup HBM budget skipped: {e}")
        if tcfg.explain_startup:
            try:
                report = _explain.explain_engine(self)
                _explain.publish_gauges(report)
                self._roofline_predicted_s = report.roofline.predicted_s
                log_dist("\n" + _explain.render(report))
            except Exception as e:                   # noqa: BLE001
                logger.warning(f"explain_startup failed (non-fatal): {e}")
        # -- model-health taps (telemetry/health.py): stats are computed
        # in-graph EVERY step behind a static build-time flag (identical
        # program on- and off-cadence → zero retraces); ``every`` only
        # gates the host-side fetch/publish below
        hcfg = tcfg.health
        self._health_enabled = bool(hcfg.enabled)
        self._health_monitor = None
        if hcfg.enabled:
            from deepspeed_tpu.telemetry.health import HealthMonitor
            self._health_monitor = HealthMonitor(
                every=hcfg.every, max_layers=hcfg.max_layers,
                z_threshold=hcfg.z_threshold,
                dead_fraction=hcfg.dead_fraction)
        # -- resilience: arm the deterministic fault injector from config
        # (env DSTPU_FAULT_PLAN is merged inside arm()) and push the
        # checkpoint IO retry knobs into the store module
        rcfg = getattr(self.config, "resilience", None)
        if rcfg is not None:
            from deepspeed_tpu.checkpoint import store as _ckpt_store
            _ckpt_store.IO_RETRIES = int(rcfg.ckpt_io_retries)
            _ckpt_store.IO_BACKOFF_S = float(rcfg.ckpt_io_backoff_s)
            if rcfg.fault_plan or os.environ.get("DSTPU_FAULT_PLAN"):
                fault_injector.arm(rcfg.fault_plan)
        self._metrics_server = None
        if tcfg.http_port is not None:
            import atexit
            from deepspeed_tpu.telemetry.endpoint import MetricsServer
            try:
                self._metrics_server = MetricsServer(
                    tcfg.http_port,
                    heartbeat_file=wcfg.heartbeat_file or
                    os.environ.get("DSTPU_HEARTBEAT_FILE") or None)
                atexit.register(self._metrics_server.close)
            except Exception as e:                   # noqa: BLE001
                logger.warning(
                    f"metrics endpoint on :{tcfg.http_port} failed: {e}")
        # -- metric history + SLO burn-rate engine: a history_file key or
        # any slo.objectives turns continuous evaluation on (the history
        # runs memory-only when no file is configured); the SLO engine
        # subscribes to history appends, so one registry snapshot per
        # flush feeds the file, the burn gauges, /healthz, and the
        # flight recorder together
        self._metric_history = None
        self._slo = None
        scfg = getattr(self.config, "slo", None)
        if tcfg.history_file or (scfg is not None and scfg.objectives):
            from deepspeed_tpu.telemetry.slo import engine_from_config
            from deepspeed_tpu.telemetry.timeseries import MetricHistory
            try:
                self._metric_history = MetricHistory(
                    path=tcfg.history_file,
                    max_bytes=tcfg.history_max_bytes,
                    downsample=tcfg.history_downsample)
                self._slo = engine_from_config(
                    scfg, healthz=self._metrics_server)
                if self._slo is not None:
                    self._metric_history.subscribe(self._slo.observe)
                    log_dist(f"SLO engine armed: "
                             f"{[o.describe() for o in self._slo.objectives]}")
            except Exception as e:                   # noqa: BLE001
                logger.warning(f"metric history/SLO init failed: {e}")
                self._metric_history = self._slo = None

    def _record_step_telemetry(self, dt_s: float) -> None:
        """Per-step registry metrics (always on — the registry is cheap).

        ``dt_s`` is HOST wall time for the step: under jax async dispatch
        it measures dispatch + any host work, not device latency, except
        on steps something synced (ThroughputTimer reporting steps, host
        optimizer sweeps). The MFU gauge inherits this caveat; the synced
        per-interval throughput line remains the calibrated number."""
        reg = telemetry.registry
        reg.counter("train/steps", help="optimizer steps completed").inc()
        if dt_s > 0:
            reg.histogram(
                "train/step_time_ms", lo=1e-2, hi=1e6,
                help="host wall time per optimizer step (ms)"
            ).record(dt_s * 1e3)
            reg.gauge(
                "train/mfu",
                help="model FLOPs utilization vs peak (0 when peak unknown)"
            ).set(telemetry.mfu(self._flops_per_step, dt_s,
                                n_devices=jax.device_count(),
                                peak=self._peak_flops or None))
            # step-time regression detection (host wall time, already a
            # float — no sync); loss/grad anomalies ride the batched
            # monitor flush instead (see _flush_monitor)
            telemetry.anomaly_detector.observe(self.global_steps,
                                               step_time_ms=dt_s * 1e3)
            if self._roofline_predicted_s > 0:
                reg.gauge(
                    "roofline/pct",
                    help="predicted/measured step time, percent"
                ).set(100.0 * self._roofline_predicted_s / dt_s)
        if self._mem_sampler is not None and \
                self.global_steps % max(1, self.config.steps_per_print) == 0:
            self._mem_sampler.sample()
        # goodput ledger sweep (rate-limited internally; no-op when
        # telemetry.goodput is off) BEFORE the history flush so the
        # goodput/* gauges land in the same history record
        telemetry.goodput_ledger.maybe_update()
        # metric history: when the monitor is enabled the history rides
        # _flush_monitor's registry pass; without one (the common case)
        # feed it here on its own cadence so SLOs still evaluate
        if self._metric_history is not None and \
                (self.monitor is None or not self.monitor.enabled):
            every = getattr(self.config.telemetry, "history_every", 0) or \
                max(1, self.config.steps_per_print)
            if self.global_steps % max(1, every) == 0:
                telemetry.registry.flush_to_monitor(
                    None, self.global_steps, history=self._metric_history)
        # flight recorder: one dict append; loss/grad_norm/loss_scale stay
        # DEVICE scalars until a dump resolves them (no pipeline stall)
        m = getattr(self, "_last_metrics", None) or {}
        telemetry.flight_recorder.record_step(
            self.global_steps, kind="train", dur_s=dt_s,
            loss=m.get("loss"), grad_norm=m.get("grad_norm"),
            loss_scale=m.get("loss_scale") if self.fp16_enabled else None,
            skipped_steps=self.skipped_steps or None)

    def _scoped_finite_check(self) -> None:
        """``check_nan_inf="scoped"``: per-leaf finite check over the
        just-updated params — a non-finite grad propagates through the
        optimizer update, and fp16 overflow-skipped steps keep the old
        (finite) params, so this never false-positives on a handled
        overflow. Costs the mode's one documented scalar sync per step;
        a hit names the first bad leaf through telemetry/anomaly.py."""
        if not self._scoped_nan_check or self._param_stream is not None \
                or self.params is None:
            return
        from deepspeed_tpu.runtime.loss_scaler import global_check
        if self._scoped_check_jit is None:
            self._scoped_check_jit = jax.jit(global_check)
        bad, flags = self._scoped_check_jit(self.params)
        if bool(jax.device_get(bad)):
            path = telemetry.first_flagged_path(jax.device_get(flags))
            telemetry.anomaly_detector.report_nonfinite(
                self.global_steps, path, what="params")

    def _end_step(self) -> None:
        """End the whole-step window opened by the first forward() of the
        accumulation window (or by train_batch): the per-step registry
        metrics. (The ``train/step`` span is train_batch's own context;
        the 3-call API has its forward / backward / optimizer spans.)"""
        t1 = telemetry.tracer.now()
        t0 = self._step_t0 if self._step_t0 is not None else t1
        self._step_t0 = None
        if self._watchdog is not None:
            self._watchdog.disarm()
        self._record_step_telemetry(t1 - t0)
        self._scoped_finite_check()

    # -------------------------------------------------------------- monitor

    def _build_monitor(self):
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster
            return MonitorMaster(self.config.monitor_config)
        except Exception:
            return None

    def _note_health(self, metrics):
        """Route the in-graph model-health stats (vector-valued, computed
        every step — telemetry/health.py) out of the step metrics and into
        the HealthMonitor's cadence gate. Off-cadence steps drop the device
        refs unfetched — no transfer, no sync; the scalar metrics left in
        the dict keep flowing to the monitor/flight-recorder paths."""
        if not isinstance(metrics, dict):
            return metrics
        health = metrics.pop("health", None)
        hm = getattr(self, "_health_monitor", None)
        if hm is None or (health is None and "aux_loss" not in metrics):
            return metrics
        try:
            hm.note(self.global_steps, health,
                    aux_loss=metrics.get("aux_loss"))
        except Exception as e:                       # noqa: BLE001
            logger.warning(f"health telemetry publish failed: {e}")
        return metrics

    def _write_monitor(self, metrics: Dict[str, jax.Array]) -> None:
        # every step is RECORDED (the reference writes monitor events each
        # step when enabled, engine.py:2822 — decimating would drop TB/W&B
        # loss-curve resolution), but device scalars are held as futures and
        # fetched in one batched device_get on reporting steps: a per-step
        # float() here would block on the just-dispatched step and stall the
        # async/offload-overlap pipeline (see ThroughputTimer.stop)
        if self.monitor is None or not self.monitor.enabled:
            return
        self._monitor_pending.append(
            (self.global_steps,
             {k: v for k, v in metrics.items() if np.ndim(v) == 0}))
        if self.global_steps % max(1, self.config.steps_per_print) == 0:
            self._flush_monitor()

    def _flush_monitor(self) -> None:
        if not self._monitor_pending:
            return
        pending, self._monitor_pending = self._monitor_pending, []
        fetched = jax.device_get([m for _, m in pending])   # ONE transfer
        events = [(f"Train/{k}", float(val), step)
                  for (step, _), vals in zip(pending, fetched)
                  for k, val in vals.items()]
        self.monitor.write_events(events)
        # anomaly detection over the just-fetched host floats — same
        # batched cadence, so it never adds a device sync of its own
        for (step, _), vals in zip(pending, fetched):
            telemetry.anomaly_detector.observe(
                step,
                loss=vals.get("loss"),
                grad_norm=vals.get("grad_norm"))
            # MoE load-balancing pressure as a first-class gauge, visible
            # without the full health cadence (rides the same fetch)
            if "aux_loss" in vals:
                telemetry.registry.gauge(
                    "train/aux_loss",
                    help="MoE load-balancing auxiliary loss").set(
                    float(vals["aux_loss"]))
        # registry snapshot rides the same flush cadence (MFU, step-time
        # histogram aggregates, mem/* watermarks, comm/* counters); the
        # metric history + SLO evaluation share the same single lock pass
        telemetry.registry.flush_to_monitor(self.monitor, self.global_steps,
                                            history=self._metric_history)

    # ------------------------------------------------------------ utilities

    def get_lr(self) -> float:
        return float(jax.device_get(self.lr_schedule(jnp.int32(self.global_steps))))

    def get_global_grad_norm(self) -> Optional[float]:
        m = getattr(self, "_last_metrics", None)
        return float(jax.device_get(m["grad_norm"])) if m else None

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return int(self.config.train_micro_batch_size_per_gpu)

    def train_batch_size(self) -> int:
        return int(self.config.train_batch_size)

    def gradient_accumulation_steps(self) -> int:
        return int(self.config.gradient_accumulation_steps)

    def loss_scale(self) -> float:
        return float(jax.device_get(self.loss_scale_state.scale))

    # ------------------------------------------------- offload/reload states

    def offload_states(self, include: Optional[Tuple[str, ...]] = None
                       ) -> None:
        """Move params/optimizer state to host DRAM and FREE the device
        buffers (reference runtime/zero/offload_states.py:90 +
        engine.offload_states — used to park a training engine while an
        inference engine owns HBM, e.g. RLHF generation phases)."""
        include = tuple(include or ("params", "opt_state"))
        if getattr(self, "_offloaded_states", None):
            raise RuntimeError("states already offloaded; reload first")
        def to_host(x):
            if not isinstance(x, jax.Array):
                return np.asarray(x)
            if x.is_fully_addressable:
                return np.asarray(jax.device_get(x))
            # multi-host sharded array: park only THIS process's shards
            # (device_get on the global array would raise); reload
            # reassembles via make_array_from_callback
            return _ParkedShards(
                shape=x.shape, dtype=x.dtype,
                shards={s.index: np.asarray(s.data)
                        for s in x.addressable_shards})

        parked: Dict[str, Any] = {}
        for name in include:
            tree = getattr(self, name)
            # `tree` may be a dict pytree OR one flat jax.Array (ZeRO++)
            if tree is None or (isinstance(tree, dict) and not tree):
                continue
            host = jax.tree.map(to_host, tree)
            for leaf in jax.tree.leaves(tree):
                if isinstance(leaf, jax.Array):
                    leaf.delete()          # actually release HBM
            parked[name] = host
            setattr(self, name, None)
        self._offloaded_states = parked

    def reload_states(self) -> None:
        """Restore offloaded states to device with their original
        shardings (reference engine.reload_states)."""
        parked = getattr(self, "_offloaded_states", None)
        if not parked:
            return
        shardings = {"params": self._param_shardings,
                     "opt_state": self._state_shardings}

        def restore(host, sh):
            if isinstance(host, _ParkedShards):
                return jax.make_array_from_callback(
                    host.shape, sh, lambda idx: host.shards[idx])
            return jax.device_put(host, sh)

        for name, host in parked.items():
            sh_tree = shardings[name]
            setattr(self, name, jax.tree.map(
                restore, host, sh_tree,
                is_leaf=lambda x: isinstance(x, _ParkedShards)))
        self._offloaded_states = None

    # --------------------------------------------------------- checkpointing

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict[str, Any]] = None,
                        save_latest: bool = True,
                        async_save: bool = False) -> None:
        """Reference engine.py:3621. Sharded universal format: each process
        writes its own shard fragments with full-array index metadata, so
        any later mesh/stage reloads with no converter (ds_to_universal is
        unnecessary) and no host ever gathers the full model.
        ``async_save`` commits on a background thread after a synchronous
        device→host snapshot (reference: DecoupledCheckpointEngine)."""
        from deepspeed_tpu.checkpoint.store import save_checkpoint as _save
        self._flush_monitor()         # don't lose buffered metric events
        if self.offload_enabled:
            self._drain_host_step()   # overlapped update must land first
        tag = tag or f"global_step{self.global_steps}"
        params = self.params if self._param_stream is None \
            else self._param_stream.full_params_np()
        state = {
            "params": params,
            "opt_state": self.opt_state,
            "loss_scale": self.loss_scale_state,
        }
        meta = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "global_samples": self.global_samples,
            "optimizer": self.optimizer.hyperparams,
            "client_state": client_state or {},
            "offload": self.offload_enabled,
            "data_sampler": (self.data_sampler.state_dict()
                             if self.data_sampler is not None else None),
            # exact-resume state: host PRNG key + dataloader cursor. With
            # these a preempt-at-step-k resume replays the SAME rng splits
            # and batch sequence the uninterrupted run would have seen
            "rng": np.asarray(jax.device_get(self._rng)).tolist(),
            "dataloader": (self.training_dataloader.state_dict()
                           if self.training_dataloader is not None and
                           hasattr(self.training_dataloader, "state_dict")
                           else None),
        }
        root = _save(save_dir, tag, state, meta, save_latest=save_latest,
                     async_save=async_save)
        if self.offload_enabled:
            np.savez(os.path.join(root, "host_optimizer.npz"),
                     **self.host_optimizer.state_dict())

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_module_strict: bool = True,
                        **_kw) -> Tuple[Optional[str], Dict[str, Any]]:
        """Reference engine.py:3273."""
        from deepspeed_tpu.checkpoint.store import load_checkpoint as _load
        if self.offload_enabled:
            self._drain_host_step()
        if self._param_stream is not None:
            # tier mode: params land on the HOST (cpu backend) and seed the
            # file store — the whole point is they don't fit device HBM
            cpu0 = jax.local_devices(backend="cpu")[0]
            sds = jax.sharding.SingleDeviceSharding(cpu0)
            tmpl = jax.tree.map(
                lambda s: np.zeros(s.shape, s.dtype),
                self._param_stream._abstract)
            state, meta, tag = _load(
                load_dir, tag, {"params": tmpl},
                {"params": jax.tree.map(lambda _: sds, tmpl)},
                strict=frozenset({"params"}) if load_module_strict
                else frozenset())
            if state is None:
                return None, {}
            with jax.default_device(cpu0):
                self._param_stream._seed_store(
                    jax.tree.map(jnp.asarray, state["params"]))
            host_path = os.path.join(load_dir, tag, "host_optimizer.npz")
            if load_optimizer_states and os.path.exists(host_path):
                self.host_optimizer.load_state_dict(dict(np.load(host_path)))
            else:
                # cross-mode checkpoint: rebuild the tiered master from
                # the loaded params (moments start fresh)
                self.host_optimizer.init_from(state["params"])
            self._param_stream._reload_resident()
            self.global_steps = meta.get("global_steps", 0)
            self.micro_steps = meta.get("micro_steps", 0)
            self.global_samples = meta.get("global_samples", 0)
            self._restore_resume_state(meta)
            return tag, meta.get("client_state", {})
        shardings = {
            "params": self._param_shardings,
            "loss_scale": jax.tree.map(lambda _: self.plan.replicated(),
                                       self.loss_scale_state),
        }
        templates = {
            "params": self.params,
            "loss_scale": self.loss_scale_state,
        }
        if load_optimizer_states and not self.offload_enabled:
            # only assemble (and strict-check) device optimizer state when it
            # will actually be consumed — a params-only resume or a cross-mode
            # load (offload checkpoints carry host_optimizer.npz instead)
            # must not fail on opt_state leaves it would discard anyway
            templates["opt_state"] = self.opt_state
            shardings["opt_state"] = self._state_shardings
        # load_module_strict gates MODULE (params) strictness only, as in the
        # reference; optimizer-state completeness is never waived by it —
        # opting out of a structural params check must not silently accept a
        # truncated optimizer state
        strict = frozenset(templates) if load_module_strict \
            else frozenset(templates) - {"params"}
        state, meta, tag = _load(load_dir, tag, templates, shardings,
                                 strict=strict)
        if state is None:
            return None, {}
        self.params = state["params"]
        if load_optimizer_states and self.offload_enabled:
            host_path = os.path.join(load_dir, tag, "host_optimizer.npz")
            if os.path.exists(host_path):
                self.host_optimizer.load_state_dict(dict(np.load(host_path)))
            else:
                # checkpoint from a non-offload run: rebuild master from
                # the loaded params (universal reshape across offload modes)
                self.host_optimizer.init_from(self.params)
        elif load_optimizer_states and not self.offload_enabled:
            if "opt_state" in state:
                self.opt_state = state["opt_state"]
            elif not self._onebit_enabled:
                # offload-run checkpoint (optimizer lives in
                # host_optimizer.npz) loaded into a non-offload engine:
                # rebuild device state from the LOADED params — fresh
                # moments, master = restored weights (mirror of the
                # init_from branch above)
                log_dist("checkpoint has no device opt_state group — "
                         "rebuilding from loaded params (cross-mode resume)")
                self.opt_state = jax.jit(
                    self.optimizer.init,
                    out_shardings=self._state_shardings)(self.params)
        if "loss_scale" in state:
            ls = state["loss_scale"]
            self.loss_scale_state = self._replicate(
                LossScaleState(*jax.tree.leaves(ls))
                if not isinstance(ls, LossScaleState) else ls)
        self.global_steps = meta.get("global_steps", 0)
        self.micro_steps = meta.get("micro_steps", 0)
        self.skipped_steps = meta.get("skipped_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        if self.data_sampler is not None and meta.get("data_sampler"):
            self.data_sampler.load_state_dict(meta["data_sampler"])
        self._restore_resume_state(meta)
        return tag, meta.get("client_state", {})

    def _restore_resume_state(self, meta: Dict[str, Any]) -> None:
        """Restore the exact-resume extras (host rng key + dataloader
        cursor) from checkpoint meta. Older checkpoints simply lack the
        keys — resume still works, just without bitwise parity."""
        if meta.get("rng") is not None:
            self._rng = jnp.asarray(
                np.asarray(meta["rng"], dtype=np.uint32))
        if meta.get("dataloader") and self.training_dataloader is not None \
                and hasattr(self.training_dataloader, "load_state_dict"):
            self.training_dataloader.load_state_dict(meta["dataloader"])
            # drop any half-consumed iterator so the next train_batch
            # builds a fresh one starting AT the restored cursor
            self._data_iter = None


# ---------------------------------------------------------------------------
# initialize()
# ---------------------------------------------------------------------------

def initialize(model: Union[ModelSpec, Any] = None,
               config: Union[str, Dict[str, Any], DeepSpeedTPUConfig, None] = None,
               mesh: Optional[Mesh] = None,
               params: Optional[Pytree] = None,
               rng: Optional[jax.Array] = None,
               training_data=None,
               loss_fn: Optional[LossFn] = None,
               config_params=None,
               **_kw):
    """Reference deepspeed/__init__.py:78. Returns
    (engine, optimizer, dataloader, lr_scheduler) for API parity."""
    cfg = DeepSpeedTPUConfig.from_any(config if config is not None
                                      else config_params)
    spec = _coerce_model_spec(model, cfg, loss_fn)
    engine = DeepSpeedTPUEngine(spec, cfg, mesh=mesh, params=params, rng=rng,
                                training_data=training_data)
    return engine, engine.optimizer, engine.training_dataloader, \
        engine.lr_schedule


def _coerce_model_spec(model, cfg: DeepSpeedTPUConfig,
                       loss_fn: Optional[LossFn]) -> ModelSpec:
    if isinstance(model, ModelSpec):
        return model
    from deepspeed_tpu.models.transformer import DecoderConfig
    if isinstance(model, DecoderConfig):
        from deepspeed_tpu.runtime.model_factory import decoder_model_spec
        return decoder_model_spec(model, cfg)
    raise TypeError(
        "model must be a ModelSpec or a models.transformer.DecoderConfig; "
        f"got {type(model)}")
