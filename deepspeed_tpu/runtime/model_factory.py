"""Bridge from model configs to engine ModelSpecs.

Plays the role of the reference's module-injection policies
(module_inject/replace_module.py:189) — instead of mutating torch modules,
we compose the functional transformer core with the attention / MoE
implementation selected by the DeepSpeed config, and attach the sharding
plan (partition_specs) for AutoTP + ZeRO-3.
"""

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.config import DeepSpeedTPUConfig
from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import (DecoderConfig,
                                              cross_entropy_loss,
                                              dot_product_attention)
from deepspeed_tpu.utils.logging import logger


#: pluggable attention implementations (the analogue of the reference's
#: inference/v2/modules registry: config-selected layer impls behind a
#: stable interface). Users register a custom ``attn_fn(q, k, v, causal=,
#: q_offset=)`` and select it via ``attention_impl`` in the config.
_ATTENTION_REGISTRY = {}


def register_attention_impl(name: str, fn) -> None:
    """Reference inference/v2/modules registry (ConfigBundle → impl)."""
    _ATTENTION_REGISTRY[name] = fn


def select_attention(ds_cfg: DeepSpeedTPUConfig,
                     dec_cfg: Optional[DecoderConfig] = None):
    """Pick the attention implementation from the config (reference: the
    replace_with_kernel_inject seam + DistributedAttention wrapping,
    sequence/layer.py:331).

    ``attention_impl``: 'auto' → chunked-XLA flash-style attention (never
    materializes [T,T]; every op is an einsum XLA tiles onto the MXU —
    robust on all TPU runtimes); 'pallas_flash' → the Pallas kernel;
    'naive' → reference dot-product (tests/short seqs)."""
    import jax as _jax
    on_tpu = _jax.default_backend() == "tpu"
    sp = ds_cfg.sequence_parallel
    impl = ds_cfg.attention_impl
    if impl in _ATTENTION_REGISTRY:
        if sp.size > 1:
            # the builtin impls get ring/Ulysses wrapping below; silently
            # running a raw custom impl on sequence shards would compute
            # wrong attention — make the combination an explicit error
            raise ValueError(
                f"attention_impl '{impl}' (registered) does not compose "
                f"with sequence_parallel.size={sp.size}: custom impls "
                f"must handle the 'seq' axis themselves — register an "
                f"SP-aware fn or use a builtin impl")
        if dec_cfg is not None and dec_cfg.layer_window_pattern:
            # forward_hidden feeds a traced per-layer `window=` kwarg —
            # a registered impl with the documented (q, k, v, causal=,
            # q_offset=) signature would TypeError at trace time
            raise ValueError(
                f"attention_impl '{impl}' (registered) does not support "
                f"per-layer attention windows (layer_window_pattern); "
                f"use a builtin impl for GPT-Neo-class models")
        if dec_cfg is not None and (dec_cfg.pos_emb == "alibi"
                                    or dec_cfg.sliding_window is not None
                                    or not dec_cfg.causal):
            from deepspeed_tpu.utils.logging import warning_once
            kind = ("ALiBi" if dec_cfg.pos_emb == "alibi" else
                    "sliding-window" if dec_cfg.sliding_window is not None
                    else "bidirectional (encoder)")
            warning_once(
                f"attention_impl '{impl}' (registered) is used as-is for "
                f"a model with {kind} attention — the impl itself must "
                f"apply the bias/window/non-causal mask or results will "
                f"silently differ")
        return _ATTENTION_REGISTRY[impl]
    if impl not in ("auto", "pallas_flash", "xla_chunked", "naive",
                    "fpdt"):
        raise ValueError(
            f"unknown attention_impl '{impl}'; expected 'auto'|"
            f"'pallas_flash'|'xla_chunked'|'naive'|'fpdt' or a name "
            f"registered via register_attention_impl "
            f"({sorted(_ATTENTION_REGISTRY)})")
    if impl == "fpdt":
        # FPDT chunked attention (reference fpdt_layer.py:510): q-chunked
        # online softmax with the KV store in pinned host DRAM — the
        # 256K+ single-chip regime, where even the flash kernel's
        # backward transients ([T, q_dim] q/k/v + dq/dk/dv) overflow
        # HBM. DSTPU_FPDT_CHUNK tunes the q/KV chunk (default 4096).
        if sp.size > 1:
            raise ValueError(
                "attention_impl 'fpdt' composes with sequence parallel "
                "by chunking each shard's local sequence — but the SP "
                "wrappers are applied instead of it today; use "
                "'auto' with sequence_parallel, or fpdt on one chip")
        if dec_cfg is not None and (
                not dec_cfg.causal or dec_cfg.pos_emb == "alibi"
                or dec_cfg.sliding_window is not None
                or dec_cfg.layer_window_pattern):
            raise ValueError(
                "attention_impl 'fpdt' supports full-causal decoders "
                "only (no ALiBi/sliding-window/encoder)")
        from deepspeed_tpu.parallel.fpdt import fpdt_attention
        return partial(fpdt_attention,
                       chunk=int(os.environ.get("DSTPU_FPDT_CHUNK",
                                                4096)))
    if dec_cfg is not None and dec_cfg.layer_window_pattern:
        # per-layer alternating windows (GPT-Neo): the window is a traced
        # scalar fed from the layer scan, which only the masked reference
        # path supports — the static block-skip kernels need a
        # compile-time window
        if sp.size > 1:
            raise ValueError(
                "sequence_parallel with per-layer attention windows "
                "(layer_window_pattern) is not supported")
        if impl in ("pallas_flash", "xla_chunked"):
            # honor the explicit kernel choice with a loud error, not a
            # silent downgrade
            raise ValueError(
                f"attention_impl '{impl}' cannot apply per-layer traced "
                f"windows (layer_window_pattern); use 'auto' or 'naive' "
                f"for GPT-Neo-class models")
        return dot_product_attention
    if dec_cfg is not None and not dec_cfg.causal:
        # encoders (BERT): bidirectional attention. The Pallas flash
        # kernel and the SP wrappers are causal-only today — route to
        # the chunked-XLA path (full T² is inherent here anyway).
        if sp.size > 1:
            raise ValueError(
                "sequence_parallel with a bidirectional (encoder) model "
                "is not supported; use DP/TP for BERT-class models")
        if impl == "pallas_flash":
            raise ValueError(
                "attention_impl 'pallas_flash' is causal-only; use "
                "'auto'/'xla_chunked'/'naive' for encoder (BERT-class) "
                "models")
        if impl == "naive":
            return partial(dot_product_attention, causal=False)
        from deepspeed_tpu.ops.xla_attention import chunked_attention
        return partial(chunked_attention, causal=False)
    if dec_cfg is not None and dec_cfg.pos_emb == "alibi":
        # ALiBi (BLOOM) adds a per-head score bias; the Pallas flash
        # kernel has no bias port, and head-sharded SP would need the
        # matching slope slice per shard — route to the chunked-XLA path
        # (still never materializes [T,T]) with slopes baked in.
        if sp.size > 1:
            raise ValueError("sequence_parallel with an ALiBi model is "
                             "not supported; use DP/TP/PP for BLOOM-class "
                             "models")
        from deepspeed_tpu.models.transformer import alibi_slopes
        from deepspeed_tpu.ops.xla_attention import chunked_attention
        return partial(chunked_attention,
                       alibi=alibi_slopes(dec_cfg.num_heads))
    window = dec_cfg.sliding_window if dec_cfg is not None else None
    if window is not None and sp.size > 1:
        raise ValueError(
            "sequence_parallel with sliding-window attention is not "
            "supported yet (the ring/Ulysses wrappers assume full causal "
            "attention); unset sliding_window or sequence_parallel")
    if sp.size > 1 and sp.mode == "ring":
        from deepspeed_tpu.parallel.ring import ring_attention
        return partial(ring_attention, axis_name="seq")
    wkw = {} if window is None else {"window": window}
    if impl == "pallas_flash" or (impl == "auto" and on_tpu and
                                  not os.environ.get("DSTPU_NO_PALLAS_ATTN")):
        # mesh-aware Pallas flash kernel — the TPU default: measured
        # 56.7% (512-element blocks, 512 MB CE budget, bf16 chunk logits) vs 45.5% MFU for the chunked-XLA
        # path on the 1.27B seq-2048 bench (v5e); shard_map head-sharding over
        # ('model','seq') IS the Ulysses all-to-all when sp > 1.
        # Unsupported shapes fall back inside flash_attention_sharded.
        # Sliding-window models pass `window` through: the kernel skips
        # out-of-window key blocks entirely (T·window FLOPs, not T²).
        from deepspeed_tpu.ops.flash_attention import flash_attention_sharded
        return partial(flash_attention_sharded, **wkw) if wkw \
            else flash_attention_sharded
    if sp.size > 1:
        from deepspeed_tpu.parallel.ulysses import distributed_attention
        return partial(distributed_attention, axis_name="seq")
    if impl == "naive" or (impl == "auto" and not on_tpu):
        return partial(dot_product_attention, **wkw) if wkw \
            else dot_product_attention
    from deepspeed_tpu.ops.xla_attention import chunked_attention
    return partial(chunked_attention, **wkw) if wkw else chunked_attention


def select_moe(dec_cfg: DecoderConfig, ds_cfg: DeepSpeedTPUConfig):
    if not dec_cfg.num_experts:
        return None
    if ds_cfg.moe.impl == "dropless":
        if ds_cfg.moe.ep_size > 1:
            raise ValueError(
                "moe.impl='dropless' requires ep_size=1: dropless "
                "dispatch has data-dependent per-expert counts, which "
                "cannot cross an EP all-to-all with static shapes. Use "
                "the capacity impl for expert parallelism.")
        if ds_cfg.pipeline.stages > 1:
            raise ValueError(
                "moe.impl='dropless' does not compose with pipeline "
                "parallelism: the pipeline already runs layers inside a "
                "shard_map over 'pipe', and the dropless per-shard "
                "dispatch is itself a shard_map (nested manual meshes "
                "conflict, same restriction as PP+SP). Use the capacity "
                "impl with pipeline stages.")
        from deepspeed_tpu.parallel.moe import dropless_moe_layer
        return partial(dropless_moe_layer,
                       top_k=dec_cfg.num_experts_per_tok,
                       aux_loss_coef=ds_cfg.moe.aux_loss_coef,
                       norm_topk=dec_cfg.norm_topk_prob)
    from deepspeed_tpu.parallel.moe import moe_layer
    return partial(moe_layer,
                   top_k=dec_cfg.num_experts_per_tok,
                   capacity_factor=ds_cfg.moe.capacity_factor,
                   min_capacity=ds_cfg.moe.min_capacity,
                   drop_tokens=ds_cfg.moe.drop_tokens,
                   aux_loss_coef=ds_cfg.moe.aux_loss_coef,
                   ep_axis="expert" if ds_cfg.moe.ep_size > 1 else None,
                   norm_topk=dec_cfg.norm_topk_prob)


def decoder_model_spec(dec_cfg: DecoderConfig,
                       ds_cfg: DeepSpeedTPUConfig):
    """Build the engine ModelSpec for the flagship decoder family.

    Batch contract: {"input_ids": [B,T] int32, "labels": [B,T] int32
    (optional; defaults to shifted input_ids)}.
    """
    from deepspeed_tpu.runtime.engine import ModelSpec

    if dec_cfg.typed:
        raise NotImplementedError(
            "ds.initialize: training a typed layer stack (DecoderConfig."
            "layer_kinds: window and full attention layers, leading dense "
            "layers, latent attention, a parallel block, an expert share — "
            "mimo_v2, deepseek_v3, cohere2_moe, nemotron_h) is not built yet: no "
            "backward for the share's dispatch, no sharding plan for a "
            "list of layers. Serve it with RaggedInferenceEngineTPU")

    if (ds_cfg.moe.use_residual and dec_cfg.num_experts
            and not dec_cfg.moe_residual):
        # Residual-MoE via the DeepSpeed config knob (reference
        # moe/layer.py use_residual) — architecture flag, so it folds
        # into the model config before init/loss/specs are built
        import dataclasses
        dec_cfg = dataclasses.replace(dec_cfg, moe_residual=True)

    if ds_cfg.activation_checkpointing.ffn_chunk:
        # FPDT sequence-chunked MLP (memory knob, not architecture —
        # but the forward reads it from the model config)
        import dataclasses
        dec_cfg = dataclasses.replace(
            dec_cfg,
            ffn_chunk=int(ds_cfg.activation_checkpointing.ffn_chunk))

    attn_fn = select_attention(ds_cfg, dec_cfg)
    moe_fn = select_moe(dec_cfg, ds_cfg)
    remat = ds_cfg.activation_checkpointing.policy
    if ds_cfg.activation_checkpointing.cpu_checkpointing and \
            not remat.startswith("offload"):
        # reference cpu_checkpointing knob: checkpointed activations live
        # in host memory — map to the host-offload analogue of the chosen
        # recompute profile (models/transformer.resolve_remat_policy)
        upgraded = {"save_attn_out": "offload_save_attn_out",
                    "save_attn_kernel": "offload_save_attn_kernel",
                    "save_attn_qkv": "offload_attn_qkv"}.get(
            remat, "offload_full")
        logger.info(f"cpu_checkpointing: remat policy "
                    f"'{remat}' -> '{upgraded}' (host-DRAM activations)")
        remat = upgraded
    ce_budget = None if ds_cfg.chunked_ce_budget_mb is None \
        else int(ds_cfg.chunked_ce_budget_mb) * 1024 * 1024
    # values validated by the config model (Literal)
    ce_dtype = jnp.bfloat16 if ds_cfg.ce_logits_dtype in ("bf16",
                                                          "bfloat16") \
        else None

    def init_fn(rng):
        return transformer.init_params(dec_cfg, rng)

    # RTS (reference top1gating:225 use_rts): random capacity-slot
    # priority, keyed from the engine's per-step rng — only meaningful
    # when capacity can drop tokens
    use_rts = (moe_fn is not None and ds_cfg.moe.use_rts
               and ds_cfg.moe.drop_tokens
               and ds_cfg.moe.impl == "capacity")

    def _moe_for_step(rng):
        """moe_fn for one step: RTS-wrapped when enabled, raw otherwise
        (the ONE selection point for all three loss paths)."""
        return _rts_moe(rng) if use_rts else moe_fn

    def _rts_moe(rng):
        """Wrap moe_fn with a PER-LAYER rts key: the layer scan traces
        its body once, so per-layer variation must come from traced
        layer data — fold the step rng with a bitcast of one router
        element (distinct across layers; equal values would only make
        two layers share a permutation, never corrupt routing)."""
        def mf(c, p, x):
            # f32 upcast first: bf16 params bitcast to int16, not int32
            lk = jax.random.fold_in(rng, lax.bitcast_convert_type(
                p["router"].reshape(-1)[0].astype(jnp.float32),
                jnp.int32))
            return moe_fn(c, p, x, rts_key=lk)
        return mf

    # Model-health taps (telemetry/health.py): bake the static flag into
    # a REPLACED config instance used only by this loss_fn's forward —
    # init/specs/pipeline/param_stream/inference keep the untapped
    # dec_cfg and its 2-tuple forward contract. The flag never flips
    # mid-run, so every step traces the identical program.
    _hcfg = ds_cfg.telemetry.health
    health_taps = bool(_hcfg.enabled and _hcfg.activations)
    if health_taps:
        import dataclasses
        taps_cfg = dataclasses.replace(dec_cfg, health_taps=True)

    # ZeRO-3 chunked-overlap plan, filled in by the engine (which owns
    # the mesh + abstract params) via ModelSpec.configure_overlap; while
    # unset, loss_fn runs the plain monolithic layer scan
    _ovl = {"plan": None}

    def loss_fn(params, batch, rng):
        tokens = batch["input_ids"]
        if "labels" in batch:
            labels = batch["labels"]
        else:
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.full_like(tokens[:, :1], -100)], axis=1)
        mf = _moe_for_step(rng)
        # encoder extras (BERT): pad masking is correctness-critical for
        # bidirectional attention (decoder batches right-pad + label
        # -100, which the causal mask already handles)
        enc = {}
        if not dec_cfg.causal:
            if "attention_mask" in batch:
                enc["attention_mask"] = batch["attention_mask"]
            if "token_type_ids" in batch:
                enc["token_type_ids"] = batch["token_type_ids"]
        plan = _ovl["plan"]
        hstats = None
        if health_taps:
            hidden, aux, hstats = transformer.forward_hidden(
                taps_cfg, params, tokens, attn_fn=attn_fn, moe_fn=mf,
                remat_policy=remat,
                layer_loop=plan.layer_loop if plan is not None else None,
                **enc)
        else:
            hidden, aux = transformer.forward_hidden(
                dec_cfg, params, tokens, attn_fn=attn_fn, moe_fn=mf,
                remat_policy=remat,
                layer_loop=plan.layer_loop if plan is not None else None,
                **enc)
        loss = transformer.chunked_cross_entropy(dec_cfg, params, hidden,
                                                 labels,
                                                 budget_bytes=ce_budget,
                                                 logits_dtype=ce_dtype)
        total = loss + aux if moe_fn is not None else loss
        metrics = {}
        if moe_fn is not None:
            # satellite: surface load-balancing pressure as
            # train/aux_loss even without the health cadence
            metrics["aux_loss"] = aux
        if hstats is not None:
            metrics["health"] = hstats
        return (total, metrics) if metrics else total

    tp = ds_cfg.tensor_parallel.enabled
    mics = int(ds_cfg.zero_optimization.mics_shard_size or 0) > 1
    specs = transformer.partition_specs(
        dec_cfg, zero_stage=ds_cfg.zero_optimization.stage, tp=tp,
        mics=mics)

    pipeline_loss_fn = None
    pipeline_grad_fn = None
    stages = ds_cfg.pipeline.stages
    if stages > 1:
        from deepspeed_tpu.runtime.pipe.pipeline import (
            pipeline_partition_specs, pipelined_loss,
            pipelined_loss_and_grads_1f1b)
        # balanced partition for L % S != 0 (reference PipelineModule
        # partition_balanced, pipe/module.py:393): pad the stacked layers
        # to S·ceil(L/S) with zero (identity) layers and mask them — every
        # stage runs ceil(L/S) real-or-dummy layers, so the tick critical
        # path equals the reference's balanced split (max stage cost);
        # dummy layers are value-identity with exactly-zero grads.
        # Embed/head never imbalance stages here: both are computed
        # replicated across 'pipe' by construction (the reference weighs
        # them into the split because ITS stages own them exclusively).
        import math as _math
        _L = dec_cfg.num_layers
        _cap = _math.ceil(_L / stages)
        _pad = _cap * stages - _L
        pipe_layer_mask = None
        if _pad:
            import numpy as _np
            pipe_layer_mask = _np.arange(_cap * stages) < _L
            _base_init = init_fn

            def init_fn(rng):                            # noqa: F811
                p = dict(_base_init(rng))
                p["layers"] = jax.tree.map(
                    lambda a: jnp.pad(
                        a, [(0, _pad)] + [(0, 0)] * (a.ndim - 1)),
                    p["layers"])
                return p
            logger.info(
                f"pipeline: {_L} layers over {stages} stages — balanced "
                f"split via {_pad} masked padding layer(s), "
                f"{_cap}/stage critical path")
        if not dec_cfg.causal or not dec_cfg.prenorm:
            # the pipeline stages assume the pre-LN decoder layout
            # (final_norm leaf, causal attention); silently pipelining a
            # BERT would KeyError deep in the schedule
            raise ValueError(
                "pipeline parallelism does not support encoder "
                "(bidirectional / post-LN) models; use DP/TP for "
                "BERT-class models")
        if dec_cfg.layer_window_pattern:
            # pipeline stages build decoder_block without the per-layer
            # window feed — training would silently run full attention
            # on GPT-Neo's local layers
            raise ValueError(
                "pipeline parallelism does not support per-layer "
                "attention windows (layer_window_pattern); use DP/TP "
                "for GPT-Neo-class models")
        if ds_cfg.sequence_parallel.size > 1:
            # the SP attention wrappers are shard_maps over 'seq'; nesting
            # them inside the pipeline's partial-manual 'pipe' region
            # trips a JAX manual-axes conflict — an honest error beats a
            # cryptic trace (use PP×TP×DP, or SP without PP)
            raise ValueError(
                "pipeline parallelism does not compose with "
                "sequence_parallel yet; drop one of the two (PP composes "
                "with TP/DP/ZeRO; SP composes with TP/DP/ZeRO/EP)")
        if tp:
            # vocab-sharded embeddings inside the partial-manual 'pipe'
            # region hit an XLA SPMD gather-partitioning CHECK failure;
            # replicate embed/lm_head across 'model' under PP (vocab ~vd
            # is small next to the layer stack — the reference keeps
            # embeddings replicated per pipeline stage too, pipe/module.py
            # tied layers)
            from jax.sharding import PartitionSpec as _P
            def _drop_model(spec):
                return _P(*(None if a == "model" else a for a in spec))
            specs["embed"] = jax.tree.map(
                _drop_model, specs["embed"],
                is_leaf=lambda x: isinstance(x, _P))
            if "lm_head" in specs:
                specs["lm_head"] = _drop_model(specs["lm_head"])
        specs = pipeline_partition_specs(specs, stages)

        # the pipeline schedule is itself a shard_map; a nested
        # shard_map'd flash kernel can't run inside it — use the XLA
        # attention there (pallas-inside-pipeline is future work)
        from deepspeed_tpu.ops.flash_attention import flash_attention_sharded
        pipe_attn = dot_product_attention \
            if attn_fn is flash_attention_sharded else attn_fn

        def _pipe_labels(tokens, batch):
            if "labels" in batch:
                return batch["labels"]
            return jnp.concatenate(
                [tokens[:, :, 1:],
                 jnp.full_like(tokens[:, :, :1], -100)], axis=2)

        def pipeline_loss_fn(params, batch, rng):
            tokens = batch["input_ids"]            # [M, B, T]
            return pipelined_loss(dec_cfg, params, tokens,
                                  _pipe_labels(tokens, batch),
                                  attn_fn=pipe_attn,
                                  moe_fn=_moe_for_step(rng),
                                  remat_policy=remat or "full",
                                  num_stages=stages,
                                  ce_budget_bytes=ce_budget,
                                  ce_logits_dtype=ce_dtype,
                                  layer_mask=pipe_layer_mask)

        if ds_cfg.pipeline.schedule == "1f1b":
            def pipeline_grad_fn(params, batch, rng, scale):
                tokens = batch["input_ids"]        # [M, B, T]
                return pipelined_loss_and_grads_1f1b(
                    dec_cfg, params, tokens, _pipe_labels(tokens, batch),
                    scale=scale, attn_fn=pipe_attn,
                    moe_fn=_moe_for_step(rng),
                    remat_policy=remat or "full", num_stages=stages,
                    ce_budget_bytes=ce_budget, ce_logits_dtype=ce_dtype,
                    layer_mask=pipe_layer_mask)
        elif ds_cfg.pipeline.schedule != "gpipe":
            raise ValueError(
                f"pipeline.schedule must be '1f1b' or 'gpipe', got "
                f"'{ds_cfg.pipeline.schedule}'")

    configure_overlap = None
    zcfg = ds_cfg.zero_optimization
    if zcfg.overlap_comm and zcfg.stage == 3 and stages <= 1:
        def configure_overlap(mesh, abstract_params):
            """Engine hook: build the chunked-overlap plan once mesh and
            abstract params exist, and arm loss_fn with it. Returns the
            plan (or None when the mesh can't run the chunked path)."""
            from deepspeed_tpu.runtime.zero.overlap import build_overlap_plan
            plan = build_overlap_plan(
                mesh, specs["layers"], abstract_params["layers"], zcfg,
                num_experts=dec_cfg.num_experts or 0)
            _ovl["plan"] = plan
            if plan is not None:
                logger.info(plan.describe())
            return plan

    n = dec_cfg.num_params()
    return ModelSpec(init_fn=init_fn, loss_fn=loss_fn,
                     partition_specs=specs,
                     flops_per_token=6.0 * n,
                     tokens_per_sample=dec_cfg.max_seq_len,
                     pipeline_loss_fn=pipeline_loss_fn,
                     pipeline_grad_fn=pipeline_grad_fn,
                     decoder_config=dec_cfg,
                     configure_overlap=configure_overlap)
