"""Functional decoder-only transformer core.

This is the flagship model family of deepspeed_tpu, playing the role the
reference's injected/containers model zoo plays for DeepSpeed
(module_inject/containers/*, inference/v2/model_implementations/*) — but
designed TPU-first:

- parameters are a plain pytree; per-layer weights are **stacked** on a
  leading ``layers`` axis and the block is applied with ``lax.scan`` →
  constant-size HLO regardless of depth, fast compiles, and natural
  pipeline-stage splitting;
- every parameter has a ``PartitionSpec`` produced by
  :func:`partition_specs`, composing tensor-parallel sharding (over the
  ``model`` axis — the AutoTP analogue of module_inject/auto_tp.py) with
  ZeRO-3/FSDP sharding (over ``data``+``expert``);
- attention is pluggable: local (reference jnp), Ulysses all-to-all
  (deepspeed/sequence/layer.py analogue), or ring attention — selected by
  the engine from the config;
- supports GPT-2 (learned pos, LayerNorm, gelu MLP, biases) and Llama
  (RoPE, RMSNorm, SwiGLU, no biases, GQA) families from one code path;
- the layer parts carry ``jax.named_scope``s from ONE fixed vocabulary
  (``telemetry/explain.SCOPE_VOCABULARY``: ``embed``, ``norm``,
  ``attn_qkv``, ``attn_core``, ``attn_out``, ``mlp``, ``moe``,
  ``lm_head``, ``loss`` here), shared by the trainer and the servers, so
  a device trace can be summed under the program's own words
  (``compile_monitor.scopes``). A scope lives in HLO metadata only: it
  changes no program and costs nothing at run time.
"""

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax import lax
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


#: the layer kinds whose mixer carries a state a sequence (ops/ssm.py): 3 a
#: Mamba-2 mixer, 4 a Mamba-1 selective scan, 5 a gated short convolution
#: (its state is the convolution's tail and nothing else), 6 a gated delta
#: rule (a matrix a head that a step decays AND corrects)
STATE_SPACE_KINDS = (3, 4, 5, 6)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None     # GQA; None => num_heads
    intermediate_size: Optional[int] = None  # None => 4*hidden (gelu) / llama default
    max_seq_len: int = 1024
    norm: str = "layernorm"                # 'layernorm' | 'rmsnorm'
    #: 'gelu' (tanh approx — HF gelu_new/gelu_pytorch_tanh) | 'gelu_exact'
    #: (erf — HF "gelu": Falcon, NeoX) | 'relu' | 'silu_glu' (Llama
    #: SwiGLU) | 'gelu_glu' (Gemma GeGLU)
    #: | 'relu2' (``relu(x)²``, no gate: Nemotron-H's experts, typed stacks)
    activation: str = "gelu"
    pos_emb: str = "learned"               # 'learned' | 'rope' | 'alibi'
    rope_theta: float = 10000.0
    use_bias: bool = True
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    #: parallel residual (GPT-J/NeoX/Falcon/Phi): h = x + attn(...) +
    #: mlp(...)
    parallel_block: bool = False
    #: 1 = ONE shared pre-norm feeds both branches (GPT-J / Falcon-7B /
    #: Phi); 2 = separate input/post_attention norms on x (GPT-NeoX /
    #: Pythia / Falcon-40B new_decoder_architecture)
    parallel_block_norms: int = 1
    #: LayerNorm bias independent of linear biases (Falcon: bias-less
    #: linears but LNs WITH bias). None → follow use_bias.
    norm_bias: Optional[bool] = None
    #: attention-projection biases independent of the MLP/LN biases
    #: (GPT-J: biased fc_in/fc_out/LN but bias-less q/k/v/out_proj).
    #: None → follow use_bias.
    attn_bias: Optional[bool] = None
    #: partial rotary (GPT-NeoX rotary_pct / GPT-J rotary_dim): RoPE on
    #: the first rotary_pct of each head's dims, pass-through on the rest
    rotary_pct: float = 1.0
    #: out-projection bias decoupled from the q/k/v biases (GPT-Neo:
    #: bias-less q/k/v but biased out_proj). None → follow qkv_bias.
    attn_out_bias: Optional[bool] = None
    #: per-layer attention windows tiled over depth (GPT-Neo
    #: attention_types: (0, 256) = alternating global/local-256; 0 means
    #: full causal). Routes to the masked attention path — the static
    #: block-skip kernels keep using ``sliding_window``.
    layer_window_pattern: Optional[Tuple[int, ...]] = None
    # MoE (used by mixtral preset; dense when num_experts == 0)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    #: normalize the selected top-k routing probs (Mixtral True;
    #: Qwen2-MoE ships norm_topk_prob False — raw softmax values)
    norm_topk_prob: bool = True
    #: Qwen2-MoE/DeepSeek shared expert: a dense MLP of this
    #: intermediate size runs on EVERY token alongside the routed
    #: experts (0 = none)
    shared_expert_size: int = 0
    #: sigmoid(x @ gate) scaling on the shared expert output (Qwen2-MoE)
    shared_expert_gate: bool = False
    #: DeepSpeed Residual-MoE (PR-MoE's "R"; reference moe/layer.py
    #: use_residual): every MoE layer also runs a DENSE MLP and the two
    #: outputs are mixed by a learned per-token 2-way softmax coefficient
    #: — out = moe·c₀ + mlp·c₁. Unlike the shared expert (additive,
    #: Qwen2-MoE) the mixture is convex and learned per token.
    moe_residual: bool = False
    # initializer
    init_std: float = 0.02
    #: decoupled head dim (Gemma head_dim=256 with H*Dh != hidden);
    #: None → hidden_size // num_heads
    head_dim_override: Optional[int] = None
    #: Gemma2 final_logit_softcapping: logits = c*tanh(logits/c); 0 = off
    logit_softcap: float = 0.0
    #: Gemma: scale token embeddings by sqrt(hidden) after lookup
    scale_embeddings: bool = False
    #: BLOOM word_embeddings_layernorm: a norm between embed and block 0
    embed_norm: bool = False
    #: causal sliding-window attention (Mistral SWA): each query sees at
    #: most the last `sliding_window` keys; None = full causal
    sliding_window: Optional[int] = None
    #: untied lm_head carries a bias vector (HF Phi's ``lm_head.bias``)
    lm_head_bias: bool = False
    #: model-health stat taps (telemetry/health.py): the scan body emits
    #: a per-layer stats dict (aux_loss, activation RMS/absmax, MoE
    #: expert load + routing entropy) instead of the scalar aux, and
    #: ``forward_hidden`` returns it stacked [L] as a third output.
    #: Trace-time static — only the training loss_fn ever sets it (on a
    #: replaced config instance), so inference/pipeline callers keep the
    #: 2-tuple contract.
    health_taps: bool = False
    #: False → bidirectional (encoder: BERT/DistilBERT). The reference's
    #: encoder containers are module_inject/containers/bert.py and
    #: distil_bert.py; here encoders are the same scan core with the
    #: causal mask dropped.
    causal: bool = True
    #: False → post-LN residuals (original-transformer/BERT order:
    #: h = LN(x + sublayer(x))); True → pre-LN (GPT-2/Llama). Post-LN
    #: models have no final norm — the last block's output LN is it.
    prenorm: bool = True
    #: >0 → segment/token-type embeddings (BERT); adds an
    #: ``embed["token_type"]`` leaf added before the embed norm
    type_vocab_size: int = 0
    #: BERT masked-LM head: transform dense+gelu+LN before the tied
    #: decode, plus a vocab bias (HF cls.predictions.*)
    mlm_head: bool = False
    #: FPDT sequence-chunked dense MLP (reference fpdt_layer.py:1056,
    #: set from activation_checkpointing.ffn_chunk): >0 runs the MLP in
    #: ffn_chunk-token tiles under remat so its [T, ffn] activations
    #: never materialize — the 128K+ single-chip memory knob. Applies
    #: to the dense MLP path only (MoE layers dispatch per token
    #: already); inference paths ignore it (decode is 1 token).
    ffn_chunk: int = 0
    # -- typed layers (models/typed_layers.py; ROADMAP C2) ------------------
    #: one entry a layer, 0 = full causal attention, 1 = window attention
    #: (MiMo-V2 ``hybrid_layer_pattern``), 2 = latent attention (DeepSeek-V3
    #: MLA: the ``*_lora_rank`` / ``qk_*_head_dim`` widths below; a latent
    #: stack is all latent), 3 = a Mamba-2 state-space mixer (the ``ssm_*``
    #: widths below; what it carries is a fixed-size state a sequence, not
    #: pages), 4 = a Mamba-1 SELECTIVE-SCAN mixer (Jamba's: a step size a
    #: channel through ``ssm_dt_rank``, ``ssm_inner_size`` channels of
    #: ``ssm_state_size`` states; a stack has kind 3 or kind 4, not both),
    #: 5 = a GATED SHORT CONVOLUTION (LFM2's: ``ssm_conv_kernel`` taps over
    #: ``hidden_size`` channels between two gates; it carries the taps' last
    #: inputs and NO state; a stack with kind 5 has neither kind 3 nor 4),
    #: 6 = a GATED DELTA-RULE mixer (Qwen3-Next's linear attention:
    #: ``ssm_heads`` value heads of ``ssm_head_dim`` over ``ssm_groups`` key
    #: heads of ``ssm_state_size``; it carries a ``[key, value]`` matrix a
    #: value head that each step decays and corrects; alone of the
    #: recurrent kinds in its stack),
    #: -1 = NO mixer: the layer is its feed-forward part alone,
    #: under the layer's one norm (Nemotron-H's ``E`` layers).
    #: Set → the stack is NOT one
    #: scanned block: ``params["layers"]`` is a list of per-layer trees
    #: whose shapes follow the layer's kind, ``sliding_window`` /
    #: ``window_*`` describe the window kind only, and ``num_kv_heads`` /
    #: ``rope_theta`` the full kind. None → the uniform stack.
    layer_kinds: Optional[Tuple[int, ...]] = None
    #: one entry a layer, 1 = sparse experts, 0 = a dense MLP of
    #: ``dense_intermediate_size`` (MiMo-V2 ``moe_layer_freq``: leading
    #: dense layers), -1 = NO feed-forward part: the layer is its mixer
    #: alone (Nemotron-H's ``M`` and ``*`` layers). None with
    #: ``layer_kinds`` set → every layer follows ``num_experts``.
    layer_sparse: Optional[Tuple[int, ...]] = None
    window_kv_heads: Optional[int] = None      #: None → ``kv_heads``
    window_rope_theta: Optional[float] = None  #: None → ``rope_theta``
    #: a learned logit per query head that joins the softmax of a window
    #: layer as one more column: it takes mass and gives no value
    window_sink: bool = False
    #: value heads narrower than query/key heads; None → ``head_dim``
    v_head_dim: Optional[int] = None
    #: V = value_scale * (x @ wv)
    value_scale: float = 1.0
    #: width of the dense layers of ``layer_sparse``; None → ``ffn_size``
    dense_intermediate_size: Optional[int] = None
    #: router scores: 'softmax' over all experts (Mixtral) | 'sigmoid'
    #: per expert, renormalised over the selected (DeepSeek-V3 / MiMo-V2)
    router_scoring: str = "softmax"
    #: a per-expert bias added to the scores for the SELECTION only; the
    #: weights stay the unbiased scores (``topk_method: noaux_tc``)
    router_select_bias: bool = False
    #: expert-parallel share: (first expert held, experts held). The
    #: router keeps ``num_experts`` outputs and ``num_experts_per_tok``;
    #: the expert weights hold the share, and the layer computes the part
    #: of the result its own experts give (parallel/moe.py). None → all.
    experts_held: Optional[Tuple[int, int]] = None
    #: group-limited selection (DeepSeek-V3 ``n_group`` / ``topk_group``):
    #: the router's experts in ``router_groups`` equal groups, a group
    #: scored by the sum of its two highest picks, the top-k taken inside
    #: the ``router_groups_kept`` best groups. 1 → a flat top-k.
    router_groups: int = 1
    router_groups_kept: int = 1
    #: ``routed_scaling_factor``: multiplies the kept (normalised) weights
    routed_scale: float = 1.0
    #: what a sigmoid router adds to the kept scores' sum before it divides
    #: by it (``norm_topk_prob``): DeepSeek-V3's 1e-20, LFM2's 1e-6
    router_norm_eps: float = 1e-20
    #: an RMSNorm over each q and k HEAD (one learned scale of ``head_dim``
    #: each, shared by the heads) BEFORE the rotary term, on the attention
    #: kinds 0 and 1 of a typed stack (LFM2's ``q_layernorm`` / ``k_layernorm``)
    qk_head_norm: bool = False
    #: the attention kinds 0 and 1 of a typed stack gate their heads'
    #: outputs: ``o ← o ⊙ σ(h·W_gate)``, a gate a query head and dim from the
    #: layer's normed input (Qwen3-Next's ``q_proj`` is twice as wide; the
    #: tree holds its gate half as ``wq_gate``)
    attn_output_gate: bool = False
    # -- latent attention (kind 2): the cache holds ONE row a token,
    # [RMSNorm(c_kv) (kv_lora_rank) ; RoPE(k_r) (qk_rope_head_dim)], read by
    # every head; ``head_dim`` = qk_nope + qk_rope, ``v_head_dim`` the V head
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    #: YaRN rotary scaling (HF ``rope_scaling`` with ``rope_type: yarn``):
    #: (factor, original_max_position_embeddings, beta_fast, beta_slow,
    #: mscale, mscale_all_dim). None → plain ``theta ** (-i / half)``.
    rope_yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    # -- learned sparse attention over the latent cache (DeepSeek-V3.2's
    # indexer, GLM-5.2's ``glm_moe_dsa``; typed_layers.py has the equations):
    # a query reads only the ``index_topk`` cached rows its layer's indexer
    # scores highest. ``layer_indexer``: one entry a layer, 1 = the layer
    # OWNS an indexer (``index_heads`` heads of ``index_head_dim``, ONE key
    # of that width a token, cached in a pool of its own), 0 = it BORROWS
    # the picks of the nearest owner below it. None → every key is read
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    layer_indexer: Optional[Tuple[int, ...]] = None
    # -- Cohere2-MoE's block on the typed stack (``parallel_block`` above) --
    #: rotary pairs are NEIGHBOURS ``(2i, 2i+1)`` (GPT-J's convention;
    #: Cohere ``position_embedding_type: rope_gptj``), not the two halves
    rope_interleaved: bool = False
    #: False → the FULL kind (0) of a typed stack has no positional term at
    #: all (Cohere2's global layers); the window kind keeps its rotary
    full_attn_rope: bool = True
    #: the shared GLU of ``shared_expert_size`` is this many shared experts
    #: side by side and its output their MEAN (Cohere2-MoE
    #: ``shared_expert_combination_strategy: average``); 1 → the sum
    shared_experts_averaged: int = 1
    # -- state-space layers (kind 3; ops/ssm.py): ``ssm_heads`` heads of
    # ``ssm_head_dim`` (their product is the mixer's inner width, whatever
    # the hidden size), B and C in ``ssm_groups`` groups of
    # ``ssm_state_size``, a causal depthwise convolution of
    # ``ssm_conv_kernel`` taps over [x | B | C]
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 4
    # -- selective-scan layers (kind 4; Mamba-1): ``ssm_inner_size`` channels
    # with NO heads and NO groups — every channel its own step size, through
    # a bottleneck of ``ssm_dt_rank`` — of ``ssm_state_size`` states each; the
    # convolution runs over the channels alone
    ssm_inner_size: int = 0
    ssm_dt_rank: int = 0
    # -- Granite's four scalar multipliers (``hf_loader``:
    # ``granitemoehybrid``); 1.0 / None add no operation to a program --
    #: the token embedding times this on the way in (a free factor:
    #: ``scale_embeddings`` is Gemma's sqrt(hidden))
    embedding_multiplier: float = 1.0
    #: each branch sum (the mixer's, the feed-forward's) times this before
    #: it joins the residual stream (typed stacks: ``tl.block_residual``)
    residual_multiplier: float = 1.0
    #: the head's logits DIVIDED by this on the way out
    logits_scaling: float = 1.0
    #: the scores' factor where the model states one; None →
    #: ``head_dim ** -0.5`` (:attr:`attn_scale`)
    attention_multiplier: Optional[float] = None
    # -- manifold-constrained hyper-connections (mHC; Xing4.0's ``xing4_0``;
    # typed_layers.py has the equations): the residual stream between the
    # layers of a typed stack is ``hc_mult`` hidden states a token, and
    # every sublayer reads a learned, token-dependent mix of them and writes
    # back through a doubly stochastic ``hc_mult x hc_mult`` matrix
    # (``hc_sinkhorn_iters`` rounds of column / row normalisation, each sum
    # + ``hc_eps``, from ``exp`` of logits clipped to ``hc_res_clamp``).
    # 1 → ONE hidden state a token, ``x + out``: no operation is added
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    def __post_init__(self):
        if self.mlm_head and not self.tie_embeddings:
            # the MLM decode is defined as tied-embedding + vocab bias
            # (HF cls.predictions.decoder); an untied lm_head would make
            # lm_logits and the chunked-CE loss decode different heads
            raise ValueError("mlm_head requires tie_embeddings=True")
        for name in ("layer_kinds", "layer_sparse", "layer_indexer"):
            per_layer = getattr(self, name)
            if per_layer is not None and len(per_layer) != self.num_layers:
                raise ValueError(
                    f"{name} has {len(per_layer)} entries for "
                    f"{self.num_layers} layers")
        if self.layer_sparse is not None and self.layer_kinds is None:
            raise ValueError("layer_sparse needs layer_kinds (the typed "
                             "stack); the uniform stack is all-dense or "
                             "all-sparse by num_experts")
        if self.layer_kinds is not None and 2 in self.layer_kinds and \
                (set(self.layer_kinds) != {2} or not (
                    self.q_lora_rank and self.kv_lora_rank and
                    self.qk_nope_head_dim and self.qk_rope_head_dim)):
            raise ValueError(
                "latent attention (layer kind 2) needs q_lora_rank, "
                "kv_lora_rank, qk_nope_head_dim and qk_rope_head_dim, and a "
                "stack whose layers are all latent")
        if self.layer_indexer is not None and not (
                self.latent and self.layer_indexer[0] == 1 and
                self.index_heads and self.index_head_dim and
                self.index_topk and
                self.index_head_dim >= self.qk_rope_head_dim):
            raise ValueError(
                "layer_indexer (learned sparse attention) needs a latent "
                "stack, index_heads, index_head_dim (at least "
                "qk_rope_head_dim: its leading dims are rotated) and "
                "index_topk, and a first layer that owns its indexer (a "
                "borrower takes the picks of an owner BELOW it)")
        if self.typed and 3 in self.layer_kinds and not (
                self.ssm_heads and self.ssm_head_dim and self.ssm_state_size
                and self.ssm_heads % self.ssm_groups == 0):
            raise ValueError(
                "a state-space layer (layer kind 3) needs ssm_heads, "
                "ssm_head_dim and ssm_state_size, and ssm_groups has to "
                "divide ssm_heads")
        if self.selective and not (
                self.ssm_inner_size and self.ssm_dt_rank and
                self.ssm_state_size and 3 not in self.layer_kinds):
            raise ValueError(
                "a selective-scan layer (layer kind 4) needs ssm_inner_size, "
                "ssm_dt_rank and ssm_state_size, and a stack without layers "
                "of kind 3 (the ssm_* widths describe one kind of scan)")
        if self.short_conv and set(self.layer_kinds) & {3, 4}:
            raise ValueError(
                "a gated short convolution (layer kind 5) needs a stack "
                "without layers of kinds 3 and 4 (ssm_conv_kernel and the "
                "convolution's pool describe one kind of mixer)")
        if self.delta_rule and (set(self.layer_kinds) & {3, 4, 5} or not (
                self.ssm_heads and self.ssm_head_dim and self.ssm_state_size
                and self.ssm_heads % self.ssm_groups == 0)):
            raise ValueError(
                "a gated delta-rule layer (layer kind 6) needs ssm_heads "
                "(value heads), ssm_head_dim, ssm_state_size (the key head's "
                "width) and ssm_groups (key heads) dividing ssm_heads, and a "
                "stack without layers of kinds 3, 4 and 5 (the ssm_* widths "
                "describe one kind of mixer)")
        if self.layer_kinds is not None and any(
                kind == -1 and not self.layer_has_ffn(l)
                for l, kind in enumerate(self.layer_kinds)):
            raise ValueError("a layer with no mixer (layer kind -1) and no "
                             "feed-forward part (layer_sparse -1) is empty")
        if self.hc_mult < 1 or (self.hc_mult > 1 and not (
                self.typed and self.hc_sinkhorn_iters >= 1 and
                not self.parallel_block and
                self.residual_multiplier == 1.0 and all(
                    kind >= 0 and self.layer_has_ffn(l)
                    for l, kind in enumerate(self.layer_kinds)))):
            raise ValueError(
                "hc_mult (a residual stream of several hidden states) is at "
                "least 1; over 1 it needs a typed stack of sequential "
                "two-part layers (a mixer AND a feed-forward part, each "
                "with its own maps; no residual multiplier) and "
                "hc_sinkhorn_iters >= 1")
        if self.num_experts and self.num_experts % self.router_groups:
            raise ValueError(f"router_groups {self.router_groups} does not "
                             f"divide num_experts {self.num_experts}")

    @property
    def typed(self) -> bool:
        """The stack is a list of typed layers, not one scanned block."""
        return self.layer_kinds is not None

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def num_held_experts(self) -> int:
        return self.experts_held[1] if self.experts_held else \
            self.num_experts

    @property
    def latent(self) -> bool:
        """The stack's layers are latent attention layers (kind 2)."""
        return self.typed and 2 in self.layer_kinds

    @property
    def picks_keys(self) -> bool:
        """The latent layers read the keys an indexer picks
        (``layer_indexer``), not every cached row."""
        return self.layer_indexer is not None

    def layer_owns_indexer(self, layer: int) -> bool:
        """The layer scores and picks for itself and for the borrowers
        above it (``layer_indexer`` 1); False: it borrows, or the stack
        picks nothing."""
        return self.picks_keys and self.layer_indexer[layer] == 1

    @property
    def indexer_layers(self) -> int:
        """Layers that own an indexer: the regions of the index-key pool."""
        return sum(self.layer_indexer) if self.picks_keys else 0

    @property
    def selective(self) -> bool:
        """The stack's state-space layers are Mamba-1 selective scans
        (kind 4)."""
        return self.typed and 4 in self.layer_kinds

    @property
    def short_conv(self) -> bool:
        """The stack's recurrent layers are gated short convolutions (kind
        5): a sequence carries their taps' last inputs and no state."""
        return self.typed and 5 in self.layer_kinds

    @property
    def delta_rule(self) -> bool:
        """The stack's recurrent layers are gated delta-rule mixers (kind
        6): the ``ssm_*`` widths read as value heads (``ssm_heads`` of
        ``ssm_head_dim``) over key heads (``ssm_groups`` of
        ``ssm_state_size``), and the convolution runs over ``[q | k | v]``."""
        return self.typed and 6 in self.layer_kinds

    @property
    def recurrent(self) -> bool:
        """The stack holds state-space layers (``STATE_SPACE_KINDS``): a
        sequence carries a recurrent state beside its pages, and a prefix of
        its pages alone is NOT a prefix of the sequence."""
        return self.typed and any(kind in STATE_SPACE_KINDS
                                  for kind in self.layer_kinds)

    @property
    def ssm_inner(self) -> int:
        """A state-space mixer's inner width: ``d = H·P``, or a selective
        scan's channels."""
        return self.ssm_inner_size or self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the mixer's convolution runs over: ``[x | B | C]``; a
        selective scan's: the channels alone; a gated short convolution's:
        the hidden size."""
        if self.short_conv:
            return self.hidden_size
        if self.ssm_inner_size:
            return self.ssm_inner_size
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def latent_dim(self) -> int:
        """Values a latent layer caches a token: the normed latent and the
        one rotary key every head shares."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        """The scores' factor: ``attention_multiplier`` where the model
        states one, else ``head_dim ** -0.5``, times YaRN's
        ``mscale(factor, mscale_all_dim) ** 2`` where that is set (HF
        ``deepseek_v3``: the sin / cos carry ``mscale / mscale_all_dim``,
        the softmax the rest)."""
        scale = self.head_dim ** -0.5 if self.attention_multiplier is None \
            else float(self.attention_multiplier)
        if self.rope_yarn is not None and self.rope_yarn[5]:
            scale *= yarn_mscale(self.rope_yarn[0], self.rope_yarn[5]) ** 2
        return scale

    def kind_kv_heads(self, kind: int) -> int:
        return (self.window_kv_heads or self.kv_heads) if kind == 1 \
            else self.kv_heads

    def kind_rope_theta(self, kind: int) -> Optional[float]:
        """The kind's rotary base; None: the kind has no positional term."""
        if kind == 1:
            return self.window_rope_theta or self.rope_theta
        if kind not in (0, 2):      # no attention in the layer
            return None
        return self.rope_theta if self.full_attn_rope else None

    def kind_window(self, kind: int) -> Optional[int]:
        return self.sliding_window if kind == 1 else None

    def layer_is_sparse(self, layer: int) -> bool:
        if self.layer_sparse is not None:
            return self.layer_sparse[layer] == 1
        return bool(self.num_experts)

    def layer_has_ffn(self, layer: int) -> bool:
        """False: the layer is its mixer alone (``layer_sparse`` -1)."""
        return self.layer_sparse is None or self.layer_sparse[layer] >= 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def has_final_norm(self) -> bool:
        return self.prenorm

    def window_per_layer(self):
        """``layer_window_pattern`` tiled over depth as a plain list
        (0 = full causal) — the ONE home for the expansion, shared by
        the forward scan and the HF export."""
        pat = self.layer_window_pattern
        return [pat[i % len(pat)] for i in range(self.num_layers)]

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_heads

    @property
    def q_dim(self) -> int:
        """Total query width H*Dh (== hidden_size unless head_dim is
        decoupled, Gemma-style)."""
        return self.num_heads * self.head_dim

    @property
    def is_glu(self) -> bool:
        return self.activation.endswith("_glu")

    @property
    def qkv_bias(self) -> bool:
        return self.use_bias if self.attn_bias is None else self.attn_bias

    @property
    def out_bias(self) -> bool:
        return self.qkv_bias if self.attn_out_bias is None \
            else self.attn_out_bias

    @property
    def ln_bias(self) -> bool:
        if self.norm != "layernorm":
            return False
        return self.use_bias if self.norm_bias is None else self.norm_bias

    @property
    def has_ln2(self) -> bool:
        return (not self.parallel_block) or self.parallel_block_norms == 2

    @property
    def rope_dim(self) -> int:
        """Dims per head that get RoPE (even; rotary_pct of head_dim; a
        latent layer's ``qk_rope_head_dim``)."""
        if self.qk_rope_head_dim:
            return self.qk_rope_head_dim
        r = int(self.head_dim * self.rotary_pct)
        return r - (r % 2)

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        if self.is_glu:
            return int(8 * self.hidden_size / 3 // 128 * 128) or 4 * self.hidden_size
        return 4 * self.hidden_size

    def num_params(self) -> int:
        """Approximate parameter count (used for MFU accounting)."""
        d, v, l = self.hidden_size, self.vocab_size, self.num_layers
        h = self.ffn_size
        attn = d * self.q_dim + 2 * d * self.kv_heads * self.head_dim \
            + self.q_dim * d + (d * self.q_dim if self.attn_output_gate else 0)
        if self.is_glu:
            mlp = 3 * d * h
        else:
            mlp = 2 * d * h
        dense_mlp = mlp
        if self.num_experts:
            mlp = mlp * self.num_experts + d * self.num_experts  # + router
            if self.shared_expert_size:
                mlp += 3 * d * self.shared_expert_size \
                    + (d if self.shared_expert_gate else 0)
            if self.moe_residual:
                mlp += dense_mlp + 2 * d + 2   # dense MLP + coefficient
        per_layer = attn + mlp + 2 * d
        layers = l * per_layer
        if self.recurrent:
            # a hybrid stack: a state-space layer's mixer is its two
            # projections, convolution and per-head vectors; a layer may
            # have no mixer or no feed-forward part
            ssm = d * (self.ssm_inner + self.ssm_conv_dim + self.ssm_heads) \
                + self.ssm_inner * d + self.ssm_inner \
                + self.ssm_conv_dim * (self.ssm_conv_kernel + 1) \
                + 3 * self.ssm_heads
            if self.selective:
                # in / out, the convolution, W_x and its three norms, W_dt
                # and its bias, A and D
                di, n, r = self.ssm_inner, self.ssm_state_size, \
                    self.ssm_dt_rank
                ssm = 3 * d * di + di * (self.ssm_conv_kernel + 1) \
                    + (di + 1) * (r + 2 * n) + (r + 1) * di + di * (n + 1)
            if self.short_conv:     # in (three blocks), out, the taps
                ssm = 4 * d * d + d * self.ssm_conv_kernel
            if self.delta_rule:
                # [q | k | v | z] and [b | a] in, out, the taps, the decay's
                # two vectors a value head, the gated norm's one scale a dim
                ssm = d * (self.ssm_inner + self.ssm_conv_dim +
                           2 * self.ssm_heads) + self.ssm_inner * d \
                    + self.ssm_conv_dim * self.ssm_conv_kernel \
                    + 2 * self.ssm_heads + self.ssm_head_dim
            layers = sum(
                (ssm if kind in STATE_SPACE_KINDS else attn if kind >= 0
                 else 0)
                + ((mlp if self.layer_is_sparse(i) else dense_mlp) + d
                   if self.layer_has_ffn(i) else 0)
                + (d if kind >= 0 or not self.layer_has_ffn(i) else 0)
                for i, kind in enumerate(self.layer_kinds))
        emb = v * d + (self.max_seq_len * d if self.pos_emb == "learned"
                       else 0) + self.type_vocab_size * d
        head = 0 if self.tie_embeddings else v * d + (v if self.lm_head_bias
                                                      else 0)
        if self.mlm_head:
            head += d * d + 3 * d + v
        return layers + emb + head + d

    def num_active_params(self) -> int:
        """Parameters touched per token (== num_params for dense models;
        MoE counts experts_per_tok of the num_experts expert MLPs) — the
        correct basis for MoE MFU/FLOPs accounting."""
        if not self.num_experts:
            return self.num_params()
        d, h = self.hidden_size, self.ffn_size
        expert = (3 if self.is_glu else 2) * d * h
        inactive = (self.num_experts - self.num_experts_per_tok) * expert
        sparse = sum(self.layer_is_sparse(i) for i in range(self.num_layers)
                     ) if self.recurrent else self.num_layers
        return self.num_params() - sparse * inactive


# ---------------------------------------------------------------------------
# Normalization (Pallas-accelerated versions live in deepspeed_tpu/ops)
# ---------------------------------------------------------------------------

@jax.named_scope("norm")
def _norm(cfg: DecoderConfig, params: Params, x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * lax.rsqrt(var + cfg.norm_eps) * params["scale"]
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) * lax.rsqrt(var + cfg.norm_eps) * params["scale"]
        if "bias" in params:
            out = out + params["bias"]
    return out.astype(x.dtype)


def _norm_params(cfg: DecoderConfig, shape_prefix=()) -> Params:
    p = {"scale": jnp.ones(shape_prefix + (cfg.hidden_size,), jnp.float32)}
    if cfg.ln_bias:
        p["bias"] = jnp.zeros(shape_prefix + (cfg.hidden_size,), jnp.float32)
    return p


@jax.named_scope("embed")
def embed_tokens(cfg: DecoderConfig, em: Params, tokens: jax.Array,
                 positions: jax.Array,
                 embed_norm: Optional[Params] = None,
                 token_type_ids: Optional[jax.Array] = None) -> jax.Array:
    """The ONE home for token-embedding semantics (Gemma sqrt(d) scaling,
    Granite's ``embedding_multiplier``, learned positions, BLOOM word_embeddings_layernorm, BERT token-type
    segments) — shared by forward_hidden, forward_with_cache, the
    pipeline stages, and the ragged inference engine so a new
    embed-affecting knob can't silently diverge between paths."""
    x = em["tokens"][tokens]
    if cfg.scale_embeddings:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.hidden_size)
             ).astype(x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier
             ).astype(x.dtype)
    if cfg.pos_emb == "learned":
        x = x + em["pos"][positions]
    if cfg.type_vocab_size:
        if token_type_ids is None:
            token_type_ids = jnp.zeros(tokens.shape, jnp.int32)
        x = x + em["token_type"][token_type_ids]
    if cfg.embed_norm:
        x = _norm(cfg, embed_norm, x)
    return x


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """HF ``yarn_get_mscale``: ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(theta: float, rope_dim: int, factor: float,
                     original_len: int, beta_fast: float, beta_slow: float
                     ) -> jax.Array:
    """The ``rope_dim // 2`` YaRN frequencies (HF
    ``_compute_yarn_parameters``): pair ``i`` turns at ``f_i = theta **
    (-2i / rope_dim)``; the pairs that turn more than ``beta_fast`` times
    over the original length keep ``f_i``, those that turn fewer than
    ``beta_slow`` times take ``f_i / factor``, a linear ramp between."""
    half = rope_dim // 2
    f = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)

    def correction_dim(rotations: float) -> float:
        return rope_dim * math.log(original_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rope_dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return f / factor * (1.0 - keep) + f * keep


@jax.named_scope("attn_qkv")
def rope_table(cfg: DecoderConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """positions: [B, T] int32 → (sin, cos) each [B, T, rope_dim//2]
    (rope_dim == head_dim unless rotary_pct < 1 — GPT-NeoX partial
    rotary). ``cfg.rope_yarn``: YaRN frequencies, and the sin / cos scaled
    by ``mscale / mscale_all_dim`` (1 where they are equal)."""
    half = cfg.rope_dim // 2
    if cfg.rope_yarn is None:
        freqs = cfg.rope_theta ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = positions[..., None].astype(jnp.float32) * freqs
        return jnp.sin(angles), jnp.cos(angles)                # [B,T,half]
    factor, original_len, fast, slow, mscale, all_dim = cfg.rope_yarn
    freqs = yarn_frequencies(cfg.rope_theta, cfg.rope_dim, factor,
                             original_len, fast, slow)
    angles = positions[..., None].astype(jnp.float32) * freqs
    mag = yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim) \
        if mscale and all_dim else yarn_mscale(factor, 1.0)
    if mag == 1.0:
        return jnp.sin(angles), jnp.cos(angles)
    return jnp.sin(angles) * mag, jnp.cos(angles) * mag


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """x: [B, T, H, Dh]; rotate-half convention (Llama): pair ``i`` is
    dims ``(i, i + rot/2)``. ``interleaved``: pair ``i`` is the neighbours
    ``(2i, 2i + 1)`` (GPT-J; ``DecoderConfig.rope_interleaved``). When the
    table covers fewer dims than Dh (partial rotary), the tail passes
    through unrotated (GPT-NeoX/GPT-J convention)."""
    rot = 2 * sin.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    if interleaved:
        pairs = x_rot.reshape(*x_rot.shape[:-1], rot // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(x_rot, 2, axis=-1)
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    halves = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    rotated = jnp.stack(halves, axis=-1).reshape(x_rot.shape) \
        if interleaved else jnp.concatenate(halves, axis=-1)
    if x_pass.shape[-1]:
        rotated = jnp.concatenate([rotated, x_pass], axis=-1)
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (reference local path; Ulysses/ring wrap this fn)
# ---------------------------------------------------------------------------

def alibi_slopes(num_heads: int) -> jax.Array:
    """Per-head ALiBi slopes (Press et al.; BLOOM build_alibi_tensor
    convention): geometric sequence 2^(-8/n · i), with the closest
    power-of-two interpolation for non-power-of-2 head counts."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        s = pow2_slopes(num_heads)
    else:
        base = 1 << int(math.floor(math.log2(num_heads)))
        s = pow2_slopes(base)
        extra = pow2_slopes(2 * base)[0::2][:num_heads - base]
        s = s + extra
    return jnp.asarray(s, jnp.float32)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = True,
                          q_offset: int = 0,
                          alibi: Optional[jax.Array] = None,
                          window: Optional[int] = None,
                          key_mask: Optional[jax.Array] = None) -> jax.Array:
    """q: [B, Tq, H, Dh], k/v: [B, Tk, KvH, Dh] → [B, Tq, H, Dh].

    GQA handled by head repetition at the einsum level (no materialized
    repeat). fp32 softmax for numerics; XLA fuses the whole block onto MXU.
    ``alibi``: per-head slopes [H] → adds slope·(kpos − qpos) to the
    scores (BLOOM/Press-et-al. linear position bias). ``window``: causal
    sliding window (Mistral SWA) — key kp visible iff qp−window < kp ≤ qp.
    ``key_mask``: [B, Tk] bool, False = padding key (HF attention_mask;
    the correctness-critical case is padded ENCODER batches).
    """
    b, tq, h, dh = q.shape
    _, tk, kvh, _ = k.shape
    groups = h // kvh
    qg = q.reshape(b, tq, kvh, groups, dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    qpos = jnp.arange(tq) + q_offset
    kpos = jnp.arange(tk)
    if alibi is not None:
        rel = (kpos[None, :] - qpos[:, None]).astype(jnp.float32)  # ≤ 0 kept
        scores = scores + alibi.reshape(kvh, groups)[None, :, :, None, None] \
            * rel[None, None, None]
    if causal or window is not None:
        mask = qpos[:, None] >= kpos[None, :] if causal else \
            jnp.ones((tq, tk), bool)
        if window is not None:
            # ``window`` may be a traced per-layer scalar (GPT-Neo
            # alternating local attention); <= 0 means full causal
            w = jnp.asarray(window)
            mask = mask & ((w <= 0) | (kpos[None, :] > qpos[:, None] - w))
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, tq, h, dh)


AttentionFn = Callable[..., jax.Array]


def default_attention(cfg: DecoderConfig) -> AttentionFn:
    """Config-correct plain attention: ALiBi models get their slopes baked
    in (a bare ``dot_product_attention`` would silently train a
    position-free BLOOM), encoders (BERT) get the causal mask dropped."""
    if not cfg.causal:
        return partial(dot_product_attention, causal=False)
    if cfg.pos_emb == "alibi":
        return partial(dot_product_attention,
                       alibi=alibi_slopes(cfg.num_heads))
    if cfg.sliding_window is not None:
        return partial(dot_product_attention, window=cfg.sliding_window)
    return dot_product_attention


def layer_windows(cfg: DecoderConfig) -> jax.Array:
    """[L] int32 of per-layer attention windows (0 = full causal), the
    ``layer_window_pattern`` tiled over depth — GPT-Neo's
    ``attention_types`` expansion."""
    return jnp.asarray(cfg.window_per_layer(), jnp.int32)


def resolve_remat_policy(name: Optional[str]):
    """Map config policy names (ActivationCheckpointingConfig.policy) to
    jax.checkpoint policies; 'full'/None -> save nothing extra."""
    policies = {
        "none": None,
        "full": None,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_with_no_batch_dims_saveable":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # save each block's attention output (64MB/layer at 8x2048x2048
        # bf16); backward recomputes the cheap-to-recompute MLP/projection
        # GEMMs but NOT attention — the best memory/time trade when
        # attention is bandwidth-bound
        # "moe_dispatch" rides along in every save_* policy: the MoE
        # counting-sort metadata (parallel/moe.py) is ~0.4MB/layer but
        # recomputing it in backward re-runs the dispatch histogram
        "save_attn_out":
            jax.checkpoint_policies.save_only_these_names(
                "attn_out", "moe_dispatch", "moe_xs"),
        # save the Pallas flash kernel's residuals (pre-projection out +
        # lse, named inside the custom_vjp fwd) instead of the projected
        # attn_out: same bytes (+~1% for lse), but the backward no longer
        # re-runs the flash FORWARD kernel to rebuild them — a whole extra
        # attention pass per layer at long sequence. Only the cheap wo
        # projection recomputes. Pallas-attention configs only (other
        # impls don't emit these names and would save nothing).
        "save_attn_kernel":
            jax.checkpoint_policies.save_only_these_names(
                "attn_kernel_out", "attn_lse", "moe_dispatch",
                "moe_xs"),
        # + the MoE GLU pre-activations (~2x[R, ffn] bf16 per layer of
        # extra HBM). Only affects the UNSCALED grouped-matmul path —
        # the default fused-combine path recomputes gate/up in-kernel
        # and has no moe_glu residuals (measured FASTER than stacking
        # them across the layer scan; ops/grouped_matmul.py docstring)
        "save_attn_kernel_moe_glu":
            jax.checkpoint_policies.save_only_these_names(
                "attn_kernel_out", "attn_lse", "moe_dispatch",
                "moe_xs", "moe_glu"),
        # also save post-rope q/k/v: backward skips the QKV projection
        # recompute at +(q_dim+2·kv·Dh)·2B per token of HBM. Helps only
        # when HBM is loose — at the 1.27B/seq2048/b8 bench point the
        # extra residency evicts the CE chunk budget and LOSES 20+ MFU
        # points; measure before enabling
        "save_attn_qkv":
            jax.checkpoint_policies.save_only_these_names(
                "attn_out", "qkv", "moe_dispatch", "moe_xs"),
        # flash-kernel residuals AND post-rope q/k/v: backward re-runs
        # neither the flash forward nor the qkv projections/rope —
        # +(q+2kv)·Dh·2B per token of HBM on top of save_attn_kernel;
        # measure per geometry (same eviction caveat as save_attn_qkv)
        "save_attn_kernel_qkv":
            jax.checkpoint_policies.save_only_these_names(
                "attn_kernel_out", "attn_lse", "qkv", "moe_dispatch",
                "moe_xs"),
        # Host-DRAM activation offload — the reference's cpu_checkpointing
        # (runtime/activation_checkpointing/checkpointing.py partition/
        # cpu_checkpoint knobs). XLA emits async copy-start/copy-done pairs
        # to pinned host memory, overlapped with layer compute; backward
        # streams the tensors back. 'offload_attn_out' keeps the
        # save_attn_out recompute profile but parks attention outputs in
        # host DRAM instead of HBM; 'offload_full' offloads each layer's
        # residual-stream input and recomputes the whole block from it
        # (max HBM savings — the cpu_checkpointing analogue proper).
        "offload_attn_out":
            jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=["moe_dispatch"],
                names_which_can_be_offloaded=["attn_out"],
                offload_src="device", offload_dst="pinned_host"),
        "offload_attn_qkv":
            jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=["moe_dispatch"],
                names_which_can_be_offloaded=["attn_out", "qkv"],
                offload_src="device", offload_dst="pinned_host"),
        "offload_full":
            jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=["moe_dispatch"],
                names_which_can_be_offloaded=["block_in"],
                offload_src="device", offload_dst="pinned_host"),
        # block_in to host + attn_out kept in HBM: backward skips the
        # flash-attention recompute (the expensive part of 'full') while
        # the carry chain stops occupying HBM — the long-context sweet
        # spot when save_attn_out alone no longer fits
        "offload_save_attn_out":
            jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=["attn_out", "moe_dispatch"],
                names_which_can_be_offloaded=["block_in"],
                offload_src="device", offload_dst="pinned_host"),
        # flash-kernel residuals kept in HBM (backward skips the flash
        # FORWARD re-run entirely — see 'save_attn_kernel') + block inputs
        # parked on host: the 32K+ sweet spot where keeping both the
        # residual chain and the kernel outputs on device OOMs
        "offload_save_attn_kernel":
            jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=["attn_kernel_out", "attn_lse",
                                           "moe_dispatch"],
                names_which_can_be_offloaded=["block_in"],
                offload_src="device", offload_dst="pinned_host"),
        # the 128K+ regime: block inputs AND the flash-kernel residuals
        # all live in host DRAM — backward re-runs only the projections
        # and MLP, never the flash forward, and device HBM holds no
        # per-layer [T, ...] residuals at all. The extra ~1GB/layer of
        # D2H+H2D traffic vanishes under the attention math at these
        # sequence lengths (attention is ~97% of step FLOPs at 128K).
        "offload_save_attn_kernel_host":
            jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=["moe_dispatch"],
                names_which_can_be_offloaded=["block_in",
                                              "attn_kernel_out",
                                              "attn_lse"],
                offload_src="device", offload_dst="pinned_host"),
    }
    if name is not None and name not in policies:
        raise ValueError(f"unknown remat policy '{name}'; "
                         f"known: {sorted(policies)}")
    return policies.get(name)


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

def linear_2d(x: jax.Array, p: Params, name: str) -> jax.Array:
    """``x [..., K] @ p[name] [K, N]`` honoring int8 weight-only
    quantization: a ``<name>_scale`` leaf (ops/quantized_linear.py
    convention, attached by the inference engines' ``weight_quant``
    config) routes through the Pallas dequant-in-VMEM matmul — weights
    live in HBM at half the bytes (a memory-capacity feature; see the
    measured tradeoffs in ops/quantized_linear.py). Without a scale
    leaf this is a plain einsum (training path, fully
    differentiable)."""
    w = p[name]
    if name + "_scale" not in p:
        return jnp.einsum("...k,kn->...n", x, w)
    from deepspeed_tpu.ops.quantized_linear import qmatmul_tp
    lead = x.shape[:-1]
    # TP roles mirror partition_specs: out-projections ("wo") are
    # row-parallel, everything else column-parallel
    out = qmatmul_tp(x.reshape(-1, x.shape[-1]), w, p[name + "_scale"],
                     role="row" if name == "wo" else "col")
    return out.reshape(*lead, w.shape[-1])


@jax.named_scope("mlp")
def _mlp(cfg: DecoderConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.is_glu:
        gate = linear_2d(x, p, "wg")
        up = linear_2d(x, p, "wi")
        act = jax.nn.silu(gate) if cfg.activation == "silu_glu" \
            else jax.nn.gelu(gate, approximate=True)
        hidden = act * up
    else:
        hidden = linear_2d(x, p, "wi")
        if "bi" in p:
            hidden = hidden + p["bi"]
        if cfg.activation == "relu":
            hidden = jax.nn.relu(hidden)
        elif cfg.activation == "relu2":
            hidden = jnp.square(jax.nn.relu(hidden))
        else:
            hidden = jax.nn.gelu(
                hidden, approximate=cfg.activation != "gelu_exact")
    out = linear_2d(hidden, p, "wo")
    if "bo" in p:
        out = out + p["bo"]
    return out


@jax.named_scope("attn_qkv")
def qkv_project(cfg: DecoderConfig, p: Params, x: jax.Array, sin, cos
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared projection for training and KV-cached inference:
    x [B,t,D] -> q [B,t,H,Dh], k/v [B,t,KvH,Dh] with bias + RoPE applied."""
    b, t = x.shape[:2]
    q = linear_2d(x, p, "wq").reshape(b, t, cfg.num_heads, cfg.head_dim)
    k = linear_2d(x, p, "wk").reshape(b, t, cfg.kv_heads, cfg.head_dim)
    v = linear_2d(x, p, "wv").reshape(b, t, cfg.kv_heads, cfg.head_dim)
    if "bq" in p:
        q = q + p["bq"].reshape(cfg.num_heads, cfg.head_dim)
        k = k + p["bk"].reshape(cfg.kv_heads, cfg.head_dim)
        v = v + p["bv"].reshape(cfg.kv_heads, cfg.head_dim)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    q = checkpoint_name(q, "qkv")
    k = checkpoint_name(k, "qkv")
    v = checkpoint_name(v, "qkv")
    return q, k, v


@jax.named_scope("attn_out")
def attn_out_project(cfg: DecoderConfig, p: Params, out: jax.Array
                     ) -> jax.Array:
    b, t = out.shape[:2]
    out = linear_2d(out.reshape(b, t, cfg.q_dim), p, "wo")
    if "bo" in p:
        out = out + p["bo"]
    return out


def _attention_block(cfg: DecoderConfig, p: Params, x: jax.Array,
                     sin, cos, attn_fn: AttentionFn,
                     layer_window: Optional[jax.Array] = None) -> jax.Array:
    q, k, v = qkv_project(cfg, p, x, sin, cos)
    with jax.named_scope("attn_core"):
        out = attn_fn(q, k, v) if layer_window is None \
            else attn_fn(q, k, v, window=layer_window)
    return attn_out_project(cfg, p, out)


def decoder_block(cfg: DecoderConfig, p: Params, x: jax.Array, sin, cos,
                  attn_fn: AttentionFn,
                  moe_fn: Optional[Callable] = None,
                  layer_window: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden, aux_loss) — aux is 0 for dense blocks, the scaled
    load-balance loss for MoE blocks (reference sharded_moe.py l_aux).

    Under ``cfg.health_taps`` the second output is instead a per-layer
    stats dict ({aux_loss, act_rms, act_absmax} + MoE router stats) that
    ``lax.scan`` stacks into [L]-leading arrays for telemetry/health.py.
    """
    pre = _norm(cfg, p["ln1"], x) if cfg.prenorm else x
    attn_out = _attention_block(cfg, p["attn"], pre, sin, cos, attn_fn,
                                layer_window)
    attn_out = checkpoint_name(attn_out, "attn_out")
    if not getattr(cfg, "health_taps", False):
        return block_combine(cfg, p, x, pre, attn_out, moe_fn)
    h, aux, rstats = block_combine(cfg, p, x, pre, attn_out, moe_fn)
    hf = h.astype(jnp.float32)
    stats = {"aux_loss": aux,
             "act_rms": jnp.sqrt(jnp.mean(jnp.square(hf))),
             "act_absmax": jnp.max(jnp.abs(hf))}
    if rstats is not None:
        stats.update(rstats)
    return h, stats


def block_combine(cfg: DecoderConfig, p: Params, x: jax.Array,
                  pre: jax.Array, attn_out: jax.Array,
                  moe_fn: Optional[Callable]) -> Tuple[jax.Array, jax.Array]:
    """Residual combine shared by training, cached decode, and ragged
    inference (one home for the parallel/sequential branch math).

    Parallel (GPT-J/NeoX/Falcon): h = x + attn + mlp(src) where src is
    the shared pre-norm (1-norm variants) or a separate ln2(x) (NeoX /
    Falcon-40B 2-norm variants); attention and MLP matmuls overlap on the
    MXU. Sequential (GPT-2/Llama): post-attention pre-norm MLP.
    Post-LN (BERT/original transformer, prenorm=False):
    h = ln1(x + attn(x)); out = ln2(h + mlp(h)).
    """
    def ffn(src):
        if cfg.num_experts and moe_fn is not None:
            with jax.named_scope("moe"):
                ret = moe_fn(cfg, p["moe"], src)
            out, aux = ret[0], ret[1]
            # 3rd element = router-health stats, present iff the moe
            # layer saw cfg.health_taps (parallel/moe.py)
            rstats = ret[2] if len(ret) > 2 else None
            if "residual" in p["moe"]:
                # Residual-MoE (reference moe/layer.py use_residual):
                # learned convex mix of the routed output and a dense MLP
                res = _mlp(cfg, p["moe"]["residual"], src)
                coef = jax.nn.softmax(
                    jnp.einsum("...d,dc->...c", src.astype(jnp.float32),
                               p["moe"]["coef"].astype(jnp.float32))
                    + p["moe"]["coef_b"].astype(jnp.float32),
                    axis=-1).astype(src.dtype)
                out = out * coef[..., 0:1] + res * coef[..., 1:2]
            return out, aux, rstats
        if cfg.ffn_chunk and src.shape[1] > cfg.ffn_chunk:
            # FPDT chunked MLP: [T, ffn]-sized activations become
            # [ffn_chunk, ffn]-sized (parallel/fpdt.fpdt_ffn)
            from deepspeed_tpu.parallel.fpdt import fpdt_ffn
            return (fpdt_ffn(partial(_mlp, cfg, p["mlp"]), src,
                             chunk=cfg.ffn_chunk),
                    jnp.zeros((), jnp.float32), None)
        return _mlp(cfg, p["mlp"], src), jnp.zeros((), jnp.float32), None

    if not cfg.prenorm:
        h = _norm(cfg, p["ln1"], x + attn_out)
        ff, aux, rstats = ffn(h)
        out = _norm(cfg, p["ln2"], h + ff)
    elif cfg.parallel_block:
        src = _norm(cfg, p["ln2"], x) if cfg.parallel_block_norms == 2 \
            else pre
        ff, aux, rstats = ffn(src)
        out = x + attn_out + ff
    else:
        h = x + attn_out
        ff, aux, rstats = ffn(_norm(cfg, p["ln2"], h))
        out = h + ff
    if getattr(cfg, "health_taps", False):
        return out, aux, rstats
    return out, aux


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: DecoderConfig, rng: jax.Array,
                dtype=jnp.float32) -> Params:
    """Initialize the full parameter pytree (stacked layers; a typed
    stack's list of layers is ``typed_layers.init_typed_params``)."""
    if cfg.typed:
        from deepspeed_tpu.models.typed_layers import init_typed_params
        return init_typed_params(cfg, rng, dtype)
    d, v, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    h = cfg.ffn_size
    kd = cfg.kv_heads * cfg.head_dim
    qd = cfg.q_dim
    keys = jax.random.split(rng, 20)

    def w(key, shape, std=cfg.init_std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    attn = {
        "wq": w(keys[0], (L, d, qd)),
        "wk": w(keys[1], (L, d, kd)),
        "wv": w(keys[2], (L, d, kd)),
        "wo": w(keys[3], (L, qd, d), std=cfg.init_std / math.sqrt(2 * L)),
    }
    if cfg.qkv_bias:
        attn.update(bq=jnp.zeros((L, qd), dtype), bk=jnp.zeros((L, kd), dtype),
                    bv=jnp.zeros((L, kd), dtype))
    if cfg.out_bias:
        attn["bo"] = jnp.zeros((L, d), dtype)

    layers: Params = {
        "attn": attn,
        "ln1": _norm_params(cfg, (L,)),
    }
    if cfg.has_ln2:
        layers["ln2"] = _norm_params(cfg, (L,))
    if cfg.num_experts:
        E = cfg.num_experts
        layers["moe"] = {
            "router": w(keys[4], (L, d, E)),
            "wg": w(keys[5], (L, E, d, h)),
            "wi": w(keys[6], (L, E, d, h)),
            "wo": w(keys[7], (L, E, h, d), std=cfg.init_std / math.sqrt(2 * L)),
        }
        if cfg.shared_expert_size:
            hs = cfg.shared_expert_size
            shared = {
                "wg": w(keys[12], (L, d, hs)),
                "wi": w(keys[13], (L, d, hs)),
                "wo": w(keys[14], (L, hs, d),
                        std=cfg.init_std / math.sqrt(2 * L)),
            }
            if cfg.shared_expert_gate:
                shared["gate"] = w(keys[15], (L, d, 1))
            layers["moe"]["shared"] = shared
        if cfg.moe_residual:
            # Residual-MoE dense branch + 2-way mixing coefficient
            # (reference moe/layer.py: self.mlp + self.coefficient)
            if cfg.is_glu:
                residual = {
                    "wg": w(keys[16], (L, d, h)),
                    "wi": w(keys[17], (L, d, h)),
                    "wo": w(keys[18], (L, h, d),
                            std=cfg.init_std / math.sqrt(2 * L)),
                }
            else:
                residual = {
                    "wi": w(keys[17], (L, d, h)),
                    "wo": w(keys[18], (L, h, d),
                            std=cfg.init_std / math.sqrt(2 * L)),
                }
                if cfg.use_bias:
                    residual.update(bi=jnp.zeros((L, h), dtype),
                                    bo=jnp.zeros((L, d), dtype))
            layers["moe"]["residual"] = residual
            layers["moe"]["coef"] = w(keys[19], (L, d, 2))
            layers["moe"]["coef_b"] = jnp.zeros((L, 2), dtype)
    else:
        if cfg.is_glu:
            layers["mlp"] = {
                "wg": w(keys[5], (L, d, h)),
                "wi": w(keys[6], (L, d, h)),
                "wo": w(keys[7], (L, h, d), std=cfg.init_std / math.sqrt(2 * L)),
            }
        else:
            layers["mlp"] = {
                "wi": w(keys[6], (L, d, h)),
                "wo": w(keys[7], (L, h, d), std=cfg.init_std / math.sqrt(2 * L)),
            }
            if cfg.use_bias:
                layers["mlp"].update(bi=jnp.zeros((L, h), dtype),
                                     bo=jnp.zeros((L, d), dtype))

    params: Params = {
        "embed": {"tokens": w(keys[8], (v, d))},
        "layers": layers,
    }
    if cfg.has_final_norm:
        params["final_norm"] = _norm_params(cfg)
    if cfg.embed_norm:
        params["embed_norm"] = _norm_params(cfg)
    if cfg.pos_emb == "learned":
        params["embed"]["pos"] = w(keys[9], (cfg.max_seq_len, d))
    if cfg.type_vocab_size:
        params["embed"]["token_type"] = w(keys[11], (cfg.type_vocab_size, d))
    if cfg.mlm_head:
        params["mlm_head"] = {
            "dense": w(keys[12], (d, d)),
            "dense_bias": jnp.zeros((d,), dtype),
            "ln": _norm_params(cfg),
            "vocab_bias": jnp.zeros((v,), dtype),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(keys[10], (d, v))
        if cfg.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((v,), dtype)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward_hidden(cfg: DecoderConfig, params: Params, tokens: jax.Array,
                   attn_fn: Optional[AttentionFn] = None,
                   moe_fn: Optional[Callable] = None,
                   positions: Optional[jax.Array] = None,
                   remat_policy: Optional[str] = None,
                   token_type_ids: Optional[jax.Array] = None,
                   attention_mask: Optional[jax.Array] = None,
                   layer_loop: Optional[Callable] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """tokens: [B, T] int32 → (final-norm hidden [B, T, D], MoE aux loss).

    Layers applied with ``lax.scan`` over the stacked pytree; optional
    ``jax.checkpoint`` per block (the reference's activation checkpointing
    runtime/activation_checkpointing/ → remat on TPU).

    ``layer_loop``: optional replacement for the plain
    ``lax.scan(body, x, xs)`` with the same contract (carry in, carry +
    stacked-aux out) — the ZeRO-3 chunked-overlap path
    (runtime/zero/overlap.py OverlapPlan.layer_loop) injects its
    gather/compute pipeline here without this module importing runtime.

    ``attention_mask``: [B, T] (1 = real, 0 = pad; HF convention). Only
    needed for ENCODERS, where pad keys attend into every position;
    right-padded decoder batches are already correct under the causal
    mask (+ label -100). The selected ``attn_fn`` must accept
    ``key_mask`` (the masked/chunked paths do; Pallas flash is
    causal-only and never selected for encoders).
    """
    if cfg.typed:
        # a list of typed layers, unrolled (models/typed_layers.py): its
        # attention is its own (window, sink, unequal K/V widths), it has
        # no balance loss, and it is not trained yet
        if attn_fn is not None or remat_policy or layer_loop is not None \
                or attention_mask is not None or token_type_ids is not None:
            raise NotImplementedError(
                "a typed layer stack (DecoderConfig.layer_kinds) takes no "
                "attn_fn / remat_policy / layer_loop / attention_mask: it "
                "is a serving model (models/typed_layers.py)")
        from deepspeed_tpu.models.typed_layers import forward_hidden_typed
        return forward_hidden_typed(cfg, params, tokens, moe_fn,
                                    positions), jnp.zeros((), jnp.float32)
    if attn_fn is None:
        attn_fn = default_attention(cfg)
    if attention_mask is not None:
        attn_fn = partial(attn_fn, key_mask=attention_mask.astype(bool))
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x = embed_tokens(cfg, params["embed"], tokens, positions,
                     params.get("embed_norm"), token_type_ids)
    if cfg.pos_emb == "rope":
        sin, cos = rope_table(cfg, positions)
    else:   # learned: applied in embed; alibi: bias in the attention impl
        sin = cos = jnp.zeros((b, t, 0), x.dtype)

    block = partial(decoder_block, cfg, attn_fn=attn_fn, moe_fn=moe_fn)

    if cfg.layer_window_pattern:
        def body(carry, xs):
            layer_params, w = xs
            carry = checkpoint_name(carry, "block_in")
            out, aux = block(layer_params, carry, sin, cos, layer_window=w)
            return out, aux
        scan_xs = (params["layers"],
                   layer_windows(cfg))
    else:
        def body(carry, layer_params):
            carry = checkpoint_name(carry, "block_in")
            out, aux = block(layer_params, carry, sin, cos)
            return out, aux
        scan_xs = params["layers"]

    if remat_policy and remat_policy != "none":
        body = jax.checkpoint(body, policy=resolve_remat_policy(remat_policy))

    if layer_loop is not None:
        x, aux = layer_loop(body, x, scan_xs)
    else:
        x, aux = lax.scan(body, x, scan_xs)
    if cfg.has_final_norm:
        x = _norm(cfg, params["final_norm"], x)
    if getattr(cfg, "health_taps", False):
        # aux is the scan-stacked per-layer stats dict ([L]-leading
        # leaves); the loss consumes only the aux_loss component, the
        # rest flows to telemetry/health.py as a third output
        return x, jnp.sum(aux["aux_loss"]), aux
    return x, jnp.sum(aux)


def _softcap(cfg: DecoderConfig, logits: jax.Array) -> jax.Array:
    """What a head's raw logits still go through: Granite's
    ``logits_scaling`` (a division), Gemma2's final_logit_softcapping
    ``c·tanh(logits/c)``."""
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        return c * jnp.tanh(logits / c)
    return logits


def mlm_transform(cfg: DecoderConfig, mh: Params, x: jax.Array) -> jax.Array:
    """HF ``cls.predictions.transform``: dense + the config activation +
    LN (shared by lm_logits and chunked_cross_entropy so the training
    loss optimizes the exact serving logits)."""
    x = jnp.einsum("btd,de->bte", x, mh["dense"]) + mh["dense_bias"]
    if cfg.activation == "relu":
        x = jax.nn.relu(x)
    else:
        x = jax.nn.gelu(x, approximate=cfg.activation != "gelu_exact")
    return _norm(cfg, mh["ln"], x)


@jax.named_scope("lm_head")
def lm_logits(cfg: DecoderConfig, params: Params, x: jax.Array,
              pre_transformed: bool = False) -> jax.Array:
    """Final projection: hidden [B,T,D] → logits [B,T,V] fp32.

    ``mlm_head`` models (BERT) first run the HF ``cls.predictions.
    transform`` — dense+act+LN — then the tied decode plus vocab bias
    (``pre_transformed=True`` when the caller already applied it)."""
    if cfg.mlm_head and "mlm_head" in params:
        if not pre_transformed:
            x = mlm_transform(cfg, params["mlm_head"], x)
        logits = jnp.einsum("btd,vd->btv", x, params["embed"]["tokens"],
                            preferred_element_type=jnp.float32)
        return logits + params["mlm_head"]["vocab_bias"].astype(jnp.float32)
    q_name = "lm_head_q" if "lm_head_q" in params else \
        ("lm_head" if "lm_head_scale" in params else None)
    if q_name:   # int8 serving head (tied models carry a transposed copy)
        from deepspeed_tpu.ops.quantized_linear import qmatmul_tp
        b, t, d = x.shape
        logits = qmatmul_tp(x.reshape(b * t, d), params[q_name],
                            params[q_name + "_scale"], role="col",
                            out_dtype=jnp.float32).reshape(b, t, -1)
        if "lm_head_bias" in params:
            logits = logits + params["lm_head_bias"].astype(jnp.float32)
    elif cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"]["tokens"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        if "lm_head_bias" in params:
            logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return _softcap(cfg, logits)


def forward(cfg: DecoderConfig, params: Params, tokens: jax.Array,
            attn_fn: Optional[AttentionFn] = None,
            moe_fn: Optional[Callable] = None,
            positions: Optional[jax.Array] = None,
            remat_policy: Optional[str] = None,
            with_aux: bool = False,
            token_type_ids: Optional[jax.Array] = None,
            attention_mask: Optional[jax.Array] = None
            ) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """tokens → logits [B,T,V] fp32 (with_aux: plus MoE aux loss)."""
    x, aux = forward_hidden(cfg, params, tokens, attn_fn=attn_fn,
                            moe_fn=moe_fn, positions=positions,
                            remat_policy=remat_policy,
                            token_type_ids=token_type_ids,
                            attention_mask=attention_mask)
    logits = lm_logits(cfg, params, x)
    if with_aux:
        return logits, aux
    return logits


#: dense (unchunked, no-remat) logits allowed up to this size only — the
#: chunk budget below may be larger, but an unchunked CE also KEEPS the
#: logits for backward, so its cap stays conservative
_DENSE_LOGITS_BYTES = 128 * 1024 * 1024


def _pick_chunk(t: int, b: int, v: int,
                budget_bytes: Optional[int] = None,
                max_chunk: Optional[int] = None,
                elt_bytes: int = 4) -> int:
    """Largest divisor of T (≤ max_chunk) whose fp32 logits chunk fits
    the budget.

    The budget trades HBM for MXU shape: too small and the [B·C, D]×[D, V]
    chunk matmul has so few rows the MXU idles (measured on v5e 1.27B/
    128k-vocab: 512 MB ≈ 11% faster steps than 128 MB). Overridable via
    ``DSTPU_CE_BUDGET_MB`` for tuning."""
    if budget_bytes is None:
        import os
        budget_bytes = int(os.environ.get("DSTPU_CE_BUDGET_MB", 512)) \
            * 1024 * 1024
    best = 1
    for c in range(1, (max_chunk or t) + 1):
        if t % c == 0 and b * c * v * elt_bytes <= budget_bytes:
            best = c
    return best


@jax.named_scope("loss")
def chunked_cross_entropy(cfg: DecoderConfig, params: Params, x: jax.Array,
                          targets: jax.Array, ignore_index: int = -100,
                          chunk_size: Optional[int] = None,
                          budget_bytes: Optional[int] = None,
                          logits_dtype=None) -> jax.Array:
    """Token-mean CE without materializing [B,T,V] logits.

    TPU-native equivalent of the reference's tiled logits-loss
    (runtime/sequence_parallel/ulysses_sp.py:TiledFusedLogitsLoss:960):
    the sequence is scanned in chunks and peak memory is one chunk of
    logits — the difference between OOM and training for 128k vocabs.
    Under differentiation the scan takes each chunk's gradients in the
    pass that computes its logits (a ``jax.custom_vjp`` whose forward rule
    keeps ``dx``, the summed ``dW`` and ``dbias`` of the chunks' SUMS; the
    backward rule scales them by cotangent / live targets, in float32), so
    a step runs the head matmul three times — logits, ``dx``, ``dW`` — and
    never a fourth to recompute the logits. What is rounded to the head's
    and the hidden's dtype is O(1) a token, so a float16 trainer's loss
    scale protects the head as it does every other layer. Everything
    either rule emits stays under the ``loss`` scope.
    """
    b, t, d = x.shape
    v = cfg.vocab_size
    # BERT-class heads: run the cls.predictions transform ONCE on the
    # full hidden (a cheap [B,T,D]×[D,D]), so every path below — dense
    # shortcut and chunk scan — decodes the exact serving logits
    mlm = cfg.mlm_head and "mlm_head" in params
    if mlm:
        x = mlm_transform(cfg, params["mlm_head"], x)
    # chunk sizing follows the EMITTED logits dtype (bf16 chunks are half
    # the bytes, so the same budget buys twice the rows for the MXU); the
    # dense shortcut below stays a 4-byte bound — that path materializes
    # fp32 lm_logits
    eb = 2 if logits_dtype == jnp.bfloat16 else 4
    chunk = chunk_size or _pick_chunk(t, b, v, budget_bytes, elt_bytes=eb)
    if chunk >= t and chunk_size is None and \
            b * t * v * 4 > _DENSE_LOGITS_BYTES:
        # the whole-T logits fit the CHUNK budget, but an unchunked CE
        # would also hold them live for backward — keep the scan with at
        # least two chunks instead
        chunk = _pick_chunk(t, b, v, budget_bytes, max_chunk=t // 2,
                            elt_bytes=eb)
    if chunk >= t:
        return cross_entropy_loss(
            lm_logits(cfg, params, x, pre_transformed=True), targets,
            ignore_index)
    if cfg.tie_embeddings:
        w = params["embed"]["tokens"]
        bias = params["mlm_head"]["vocab_bias"] if mlm else None
    else:
        w = params["lm_head"]
        bias = params.get("lm_head_bias")
    nc = t // chunk
    xs = jnp.moveaxis(x.reshape(b, nc, chunk, d), 1, 0)       # [nc,B,C,D]
    ts = jnp.moveaxis(targets.reshape(b, nc, chunk), 1, 0)    # [nc,B,C]
    # the live targets are counted BEFORE the scan: the token mean and its
    # gradients are the chunks' sums times one scalar
    live = jnp.maximum(jnp.sum(targets != ignore_index), 1) \
        .astype(jnp.float32)

    # logits_dtype=bf16 emits chunk logits in bf16 and upcasts inside the
    # fused reductions: the MXU still accumulates fp32 (preferred_element_
    # type sets the OUTPUT type on TPU), but the [B,C,V] HBM roundtrip
    # halves — measured +0.6 MFU points on the v5e bench. Default fp32.
    out_dt = logits_dtype or jnp.float32

    # inside a shard_map (the pipeline's) the hidden may vary over a manual
    # axis the head does not: everything the scans carry is made to vary
    # as their inputs do, and the head with it, so dW crosses that axis
    # once, where the cast is transposed, not once a chunk (outside a
    # shard_map nothing varies and nothing is cast)
    vma = frozenset().union(*(jax.typeof(a).vma for a in jax.tree.leaves(
        (xs, w, bias, ts))))

    def vary(a):
        missing = tuple(sorted(vma - jax.typeof(a).vma))
        return lax.pcast(a, missing, to="varying") if missing else a

    w, bias, live = jax.tree.map(vary, (w, bias, live))

    def term(xc, w, bias, tc):
        """One chunk's SUM of token losses (the mean's divisor comes after:
        what is differentiated is O(1) a token in any 16-bit dtype)."""
        logits = jnp.einsum(
            "bcd,vd->bcv" if cfg.tie_embeddings else "bcd,dv->bcv", xc, w,
            preferred_element_type=out_dt)
        if bias is not None:
            logits = logits + bias.astype(out_dt)
        logits = _softcap(cfg, logits)
        mask = tc != ignore_index
        safe = jnp.where(mask, tc, 0)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None],
                                   axis=-1)[..., 0].astype(jnp.float32)
        return jnp.sum((logz - gold) * mask)

    @jax.custom_vjp
    def scanned(xs, w, bias, ts, live):
        with jax.named_scope("loss"):
            return lax.scan(
                lambda acc, xc_tc: (acc + term(xc_tc[0], w, bias, xc_tc[1]),
                                    None),
                vary(jnp.zeros((), jnp.float32)), (xs, ts))[0] / live

    def scanned_fwd(xs, w, bias, ts, live):
        def body(carry, xc_tc):
            total, dwb = carry
            xc, tc = xc_tc
            lc, (dxc, dwc, dbc) = jax.value_and_grad(
                term, argnums=(0, 1, 2))(xc, w, bias, tc)
            dwb = jax.tree.map(lambda a, g: a + g.astype(a.dtype), dwb,
                               (dwc, dbc))
            return (total + lc, dwb), dxc

        # the SUM's gradients are kept (softmax - onehot is O(1), so dx and
        # dW round in range in x's and w's own dtype, float16 under a loss
        # scale included); dW / dbias are summed over the chunks in float32
        # whatever the head's dtype (the scan's transpose summed them in
        # w's own)
        with jax.named_scope("loss"):
            zeros = jax.tree.map(
                lambda p: vary(jnp.zeros(p.shape, jnp.promote_types(
                    p.dtype, jnp.float32))), (w, bias))
            (total, dwb), dxs = lax.scan(
                body, (vary(jnp.zeros((), jnp.float32)), zeros), (xs, ts))
        return total / live, (dxs, dwb, live)

    head_dtypes = jax.tree.map(lambda p: p.dtype, (w, bias))

    def scanned_bwd(res, g):
        dxs, dwb, live = res
        # cotangent and the mean's divisor meet the residuals as ONE
        # float32 scalar, before anything is cast down
        with jax.named_scope("loss"):
            s = g.astype(jnp.float32) / live
            dxs = (s * dxs).astype(dxs.dtype)
            dw, dbias = jax.tree.map(lambda r, dt: (s * r).astype(dt),
                                     dwb, head_dtypes)
        return dxs, dw, dbias, None, None

    scanned.defvjp(scanned_fwd, scanned_bwd)
    return scanned(xs, w, bias, ts, live)


@jax.named_scope("loss")
def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       ignore_index: int = -100) -> jax.Array:
    """Token-mean CE in fp32 (reference: sequence/cross_entropy.py
    semantics; under TP the embed/lm_head specs shard the vocab dim over
    'model' and GSPMD emits the vocab-parallel max/sum collectives the
    reference hand-writes)."""
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_index)
    safe_targets = jnp.where(mask, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


# ---------------------------------------------------------------------------
# KV-cached forward (inference; reference: inference_context.h KV rings +
# inference/v2 blocked KV — here a static-shape cache updated in place)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: DecoderConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_attention(cfg: DecoderConfig, p: Params, x, sin, cos,
                      k_cache, v_cache, cache_len, layer_window=None):
    """One block's attention against the cache; returns (out, k_new, v_new).

    x: [B, t, D] new tokens; k_cache/v_cache: [B, Tmax, KvH, Dh];
    cache_len: scalar int32 — tokens already cached. ``layer_window``:
    traced per-layer window (GPT-Neo local layers; <=0 = full).
    """
    b, t, d = x.shape
    q, k, v = qkv_project(cfg, p, x, sin, cos)
    k_cache = lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, cache_len, 0, 0))
    v_cache = lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, cache_len, 0, 0))

    # attend over the whole (static) cache with a validity+causal mask
    tmax = k_cache.shape[1]
    kvh, dh = cfg.kv_heads, cfg.head_dim
    groups = cfg.num_heads // kvh
    qg = q.reshape(b, t, kvh, groups, dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg,
                        k_cache.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    qpos = cache_len + jnp.arange(t)
    kpos = jnp.arange(tmax)
    if cfg.pos_emb == "alibi":
        rel = (kpos[None, :] - qpos[:, None]).astype(jnp.float32)
        scores = scores + alibi_slopes(cfg.num_heads).reshape(
            kvh, groups)[None, :, :, None, None] * rel[None, None, None]
    mask = qpos[:, None] >= kpos[None, :]
    if cfg.sliding_window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - cfg.sliding_window)
    if layer_window is not None:
        w = jnp.asarray(layer_window)
        mask = mask & ((w <= 0) | (kpos[None, :] > qpos[:, None] - w))
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v_cache)
    out = out.reshape(b, t, cfg.num_heads, dh)
    return attn_out_project(cfg, p, out), k_cache, v_cache


def forward_with_cache(cfg: DecoderConfig, params: Params, tokens: jax.Array,
                       cache: Params, cache_len: jax.Array,
                       moe_fn: Optional[Callable] = None
                       ) -> Tuple[jax.Array, Params]:
    """tokens: [B, t] (prefill t>1 or decode t==1) → (logits of the LAST
    position [B, V] fp32, updated cache). cache_len: tokens already held.
    """
    if cfg.typed:
        raise NotImplementedError(
            "forward_with_cache (the v1 contiguous KV cache) has no typed "
            "layer stack (DecoderConfig.layer_kinds): serve it with "
            "RaggedInferenceEngineTPU")
    b, t = tokens.shape
    positions = cache_len + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x = embed_tokens(cfg, params["embed"], tokens, positions,
                     params.get("embed_norm"))
    if cfg.pos_emb == "rope":
        sin, cos = rope_table(cfg, positions)
    else:
        sin = cos = jnp.zeros((b, t, 0), x.dtype)

    def body(carry, layer):
        x = carry
        layer_params, k_c, v_c = layer[:3]
        w = layer[3] if len(layer) > 3 else None
        h_in = _norm(cfg, layer_params["ln1"], x) if cfg.prenorm else x
        attn_out, k_c, v_c = _cached_attention(
            cfg, layer_params["attn"], h_in, sin, cos, k_c, v_c, cache_len,
            layer_window=w)
        out, _aux = block_combine(cfg, layer_params, x, h_in, attn_out,
                                  moe_fn)
        return out, (k_c, v_c)

    scan_xs = (params["layers"], cache["k"], cache["v"])
    if cfg.layer_window_pattern:
        scan_xs = scan_xs + (layer_windows(cfg),)
    x, (k_new, v_new) = lax.scan(body, x, scan_xs)
    x = x[:, -1:]
    if cfg.has_final_norm:
        x = _norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {"k": k_new, "v": v_new}


# ---------------------------------------------------------------------------
# Partition specs — the AutoTP + ZeRO sharding planner
# ---------------------------------------------------------------------------

def partition_specs(cfg: DecoderConfig, zero_stage: int = 0,
                    tp: bool = False, mics: bool = False) -> Params:
    """PartitionSpec pytree matching :func:`init_params`.

    TP (reference module_inject/auto_tp.py row/col slicing): qkv + mlp-in are
    column-parallel (shard output dim over 'model'), attn-out + mlp-out are
    row-parallel (shard input dim); embeddings shard vocab.

    ZeRO-3 (reference zero/partition_parameters.py): shard a *different* axis
    over ('data','expert') so FSDP and TP compose. Stages 0-2 leave params
    replicated (grads/opt-state sharding is handled by the engine).
    """
    if cfg.typed:
        raise NotImplementedError(
            "partition_specs: a typed layer stack (DecoderConfig."
            "layer_kinds: mimo_v2, deepseek_v3, cohere2_moe, nemotron_h) is served on "
            "one shard and not trained yet — no sharding plan exists for "
            "its list of layers")
    # MiCS (reference runtime/zero/mics.py:63): param shards live within
    # the (data_inner, expert) sub-group and replicate across 'data', so
    # stage-3 allgathers stay inside the cheap sub-group links
    if zero_stage >= 3:
        fsdp = ("data_inner", "expert") if mics else \
            ("data", "data_inner", "expert")
    else:
        fsdp = None
    model = "model" if tp else None

    def spec(*axes):
        return P(*axes)

    attn = {
        "wq": spec(None, fsdp, model),
        "wk": spec(None, fsdp, model),
        "wv": spec(None, fsdp, model),
        "wo": spec(None, model, fsdp),
    }
    if cfg.qkv_bias:
        attn.update(bq=spec(None, model), bk=spec(None, model),
                    bv=spec(None, model))
    if cfg.out_bias:
        attn["bo"] = spec(None, None)

    layers: Params = {
        "attn": attn,
        "ln1": {"scale": spec(None, None)},
    }
    if cfg.has_ln2:
        layers["ln2"] = {"scale": spec(None, None)}
    if cfg.ln_bias:
        layers["ln1"]["bias"] = spec(None, None)
        if cfg.has_ln2:
            layers["ln2"]["bias"] = spec(None, None)

    if cfg.num_experts:
        # expert weights: E dim sharded over 'expert'; FSDP restricted to
        # the data axes so they don't collide (reference: expert params are
        # DP'd over the expert-data-parallel group only, groups.py:315)
        if zero_stage >= 3:
            efsdp = "data_inner" if mics else ("data", "data_inner")
        else:
            efsdp = None
        layers["moe"] = {
            "router": spec(None, fsdp, None),
            "wg": spec(None, "expert", efsdp, model),
            "wi": spec(None, "expert", efsdp, model),
            "wo": spec(None, "expert", model, efsdp),
        }
        if cfg.shared_expert_size:
            # shared expert is DENSE (runs on every token): sharded like
            # a dense MLP, replicated over 'expert'
            shared = {
                "wg": spec(None, fsdp, model),
                "wi": spec(None, fsdp, model),
                "wo": spec(None, model, fsdp),
            }
            if cfg.shared_expert_gate:
                shared["gate"] = spec(None, fsdp, None)
            layers["moe"]["shared"] = shared
        if cfg.moe_residual:
            # residual dense branch: sharded like a dense MLP,
            # replicated over 'expert' (runs on every token)
            residual = {
                "wi": spec(None, fsdp, model),
                "wo": spec(None, model, fsdp),
            }
            if cfg.is_glu:
                residual["wg"] = spec(None, fsdp, model)
            elif cfg.use_bias:
                residual.update(bi=spec(None, model), bo=spec(None, None))
            layers["moe"]["residual"] = residual
            layers["moe"]["coef"] = spec(None, fsdp, None)
            layers["moe"]["coef_b"] = spec(None, None)
    else:
        mlp = {
            "wi": spec(None, fsdp, model),
            "wo": spec(None, model, fsdp),
        }
        if cfg.is_glu:
            mlp["wg"] = spec(None, fsdp, model)
        elif cfg.use_bias:
            mlp.update(bi=spec(None, model), bo=spec(None, None))
        layers["mlp"] = mlp

    specs: Params = {
        "embed": {"tokens": spec(model, fsdp)},
        "layers": layers,
    }
    if cfg.has_final_norm:
        specs["final_norm"] = {"scale": spec(None)}
        if cfg.ln_bias:
            specs["final_norm"]["bias"] = spec(None)
    if cfg.type_vocab_size:
        specs["embed"]["token_type"] = spec(None, fsdp)
    if cfg.mlm_head:
        mh = {"dense": spec(fsdp, None), "dense_bias": spec(None),
              "ln": {"scale": spec(None)}, "vocab_bias": spec(model)}
        if cfg.ln_bias:
            mh["ln"]["bias"] = spec(None)
        specs["mlm_head"] = mh
    if cfg.embed_norm:
        specs["embed_norm"] = {"scale": spec(None)}
        if cfg.ln_bias:
            specs["embed_norm"]["bias"] = spec(None)
    if cfg.pos_emb == "learned":
        specs["embed"]["pos"] = spec(None, fsdp)
    if not cfg.tie_embeddings:
        specs["lm_head"] = spec(fsdp, model)
        if cfg.lm_head_bias:
            specs["lm_head_bias"] = spec(model)
    return specs
