"""HuggingFace checkpoint interop (safetensors ↔ transformer pytree).

TPU-native equivalent of the reference's checkpoint engines + injection
policies (inference/v2/checkpoint/huggingface_engine.py streaming loader,
module_inject/auto_tp.py:193 layer-name policy walk). Instead of mutating
torch modules layer-by-layer, we map HF tensor names into the functional
pytree layout (layers stacked on a leading [L] axis for ``lax.scan``) and
let `transformer.partition_specs` supply the TP/FSDP sharding rules — the
AutoTP analogue is rule-driven sharding of the loaded pytree, applied by
the engine via `jax.device_put` at initialize().

Supported families: Llama/Mistral (silu_glu, RMSNorm, rope), Mixtral
(MoE experts w1/w2/w3), Qwen2 (adds qkv biases). HF stores Linear weights
as [out, in]; our einsum layout is [in, out], hence the transposes.
"""

import dataclasses
import json
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

from deepspeed_tpu.models.transformer import DecoderConfig
from deepspeed_tpu.utils.logging import logger

Params = Any


# ---------------------------------------------------------------------------
# config mapping
# ---------------------------------------------------------------------------

_FAMILIES = ("llama", "mistral", "mixtral", "qwen", "qwen2", "qwen2_moe",
              "gpt_neox", "gemma", "gpt2", "opt", "bloom", "falcon",
              "phi", "phi3", "gpt_bigcode", "gptj", "bert", "distilbert",
              "gpt_neo", "internlm", "mimo_v2", "deepseek_v3",
              "cohere2_moe", "nemotron_h", "granitemoehybrid", "jamba",
              "glm_moe_dsa", "lfm2_moe", "xing4_0", "qwen3_next")


def _map_hf_act(act: str) -> str:
    """HF activation_function → DecoderConfig.activation. HF 'gelu' is
    the exact erf form; 'gelu_new'/'gelu_fast'/'gelu_pytorch_tanh' are
    the tanh approximation this repo calls plain 'gelu'."""
    table = {"gelu": "gelu_exact", "gelu_new": "gelu", "gelu_fast": "gelu",
             "gelu_pytorch_tanh": "gelu", "relu": "relu"}
    if act not in table:
        raise ValueError(f"unsupported HF activation_function '{act}'")
    return table[act]


def config_from_hf(hf: Dict[str, Any]) -> DecoderConfig:
    """HF config.json dict → DecoderConfig."""
    mt = hf.get("model_type", "llama")
    if mt not in _FAMILIES:
        raise ValueError(f"unsupported model_type '{mt}'; "
                         f"supported: {_FAMILIES}")
    if mt == "mimo_v2":
        return _mimo_v2_config(hf)
    if mt == "deepseek_v3":
        return _deepseek_v3_config(hf)
    if mt == "cohere2_moe":
        return _cohere2_moe_config(hf)
    if mt == "nemotron_h":
        return _nemotron_h_config(hf)
    if mt == "granitemoehybrid":
        return _granitemoehybrid_config(hf)
    if mt == "jamba":
        return _jamba_config(hf)
    if mt == "glm_moe_dsa":
        return _glm_moe_dsa_config(hf)
    if mt == "lfm2_moe":
        return _lfm2_moe_config(hf)
    if mt == "xing4_0":
        return _xing4_config(hf)
    if mt == "qwen3_next":
        return _qwen3_next_config(hf)
    if mt == "bert":
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            intermediate_size=hf["intermediate_size"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 512),
            norm="layernorm",
            activation=_map_hf_act(hf.get("hidden_act", "gelu")),
            pos_emb="learned",
            norm_eps=float(hf.get("layer_norm_eps", 1e-12)),
            use_bias=True, tie_embeddings=True,
            causal=False, prenorm=False, embed_norm=True,
            type_vocab_size=int(hf.get("type_vocab_size", 2)),
            mlm_head=True)
    if mt == "distilbert":
        return DecoderConfig(
            hidden_size=hf["dim"],
            num_layers=hf["n_layers"],
            num_heads=hf["n_heads"],
            intermediate_size=hf["hidden_dim"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 512),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation", "gelu")),
            pos_emb="learned",
            norm_eps=1e-12,
            use_bias=True, tie_embeddings=True,
            causal=False, prenorm=False, embed_norm=True,
            type_vocab_size=0, mlm_head=True)
    if mt == "gpt_neox":
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            intermediate_size=hf["intermediate_size"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_hf_act(hf.get("hidden_act", "gelu")),
            pos_emb="rope",
            rope_theta=float(hf.get("rotary_emb_base", 10000.0)),
            rotary_pct=float(hf.get("rotary_pct", 0.25)),
            norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
            use_bias=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            parallel_block=bool(hf.get("use_parallel_residual", True)),
            parallel_block_norms=2)
    if mt == "gptj":
        dh = hf["n_embd"] // hf["n_head"]
        return DecoderConfig(
            hidden_size=hf["n_embd"],
            num_layers=hf["n_layer"],
            num_heads=hf["n_head"],
            intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("n_positions", 2048),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation_function",
                                          "gelu_new")),
            pos_emb="rope",
            rotary_pct=float(hf.get("rotary_dim") or dh) / dh,
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            use_bias=True, attn_bias=False,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            lm_head_bias=True,
            parallel_block=True, parallel_block_norms=1)
    if mt == "qwen":
        # Qwen v1 (reference: inference/v2/model_implementations/qwen/
        # model.py) — llama math, fused biased c_attn, always MHA with
        # head_dim = kv_channels; HF intermediate_size is 2x the real
        # per-projection FFN width (model.py:72)
        if not hf.get("no_bias", True):
            # no_bias=false puts biases on c_proj/w1/w2 too; we have no
            # slots for those — loading would silently drop them
            raise ValueError("qwen v1 checkpoints with no_bias=false are "
                             "not supported (c_proj/mlp biases)")
        dh = int(hf.get("kv_channels", 128))
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            intermediate_size=hf["intermediate_size"] // 2,
            vocab_size=hf["vocab_size"],
            max_seq_len=int(hf.get("seq_length", 8192)),
            norm="rmsnorm", activation="silu_glu", pos_emb="rope",
            rope_theta=float(hf.get("rotary_emb_base", 10000.0)),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-6)),
            use_bias=True, attn_out_bias=False, tie_embeddings=False,
            head_dim_override=(
                dh if dh * hf["num_attention_heads"] != hf["hidden_size"]
                else None))
    if mt == "internlm":
        # llama math with "bias": true on all four attention projections
        # (reference: module_inject/containers InternLMLayerPolicy); the
        # generic llama-layout loader picks up the bias tensors
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads"),
            intermediate_size=hf["intermediate_size"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="rmsnorm", activation="silu_glu", pos_emb="rope",
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            use_bias=False, attn_bias=bool(hf.get("bias", True)),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)))
    if mt == "gpt_neo":
        window = int(hf.get("window_size", 256))
        at = hf.get("attention_types") or \
            [[["global", "local"], hf["num_layers"] // 2]]
        kinds = []
        for types, count in at:
            kinds.extend(list(types) * int(count))
        pattern = tuple(0 if k == "global" else window for k in kinds)
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_layers"],
            num_heads=hf["num_heads"],
            intermediate_size=hf.get("intermediate_size")
            or 4 * hf["hidden_size"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation_function",
                                          "gelu_new")),
            pos_emb="learned",
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            use_bias=True, attn_bias=False, attn_out_bias=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            layer_window_pattern=pattern)
    if mt == "gpt2":
        return DecoderConfig(
            hidden_size=hf["n_embd"],
            num_layers=hf["n_layer"],
            num_heads=hf["n_head"],
            intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("n_positions", 1024),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation_function",
                                          "gelu_new")),
            pos_emb="learned",
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            use_bias=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)))
    if mt == "gpt_bigcode":
        H = hf["n_head"]
        return DecoderConfig(
            hidden_size=hf["n_embd"],
            num_layers=hf["n_layer"],
            num_heads=H,
            num_kv_heads=1 if hf.get("multi_query", True) else H,
            intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("n_positions", 1024),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation_function",
                                          "gelu_pytorch_tanh")),
            pos_emb="learned",
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            use_bias=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)))
    if mt == "opt":
        if not hf.get("do_layer_norm_before", True):
            raise ValueError("OPT post-norm variants (do_layer_norm_before="
                             "False, e.g. opt-350m) are not supported")
        if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
            raise ValueError("OPT word_embed_proj_dim != hidden_size "
                             "(opt-350m projection) is not supported")
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            intermediate_size=hf["ffn_dim"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation_function", "relu")),
            pos_emb="learned", use_bias=bool(hf.get("enable_bias", True)),
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)))
    if mt == "bloom":
        d = hf.get("hidden_size") or hf["n_embed"]
        return DecoderConfig(
            hidden_size=d,
            num_layers=hf["n_layer"],
            num_heads=hf["n_head"],
            intermediate_size=4 * d,
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("seq_length", 2048),
            norm="layernorm", activation="gelu", pos_emb="alibi",
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            use_bias=True, embed_norm=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)))
    if mt == "falcon":
        new_arch = bool(hf.get("new_decoder_architecture", False))
        H = hf["num_attention_heads"]
        if new_arch:
            kv = hf.get("num_kv_heads") or H
            norms = hf.get("num_ln_in_parallel_attn") or 2
        else:
            kv = 1 if hf.get("multi_query", True) else H
            norms = 1
        if not hf.get("parallel_attn", True):
            raise ValueError("falcon parallel_attn=False (falcon-rw) "
                             "layout is not supported")
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=H, num_kv_heads=kv,
            intermediate_size=hf.get("ffn_hidden_size") or 4 * hf["hidden_size"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=_map_hf_act(hf.get("activation", "gelu")),
            pos_emb="alibi" if hf.get("alibi") else "rope",
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            norm_eps=float(hf.get("layer_norm_epsilon", 1e-5)),
            use_bias=bool(hf.get("bias", False)), norm_bias=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
            parallel_block=True, parallel_block_norms=norms)
    if mt == "phi":
        if hf.get("qk_layernorm"):
            raise ValueError("phi qk_layernorm=True is not supported")
        return DecoderConfig(
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads")
            or hf["num_attention_heads"],
            intermediate_size=hf["intermediate_size"],
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm", activation="gelu", pos_emb="rope",
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rotary_pct=float(hf.get("partial_rotary_factor", 0.5)),
            norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
            use_bias=True,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            lm_head_bias=True,
            parallel_block=True, parallel_block_norms=1)
    kw = dict(
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads",
                            hf["num_attention_heads"]),
        intermediate_size=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 4096),
        norm="rmsnorm",
        activation="silu_glu",
        pos_emb="rope",
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        # qwen2: qkv bias only (use_bias); llama attention_bias=true (the
        # InternLM round-trip layout): biases on all four attention
        # projections via attn_bias — NOT use_bias, so the config
        # re-exports through the same llama+attention_bias branch
        use_bias=(mt in ("qwen2", "qwen2_moe")),
        attn_bias=True if bool(hf.get("attention_bias", False)) else None,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )
    # HF semantics differ per family: Mistral applies sliding_window
    # whenever set; Qwen2 gates it behind use_sliding_window=False BY
    # DEFAULT
    if mt == "phi3":
        if hf.get("rope_scaling"):
            raise ValueError("phi3 rope_scaling (longrope) is not "
                             "supported; use the base-context variant")
        kw["rotary_pct"] = float(hf.get("partial_rotary_factor", 1.0))
    use_swa_default = mt not in ("qwen2", "qwen2_moe")
    if hf.get("sliding_window") and hf.get("use_sliding_window",
                                           use_swa_default):
        kw["sliding_window"] = int(hf["sliding_window"])
    if mt == "mixtral":
        kw.update(num_experts=hf["num_local_experts"],
                  num_experts_per_tok=hf.get("num_experts_per_tok", 2))
    if mt == "qwen2_moe":
        if hf.get("decoder_sparse_step", 1) != 1 or \
                hf.get("mlp_only_layers"):
            raise ValueError(
                "qwen2_moe with interleaved dense layers "
                "(decoder_sparse_step != 1 / mlp_only_layers) is not "
                "supported — the stacked-layer scan needs uniform blocks")
        kw.update(
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf.get("num_experts_per_tok", 4),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            # experts use moe_intermediate_size; the config's dense
            # intermediate_size only applies to mlp_only layers (none)
            intermediate_size=hf["moe_intermediate_size"],
            shared_expert_size=hf["shared_expert_intermediate_size"],
            shared_expert_gate=True)
    if mt == "gemma":
        # gemma stores RMSNorm as (1 + w) — folded into `scale` at load —
        # plus GeGLU, sqrt(d)-scaled embeddings and a decoupled head_dim
        # (GemmaConfig's DEFAULT is 256, NOT hidden//heads)
        kw.update(activation="gelu_glu", scale_embeddings=True,
                  head_dim_override=int(hf.get("head_dim", 256)),
                  tie_embeddings=bool(hf.get("tie_word_embeddings", True)))
        if hf.get("final_logit_softcapping"):
            kw["logit_softcap"] = float(hf["final_logit_softcapping"])
    return DecoderConfig(**kw)


def _mimo_v2_config(hf: Dict[str, Any]) -> DecoderConfig:
    """MiMo-V2 / V2.5's language model (``modeling_mimo_v2``): a typed
    layer stack (models/typed_layers.py has the equations).
    ``hybrid_layer_pattern`` (0 full, 1 window) and ``moe_layer_freq``
    (0 dense, 1 sparse) may be longer than ``num_hidden_layers`` (a
    depth-cut file keeps the published lists): the first
    ``num_hidden_layers`` entries are read. The plain keys describe the
    full kind, the ``swa_*`` keys the window kind. ``expert_share``
    (not a published key: ``{"router_experts", "first_expert"}``) says the
    file's ``n_routed_experts`` experts are ONE chip's share of an
    expert-parallel layer whose router is ``router_experts`` wide. Not
    built: the vision / audio towers and the multi-token-prediction
    layers (no key of the language model's config names them)."""
    L = int(hf["num_hidden_layers"])
    heads, dk = int(hf["num_attention_heads"]), int(hf["head_dim"])
    for key, want in (("swa_num_attention_heads", heads),
                      ("swa_head_dim", dk),
                      ("swa_v_head_dim", hf.get("v_head_dim", dk)),
                      ("sliding_window_size", hf.get("sliding_window"))):
        if key in hf and hf[key] != want:
            raise ValueError(f"mimo_v2: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    # (its published module has no shared expert: the key is null there)
    for key in ("hybrid_block_size", "add_full_attention_sink_bias",
                "attention_bias", "n_shared_experts"):
        if hf.get(key):
            raise ValueError(f"mimo_v2: {key}={hf[key]!r} is not built")
    if _rope_type(hf) != "default":
        raise ValueError(f"mimo_v2: rope_scaling {hf['rope_scaling']!r} is "
                         f"not built")
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L, num_heads=heads,
        num_kv_heads=hf["num_key_value_heads"],
        head_dim_override=dk, v_head_dim=int(hf.get("v_head_dim", dk)),
        value_scale=float(hf.get("attention_value_scale") or 1.0),
        intermediate_size=hf["moe_intermediate_size"],
        dense_intermediate_size=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 4096),
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        norm_eps=float(hf.get("layernorm_epsilon", 1e-5)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rotary_pct=float(hf.get("partial_rotary_factor", 1.0)),
        use_bias=False,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_kinds=tuple(int(a) for a in hf["hybrid_layer_pattern"][:L]),
        layer_sparse=tuple(int(a) for a in hf["moe_layer_freq"][:L]),
        sliding_window=int(hf["sliding_window"]),
        window_kv_heads=int(hf.get("swa_num_key_value_heads",
                                   hf["num_key_value_heads"])),
        window_rope_theta=float(hf.get("swa_rope_theta",
                                       hf.get("rope_theta", 10000.0))),
        window_sink=bool(hf.get("add_swa_attention_sink_bias", False)),
        **_sigmoid_router(hf, "mimo_v2"))


def _layer_kinds(hf: Dict[str, Any], fam: str, names: Dict[str, int]
                 ) -> tuple:
    """``layer_types`` → ``DecoderConfig.layer_kinds`` by a family's names
    for its mixers; the list may be longer than ``num_hidden_layers`` (a
    depth-cut file keeps the published list: the first entries are read). A
    short list or an unknown name is refused by name."""
    L = int(hf["num_hidden_layers"])
    if len(hf["layer_types"]) < L:
        raise ValueError(f"{fam}: layer_types has "
                         f"{len(hf['layer_types'])} entries for {L} layers")
    for name in hf["layer_types"][:L]:
        if name not in names:
            raise ValueError(f"{fam}: layer type {name!r} is not built "
                             f"(expected one of {sorted(names)})")
    return tuple(names[n] for n in hf["layer_types"][:L])


def _rope_type(hf: Dict[str, Any]) -> str:
    scaling = hf.get("rope_scaling") or hf.get("rope_parameters") or {}
    return scaling.get("rope_type", scaling.get("type", "default"))


def _sigmoid_router(hf: Dict[str, Any], family: str) -> Dict[str, Any]:
    """The router keys the sigmoid families share (DeepSeek-V3's gate, which
    MiMo-V2 took over) → DecoderConfig fields: a score per expert, a
    selection-only bias (``noaux_tc``), group-limited selection
    (``n_group`` / ``topk_group``), ``norm_topk_prob``,
    ``routed_scaling_factor`` and ``n_shared_experts`` shared experts of
    ``moe_intermediate_size`` each. ``expert_share`` (not a published key:
    ``{"router_experts", "first_expert"}``) says the file's
    ``n_routed_experts`` experts are ONE chip's share of an expert-parallel
    layer whose router is ``router_experts`` wide."""
    for key, want in (("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu")):
        if key in hf and hf[key] != want:
            raise ValueError(f"{family}: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    held = int(hf["n_routed_experts"])
    share = hf.get("expert_share")
    return dict(
        num_experts=int(share["router_experts"]) if share else held,
        experts_held=(int(share["first_expert"]), held) if share else None,
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        router_scoring="sigmoid", router_select_bias=True,
        router_groups=int(hf.get("n_group") or 1),
        router_groups_kept=int(hf.get("topk_group") or 1),
        routed_scale=float(hf.get("routed_scaling_factor") or 1.0),
        shared_expert_size=int(hf.get("n_shared_experts") or 0) *
        int(hf["moe_intermediate_size"]))


def _deepseek_v3_config(hf: Dict[str, Any]) -> DecoderConfig:
    """DeepSeek-V3's block (HF ``deepseek_v3``; GigaChat3.1 publishes it
    with a V head of 192): a typed stack of LATENT attention layers
    (models/typed_layers.py has the equations), ``first_k_dense_replace``
    leading dense layers, then sparse layers every ``moe_layer_freq``-th:
    a group-limited sigmoid router, the kept weights scaled, a shared
    expert. ``rope_scaling``: YaRN or none. ``num_key_value_heads``
    answers as published whatever the cache holds (one latent row a
    token). Not built, and accepted: ``num_nextn_predict_layers`` (the
    multi-token-prediction module; HF's ``deepseek_v3`` drops those weights
    on load as well). ``expert_share``: :func:`_sigmoid_router`. A stream
    of several hidden states (``hc_mult`` over 1) is ``xing4_0``'s
    (:func:`_xing4_config`, which takes its keys out before it calls this):
    refused by name here, not ignored."""
    for key, want in (("attention_bias", False), ("ep_size", 1),
                      ("hc_mult", 1)):
        if hf.get(key, want) != want:
            raise ValueError(f"deepseek_v3: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim"):
        if not hf.get(key):
            raise ValueError(f"deepseek_v3: {key}={hf.get(key)!r} is not "
                             f"built (a latent layer needs every width)")
    rope_type, yarn = _rope_type(hf), None
    if rope_type == "yarn":
        sc = hf["rope_scaling"]
        yarn = (float(sc["factor"]),
                int(sc["original_max_position_embeddings"]),
                *(float(sc.get(key) or default) for key, default in (
                    ("beta_fast", 32), ("beta_slow", 1), ("mscale", 0),
                    ("mscale_all_dim", 0))))
    elif rope_type != "default":
        raise ValueError(f"deepseek_v3: rope_scaling {hf['rope_scaling']!r} "
                         f"is not built (yarn or none)")
    L = int(hf["num_hidden_layers"])
    dense, freq = int(hf.get("first_k_dense_replace", 0)), \
        int(hf.get("moe_layer_freq", 1))
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads"),
        head_dim_override=nope + rope, v_head_dim=int(hf["v_head_dim"]),
        q_lora_rank=int(hf["q_lora_rank"]),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        intermediate_size=hf["moe_intermediate_size"],
        dense_intermediate_size=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 4096),
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rope_theta=float(hf.get("rope_theta", 10000.0)), rope_yarn=yarn,
        use_bias=False,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_kinds=(2,) * L,
        layer_sparse=tuple(int(l >= dense and l % freq == 0)
                           for l in range(L)),
        **_sigmoid_router(hf, "deepseek_v3"))


def _glm_moe_dsa_config(hf: Dict[str, Any]) -> DecoderConfig:
    """GLM-5.2's block (``model_type: glm_moe_dsa``): DeepSeek-V3's latent
    block (:func:`_deepseek_v3_config` reads the widths — the file's
    ``head_dim`` is the NOPE width and is not a head's; a head is
    ``qk_nope_head_dim + qk_rope_head_dim`` —, the flat sigmoid router and
    the shared expert) with DeepSeek-V3.2's sparse-attention INDEXER in
    front of the softmax (models/typed_layers.py has the equations): a
    layer whose ``indexer_types`` entry is ``full`` owns an indexer
    (``index_n_heads`` heads of ``index_head_dim``, one key a token), one
    whose entry is ``shared`` borrows the picks of the nearest ``full``
    layer below it; a query reads the ``index_topk`` keys picked for it.
    ``mlp_layer_types`` says which layers are dense and is held against
    ``first_k_dense_replace`` / ``moe_layer_freq``; the rotary base lies in
    ``rope_parameters``. Without ``indexer_types`` the list is made from
    ``index_topk_freq`` / ``index_skip_topk_offset`` as the published one
    is: the layers below the offset own one, then every ``freq``-th (three
    borrowers, then an owner).
    Not built, and refused by name: ``index_topk_pattern``, a rotary
    scaling, a first layer that borrows. Accepted and not built, as the
    parent family's: ``num_nextn_predict_layers`` (with
    ``index_share_for_mtp_iteration``, which speaks of that module alone).
    ``rope_interleave`` / ``indexer_rope_interleave`` name the published
    tensors' pair order: HF de-interleaves and rotates halves, a weight
    loader's permutation (the assumption ``deepseek_v3`` makes too)."""
    L = int(hf["num_hidden_layers"])
    params = hf.get("rope_parameters") or {}
    if hf.get("index_topk_pattern") is not None:
        raise ValueError(f"glm_moe_dsa: index_topk_pattern="
                         f"{hf['index_topk_pattern']!r} is not built (one "
                         f"index_topk for every layer)")
    if params.get("rope_type", "default") != "default" or \
            hf.get("rope_scaling"):
        raise ValueError(f"glm_moe_dsa: rope_parameters {params!r} / "
                         f"rope_scaling {hf.get('rope_scaling')!r} is not "
                         f"built (the default rotary alone)")
    for key in ("index_n_heads", "index_head_dim", "index_topk"):
        if not hf.get(key):
            raise ValueError(f"glm_moe_dsa: {key}={hf.get(key)!r} is not "
                             f"built (the indexer needs every width)")
    base = _deepseek_v3_config({
        **{k: v for k, v in hf.items() if k != "rope_parameters"},
        "rope_theta": params.get("rope_theta", hf.get("rope_theta", 1e4))})
    mlp = hf.get("mlp_layer_types")
    if mlp is not None:
        built = tuple("sparse" if s else "dense" for s in base.layer_sparse)
        if tuple(mlp) != built:
            raise ValueError(
                f"glm_moe_dsa: mlp_layer_types {list(mlp)!r} is not what "
                f"first_k_dense_replace={hf.get('first_k_dense_replace')!r}"
                f" / moe_layer_freq={hf.get('moe_layer_freq', 1)!r} build "
                f"({list(built)!r})")
    types = hf.get("indexer_types")
    if types is None:
        freq = int(hf.get("index_topk_freq") or 1)
        skip = int(hf.get("index_skip_topk_offset") or 0)
        types = ["full" if l < skip or (l - skip) % freq == freq - 1
                 else "shared" for l in range(L)]
    if len(types) != L or set(types) - {"full", "shared"}:
        raise ValueError(f"glm_moe_dsa: indexer_types {list(types)!r} is "
                         f"not built ({L} entries of 'full' / 'shared')")
    if types[0] != "full":
        raise ValueError("glm_moe_dsa: indexer_types[0]='shared' is not "
                         "built (a layer borrows from a 'full' layer "
                         "below it, and the first has none)")
    return dataclasses.replace(
        base, index_heads=int(hf["index_n_heads"]),
        index_head_dim=int(hf["index_head_dim"]),
        index_topk=int(hf["index_topk"]),
        layer_indexer=tuple(int(t == "full") for t in types))


def _cohere2_moe_config(hf: Dict[str, Any]) -> DecoderConfig:
    """Cohere2-MoE's language model (Command A+; ``model_type:
    cohere2_moe``): a typed stack (models/typed_layers.py has the
    equations) of PARALLEL blocks under one bias-free LayerNorm
    (``layer_norm_eps``; the published ``rms_norm_eps`` is null and a file
    may leave it out) — ``layer_types`` names each layer
    ``sliding_attention`` (window ``sliding_window``, interleaved rotary:
    ``position_embedding_type: rope_gptj``, ``rotary_pct`` of the head) or
    ``full_attention`` (NO positional term), and may be longer than
    ``num_hidden_layers`` (a depth-cut file keeps the published list: the
    first ``num_hidden_layers`` entries are read). Every layer is sparse:
    a sigmoid router ``num_experts`` wide with NO selection bias, top-k
    renormalised, experts of ``intermediate_size``, ``num_shared_experts``
    shared experts of the same width AVERAGED; the head is tied. ONE
    published key names both the router's width and the expert count, so
    a share is told apart by ``expert_share`` (not a published key:
    ``{"router_experts", "first_expert", "held_experts"}``): the router
    keeps ``num_experts`` outputs and the weights hold ``held_experts``.
    ``logit_scale`` multiplies the logits (``logits_scaling`` = its
    inverse). Refused by name: QK norm, attention biases, leading dense
    layers (``first_k_dense_replace`` > 0; with none,
    ``prefix_dense_*`` name no layer), any ``rope_type`` but ``default``.
    Not built: the vision tower (no key of the language model's config)."""
    fam = "cohere2_moe"
    for key, want in (("use_qk_norm", False), ("attention_bias", False),
                      ("first_k_dense_replace", 0),
                      ("use_parallel_block", True),
                      ("use_gated_activation", True),
                      ("hidden_act", "silu"),
                      ("expert_selection_fn", "sigmoid"),
                      ("position_embedding_type", "rope_gptj"),
                      ("shared_expert_combination_strategy", "average"),
                      ("tie_word_embeddings", True)):
        if hf.get(key, want) != want:
            raise ValueError(f"{fam}: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    if _rope_type(hf) != "default":
        raise ValueError(f"{fam}: rope_type {_rope_type(hf)!r} is not "
                         f"built (expected 'default')")
    L = int(hf["num_hidden_layers"])
    kinds = _layer_kinds(hf, fam, {"full_attention": 0, "sliding_attention": 1})
    share = hf.get("expert_share")
    E = int(hf["num_experts"])
    if share and int(share["router_experts"]) != E:
        raise ValueError(
            f"{fam}: expert_share.router_experts="
            f"{share['router_experts']!r} is not num_experts={E} (the one "
            f"published key is the router's width; the share's count is "
            f"expert_share.held_experts)")
    shared_n = int(hf.get("num_shared_experts") or 0)
    width = int(hf["intermediate_size"])
    theta = float((hf.get("rope_parameters") or {}).get(
        "rope_theta", hf.get("rope_theta", 10000.0)))
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim_override=int(hf.get(
            "head_dim", hf["hidden_size"] // hf["num_attention_heads"])),
        intermediate_size=width, vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 8192),
        norm="layernorm", norm_bias=False, use_bias=False,
        norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
        activation="silu_glu", pos_emb="rope", rope_theta=theta,
        rotary_pct=float(hf.get("rotary_pct", 1.0)),
        rope_interleaved=True, full_attn_rope=False,
        parallel_block=True, parallel_block_norms=1, tie_embeddings=True,
        layer_kinds=kinds,
        sliding_window=int(hf["sliding_window"]),
        num_experts=E, num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        router_scoring="sigmoid", router_select_bias=False,
        experts_held=(int(share["first_expert"]),
                      int(share["held_experts"])) if share else None,
        shared_expert_size=shared_n * width,
        shared_experts_averaged=max(shared_n, 1),
        logits_scaling=1.0 / float(hf.get("logit_scale") or 1))


def _nemotron_h_config(hf: Dict[str, Any]) -> DecoderConfig:
    """Nemotron-H's hybrid stack (Nemotron 3 Nano; ``model_type:
    nemotron_h``): a typed stack (models/typed_layers.py has the equations)
    whose ``hybrid_override_pattern`` gives each layer ONE part under one
    RMSNorm (``norm_eps``): ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads
    of ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
    ``conv_kernel``; the inner width is heads x head size, NOT ``expand`` x
    hidden), ``*`` attention (``num_attention_heads`` / ``num_key_value_
    heads`` of ``head_dim``, NO positional term: the family's attention
    builds none, so ``rope_theta`` / ``partial_rotary_factor`` are read by
    nothing), ``E`` the experts (a sigmoid router with a selection bias,
    top-``num_experts_per_tok`` renormalised x ``routed_scaling_factor``;
    UN-GATED ``relu2`` experts of ``moe_intermediate_size``; one shared
    expert of ``moe_shared_expert_intermediate_size``); an untied head. The
    pattern may be longer than ``num_hidden_layers`` (a depth-cut file may
    keep it whole): the first ``num_hidden_layers`` letters are read, and
    an unknown letter is refused by name (``-``, the family's dense MLP
    layer, is not built). ``expert_share``: :func:`_sigmoid_router`.
    Refused by name: biases other than the convolution's, gated or other
    activations, a ``time_step_limit`` (a clamp on the step), more than one
    shared expert."""
    fam = "nemotron_h"
    for key, want in (("attention_bias", False), ("mlp_bias", False),
                      ("mamba_proj_bias", False), ("use_bias", False),
                      ("use_conv_bias", True), ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("n_shared_experts", 1),
                      ("tie_word_embeddings", False),
                      ("time_step_limit", None)):
        if hf.get(key, want) != want:
            raise ValueError(f"{fam}: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    L = int(hf["num_hidden_layers"])
    pattern = str(hf["hybrid_override_pattern"])
    if len(pattern) < L:
        raise ValueError(f"{fam}: hybrid_override_pattern has "
                         f"{len(pattern)} letters for {L} layers")
    parts = {"M": (3, -1), "*": (0, -1), "E": (-1, 1)}
    for l, letter in enumerate(pattern[:L]):
        if letter not in parts:
            raise ValueError(
                f"{fam}: hybrid_override_pattern letter {letter!r} (layer "
                f"{l}) is not built (expected one of {sorted(parts)})")
    kinds, sparse = zip(*(parts[letter] for letter in pattern[:L]))
    router = _sigmoid_router(hf, fam)
    router["shared_expert_size"] = int(
        hf["moe_shared_expert_intermediate_size"])
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim_override=int(hf["head_dim"]),
        intermediate_size=hf["moe_intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 4096),
        norm="rmsnorm", activation="relu2", pos_emb="rope",
        norm_eps=float(hf.get("norm_eps", hf.get("layer_norm_epsilon",
                                                 1e-5))),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        full_attn_rope=False, use_bias=False, tie_embeddings=False,
        layer_kinds=kinds, layer_sparse=sparse,
        ssm_heads=int(hf["mamba_num_heads"]),
        ssm_head_dim=int(hf["mamba_head_dim"]),
        ssm_groups=int(hf["n_groups"]),
        ssm_state_size=int(hf["ssm_state_size"]),
        ssm_conv_kernel=int(hf["conv_kernel"]), **router)


def _granitemoehybrid_config(hf: Dict[str, Any]) -> DecoderConfig:
    """GraniteMoeHybrid's stack (Granite 4.0-H; ``model_type:
    granitemoehybrid``): a typed stack (models/typed_layers.py has the
    equations) whose EVERY layer is a mixer AND the experts under two
    RMSNorms (``rms_norm_eps``). ``layer_types`` names each layer's mixer:
    ``mamba`` a Mamba-2 mixer (``mamba_n_heads`` heads of ``mamba_d_head``
    — which has to be ``mamba_expand`` x hidden —, ``mamba_n_groups``,
    ``mamba_d_state``, ``mamba_d_conv``) or ``attention``
    (``num_attention_heads`` / ``num_key_value_heads`` heads of hidden /
    heads, ``position_embedding_type: nope``: NO positional term, so
    ``rope_theta`` is held and read by nothing); the list may be longer
    than ``num_hidden_layers`` (the first ``num_hidden_layers`` entries are
    read). Every layer ends in ``num_local_experts`` SiLU-GLU experts of
    ``intermediate_size`` — ``num_experts_per_tok`` a token, weighed by the
    softmax over the kept router logits — beside one shared expert of
    ``shared_intermediate_size``. Four scalars: ``embedding_multiplier``,
    ``attention_multiplier`` (the scores' factor), ``residual_multiplier``
    (each branch sum), ``logits_scaling`` (a divisor); the head is tied.
    ONE published key names both the router's width and the expert count,
    so a share is ``expert_share`` (not a published key:
    ``{"first_expert", "held_experts"}``; ``router_experts``, where given,
    has to be ``num_local_experts``). Refused by name: biases other than
    the convolution's, a ``position_embedding_type`` other than ``nope``,
    ``rope_scaling``, another activation or norm, an untied head, a stack
    with no shared expert, an unknown layer type."""
    fam = "granitemoehybrid"
    for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True), ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm"),
                      ("position_embedding_type", "nope"),
                      ("rope_scaling", None),
                      ("tie_word_embeddings", True)):
        if hf.get(key, want) != want:
            raise ValueError(f"{fam}: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    L = int(hf["num_hidden_layers"])
    kinds = _layer_kinds(hf, fam, {"mamba": 3, "attention": 0})
    heads, p_dim = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    if heads * p_dim != int(hf["mamba_expand"]) * int(hf["hidden_size"]):
        raise ValueError(
            f"{fam}: mamba_n_heads x mamba_d_head = {heads * p_dim} is not "
            f"mamba_expand x hidden_size = "
            f"{int(hf['mamba_expand']) * int(hf['hidden_size'])}")
    if not int(hf.get("shared_intermediate_size") or 0):
        raise ValueError(f"{fam}: shared_intermediate_size="
                         f"{hf.get('shared_intermediate_size')!r} is not "
                         f"built (every layer has one shared expert)")
    E = int(hf["num_local_experts"])
    share = hf.get("expert_share")
    if share and int(share.get("router_experts", E)) != E:
        raise ValueError(
            f"{fam}: expert_share.router_experts="
            f"{share['router_experts']!r} is not num_local_experts={E} "
            f"(the one published key is the router's width; the share's "
            f"count is expert_share.held_experts)")
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        intermediate_size=int(hf["intermediate_size"]),
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 4096),
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        full_attn_rope=False, use_bias=False, tie_embeddings=True,
        layer_kinds=kinds,
        layer_sparse=(1,) * L,
        ssm_heads=heads, ssm_head_dim=p_dim,
        ssm_groups=int(hf["mamba_n_groups"]),
        ssm_state_size=int(hf["mamba_d_state"]),
        ssm_conv_kernel=int(hf["mamba_d_conv"]),
        num_experts=E, num_experts_per_tok=int(hf["num_experts_per_tok"]),
        router_scoring="softmax", norm_topk_prob=True,
        router_select_bias=False,
        experts_held=(int(share["first_expert"]),
                      int(share["held_experts"])) if share else None,
        shared_expert_size=int(hf["shared_intermediate_size"]),
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        logits_scaling=float(hf.get("logits_scaling", 1.0)),
        attention_multiplier=float(hf["attention_multiplier"])
        if hf.get("attention_multiplier") is not None else None)


def _jamba_config(hf: Dict[str, Any]) -> DecoderConfig:
    """Jamba's stack (AI21 Jamba2-3B; ``model_type: jamba``): a typed stack
    (models/typed_layers.py has the equations) whose EVERY layer is a mixer
    AND a dense SiLU-GLU of ``intermediate_size`` under two RMSNorms
    (``rms_norm_eps``). Layer ``l`` is attention where ``l %
    attn_layer_period == attn_layer_offset`` (``num_attention_heads`` /
    ``num_key_value_heads`` heads of hidden / heads, NO positional term: the
    family builds none), else a Mamba-1 SELECTIVE-SCAN mixer (kind 4):
    ``mamba_expand`` x hidden channels, ``mamba_d_state`` states a channel,
    a step size a channel through ``mamba_dt_rank``, a convolution of
    ``mamba_d_conv`` taps over the channels alone, RMSNorms on the step's
    bottleneck, ``B`` and ``C``. ``num_experts: 1`` is the family's dense
    MLP in every layer (``expert_layer_offset`` / ``expert_layer_period``
    then choose nothing). Refused by name: ``num_experts`` above 1 (the
    family's sparse layers are not built), projection biases, a convolution
    without its bias, another activation, a sliding window."""
    fam = "jamba"
    for key, want in (("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("hidden_act", "silu"), ("sliding_window", None),
                      ("num_experts", 1), ("num_experts_per_tok", 1)):
        if hf.get(key, want) != want:
            raise ValueError(f"{fam}: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    L = int(hf["num_hidden_layers"])
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        intermediate_size=int(hf["intermediate_size"]),
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 262144),
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        full_attn_rope=False, use_bias=False,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_kinds=tuple(0 if l % period == offset else 4
                          for l in range(L)),
        layer_sparse=(0,) * L,
        # (one "expert" a token: the dense MLP; ``layer_sparse`` is what the
        # layers read, and no router is built)
        num_experts=1, num_experts_per_tok=1,
        ssm_inner_size=int(hf["mamba_expand"]) * int(hf["hidden_size"]),
        ssm_state_size=int(hf["mamba_d_state"]),
        ssm_dt_rank=int(hf["mamba_dt_rank"]),
        ssm_conv_kernel=int(hf["mamba_d_conv"]))


def _lfm2_moe_config(hf: Dict[str, Any]) -> DecoderConfig:
    """LFM2-MoE's stack (Liquid AI LFM2-24B-A2B; ``model_type: lfm2_moe``),
    the published names as they are: a typed stack (models/typed_layers.py
    has the equations) whose EVERY layer is a mixer AND a feed-forward part
    under two RMSNorms (``norm_eps``). ``layer_types`` names each layer's
    mixer: ``conv`` a GATED SHORT CONVOLUTION (kind 5: ``conv_L_cache`` taps
    over the hidden size, no bias) or ``full_attention``
    (``num_attention_heads`` / ``num_key_value_heads`` heads of hidden /
    heads, each q and k head under an RMSNorm before rotate-half RoPE, θ
    nested in ``rope_parameters``). The first ``num_dense_layers`` layers
    end in a dense SiLU-GLU of ``intermediate_size``, the others in
    ``num_experts`` experts of ``moe_intermediate_size`` —
    ``num_experts_per_tok`` a token by sigmoid scores, ``use_expert_bias``:
    a bias that moves the pick and never the weight, the kept scores over
    their sum + 1e-6 (``norm_topk_prob``), times
    ``routed_scaling_factor``; no shared expert. The head is tied unless
    ``tie_word_embeddings`` says otherwise (the published file has no such
    key; HF's ``Lfm2MoeConfig`` ties by default). ONE published key names
    both the router's width and the expert count, so a share is
    ``expert_share`` (not a published key: ``{"router_experts",
    "first_expert", "held_experts"}``, as ``cohere2_moe``). Refused by
    name: ``conv_bias``, an unknown layer type, any ``rope_type`` but
    ``default``."""
    fam = "lfm2_moe"
    if hf.get("conv_bias", False):
        raise ValueError(f"{fam}: conv_bias={hf['conv_bias']!r} is not "
                         f"built (expected False)")
    if _rope_type(hf) != "default":
        raise ValueError(f"{fam}: rope_type {_rope_type(hf)!r} is not "
                         f"built (expected 'default')")
    L = int(hf["num_hidden_layers"])
    kinds = _layer_kinds(hf, fam, {"conv": 5, "full_attention": 0})
    E = int(hf["num_experts"])
    share = hf.get("expert_share")
    if share and int(share["router_experts"]) != E:
        raise ValueError(
            f"{fam}: expert_share.router_experts="
            f"{share['router_experts']!r} is not num_experts={E} (the one "
            f"published key is the router's width; the share's count is "
            f"expert_share.held_experts)")
    dense = int(hf.get("num_dense_layers", 0))
    theta = float((hf.get("rope_parameters") or {}).get(
        "rope_theta", hf.get("rope_theta", 1000000.0)))
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        intermediate_size=int(hf["moe_intermediate_size"]),
        dense_intermediate_size=int(hf["intermediate_size"]),
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 128000),
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        norm_eps=float(hf.get("norm_eps", 1e-5)), rope_theta=theta,
        use_bias=False, qk_head_norm=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        layer_kinds=kinds,
        layer_sparse=tuple(0 if l < dense else 1 for l in range(L)),
        ssm_conv_kernel=int(hf["conv_L_cache"]),
        num_experts=E, num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        router_scoring="sigmoid",
        router_select_bias=bool(hf.get("use_expert_bias", True)),
        router_norm_eps=1e-6,
        routed_scale=float(hf.get("routed_scaling_factor") or 1.0),
        experts_held=(int(share["first_expert"]),
                      int(share["held_experts"])) if share else None)


def _qwen3_next_config(hf: Dict[str, Any]) -> DecoderConfig:
    """Qwen3-Next's stack (Qwen3-Next-80B-A3B; ``model_type: qwen3_next``):
    a typed stack (models/typed_layers.py has the equations) whose EVERY
    layer is a mixer AND the experts under two ZERO-CENTRED RMSNorms
    (``rms_norm_eps``; ``x̂·(1 + w)``: :func:`fold_zero_centred` folds the
    ``1 +`` into the trees' ``scale``). Layer ``l`` is FULL attention where
    ``(l + 1) % full_attention_interval == 0`` (``layer_types``, where the
    file has the list, is read in its place: ``full_attention`` /
    ``linear_attention``) — ``num_attention_heads`` / ``num_key_value_heads``
    heads of ``head_dim``, a q / k head norm, rotate-half RoPE on
    ``partial_rotary_factor`` of the head, an output gate from a ``q_proj``
    twice as wide — and a GATED DELTA RULE (kind 6) everywhere else:
    ``linear_num_value_heads`` value heads of ``linear_value_head_dim`` over
    ``linear_num_key_heads`` key heads of ``linear_key_head_dim``, a
    convolution of ``linear_conv_kernel_dim`` taps over ``[q | k | v]``.
    Every layer (``decoder_sparse_step`` 1, ``mlp_only_layers`` []) ends in
    ``num_experts`` SiLU-GLU experts of ``moe_intermediate_size`` —
    ``num_experts_per_tok`` a token by the softmax over all, renormalised
    over the kept (``norm_topk_prob``) — beside one shared expert of
    ``shared_expert_intermediate_size`` behind a sigmoid gate; the head is
    untied. ONE published key names both the router's width and the expert
    count, so a share is ``expert_share`` (not a published key:
    ``{"router_experts", "first_expert", "held_experts"}``, as
    ``lfm2_moe``). Accepted and not built: ``intermediate_size`` (the width
    of ``mlp_only_layers``, of which there are none) and the family's
    multi-token-prediction module (no key of the config describes one).
    Refused by name: biases, ``rope_scaling``, a sliding window, dense
    layers (``decoder_sparse_step`` / ``mlp_only_layers``), a stack with no
    shared expert, another activation, an unknown layer type."""
    fam = "qwen3_next"
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", [])):
        if hf.get(key, want) != want:
            raise ValueError(f"{fam}: {key}={hf[key]!r} is not built "
                             f"(expected {want!r})")
    if not int(hf.get("shared_expert_intermediate_size") or 0):
        raise ValueError(f"{fam}: shared_expert_intermediate_size="
                         f"{hf.get('shared_expert_intermediate_size')!r} is "
                         f"not built (every layer has one shared expert)")
    L = int(hf["num_hidden_layers"])
    if hf.get("layer_types") is not None:
        kinds = _layer_kinds(hf, fam, {"linear_attention": 6,
                                       "full_attention": 0})
    else:
        every = int(hf.get("full_attention_interval", 4))
        kinds = tuple(0 if (l + 1) % every == 0 else 6 for l in range(L))
    hv, hk = int(hf["linear_num_value_heads"]), int(hf["linear_num_key_heads"])
    if hv % hk:
        raise ValueError(f"{fam}: linear_num_key_heads={hk} does not divide "
                         f"linear_num_value_heads={hv}")
    E = int(hf["num_experts"])
    share = hf.get("expert_share")
    if share and int(share["router_experts"]) != E:
        raise ValueError(
            f"{fam}: expert_share.router_experts="
            f"{share['router_experts']!r} is not num_experts={E} (the one "
            f"published key is the router's width; the share's count is "
            f"expert_share.held_experts)")
    return DecoderConfig(
        hidden_size=hf["hidden_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim_override=int(hf["head_dim"]),
        intermediate_size=int(hf["moe_intermediate_size"]),
        vocab_size=hf["vocab_size"],
        max_seq_len=hf.get("max_position_embeddings", 262144),
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rotary_pct=float(hf.get("partial_rotary_factor", 1.0)),
        use_bias=False, qk_head_norm=True, attn_output_gate=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_kinds=kinds, layer_sparse=(1,) * L,
        ssm_heads=hv, ssm_head_dim=int(hf["linear_value_head_dim"]),
        ssm_groups=hk, ssm_state_size=int(hf["linear_key_head_dim"]),
        ssm_conv_kernel=int(hf["linear_conv_kernel_dim"]),
        num_experts=E, num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        router_scoring="softmax", router_select_bias=False,
        shared_expert_size=int(hf["shared_expert_intermediate_size"]),
        shared_expert_gate=True,
        experts_held=(int(share["first_expert"]),
                      int(share["held_experts"])) if share else None)


def fold_zero_centred(w: np.ndarray) -> np.ndarray:
    """A zero-centred RMSNorm's published weight ``w`` (``x̂·(1 + w)``:
    Gemma's norm, Qwen3-Next's) → the ``scale`` this repo's norm multiplies
    by, float32: the ``1 +`` is paid once at load and adds no operation to
    a program."""
    return np.asarray(w, np.float32) + 1.0


def _load_qwen3_next(cfg: DecoderConfig, get, dtype) -> Params:
    """The published ``Qwen3NextForCausalLM`` tensors → a typed stack's
    tree (``typed_layers.init_typed_params``' names and shapes: ``[in,
    out]`` matrices). Every ``*layernorm`` / ``q_norm`` / ``k_norm`` /
    ``model.norm`` weight is ZERO-CENTRED and folded
    (:func:`fold_zero_centred`); ``linear_attn.norm`` is NOT. ``q_proj``'s
    rows are ``[q | gate]`` a HEAD: split into ``wq`` and ``wq_gate``.
    ``in_proj_qkvz``'s rows are ``[q | k | v·R | z·R]`` a KEY head (``R``
    value heads a key head), ``in_proj_ba``'s ``[b·R | a·R]`` likewise:
    regrouped into ``[q | k | v | z]`` and ``[b | a]`` blocks over all
    heads; ``conv1d`` ``[C, 1, K]`` → ``conv_w [C, K]`` (its channels are
    ``[q | k | v]`` already). A share (``cfg.experts_held``) loads its own
    experts."""
    def T(name):
        return np.ascontiguousarray(get(name).T).astype(dtype)

    def folded(name):
        return {"scale": fold_zero_centred(get(name))}

    d, H, dk = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    hv, hk, n, p_dim = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state_size, \
        cfg.ssm_head_dim
    r = hv // hk
    first, held = cfg.experts_held or (0, cfg.num_experts)
    layers = []
    for l, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{l}."
        lp = {"ln1": folded(pre + "input_layernorm.weight"),
              "ln2": folded(pre + "post_attention_layernorm.weight")}
        if kind == 6:
            a = pre + "linear_attn."
            qkvz = T(a + "in_proj_qkvz.weight").reshape(
                d, hk, 2 * n + 2 * r * p_dim)
            cuts = np.cumsum([n, n, r * p_dim])
            ba = T(a + "in_proj_ba.weight").reshape(d, hk, 2 * r)
            lp["ssm"] = {
                "w_in": np.concatenate(
                    [part.reshape(d, -1)
                     for part in np.split(qkvz, cuts, axis=-1)], axis=-1),
                "w_ba": np.concatenate([ba[..., :r].reshape(d, hv),
                                        ba[..., r:].reshape(d, hv)], axis=-1),
                "conv_w": get(a + "conv1d.weight")[:, 0].astype(dtype),
                "dt_bias": get(a + "dt_bias").astype(np.float32),
                "A_log": get(a + "A_log").astype(np.float32),
                "norm": {"scale": get(a + "norm.weight").astype(np.float32)},
                "w_out": T(a + "out_proj.weight")}
        else:
            a = pre + "self_attn."
            qg = T(a + "q_proj.weight").reshape(d, H, 2 * dk)
            lp["attn"] = {
                "wq": qg[..., :dk].reshape(d, H * dk),
                "wq_gate": qg[..., dk:].reshape(d, H * dk),
                "wk": T(a + "k_proj.weight"), "wv": T(a + "v_proj.weight"),
                "wo": T(a + "o_proj.weight"),
                "q_norm": folded(a + "q_norm.weight"),
                "k_norm": folded(a + "k_norm.weight")}
        m = pre + "mlp."
        experts = range(first, first + held)
        lp["moe"] = {"router": T(m + "gate.weight"), **{
            ours: np.stack([T(f"{m}experts.{e}.{theirs}.weight")
                            for e in experts])
            for ours, theirs in (("wg", "gate_proj"), ("wi", "up_proj"),
                                 ("wo", "down_proj"))}}
        lp["shared"] = {"wg": T(m + "shared_expert.gate_proj.weight"),
                        "wi": T(m + "shared_expert.up_proj.weight"),
                        "wo": T(m + "shared_expert.down_proj.weight"),
                        "gate": T(m + "shared_expert_gate.weight")}
        layers.append(lp)
    return {"embed": {"tokens": get("model.embed_tokens.weight"
                                    ).astype(dtype)},
            "layers": layers,
            "final_norm": folded("model.norm.weight"),
            "lm_head": T("lm_head.weight")}


def _is_gemma_layout(cfg: DecoderConfig) -> bool:
    return cfg.activation == "gelu_glu" and cfg.scale_embeddings


def _no_exotics(cfg: DecoderConfig) -> bool:
    """Features NO classic (gpt2/bigcode/opt/bloom/falcon/phi/neox) HF layout has
    a slot for — a config carrying any of them must NOT match those
    branches, or the export silently drops the feature."""
    return (not cfg.num_experts and cfg.head_dim_override is None
            and not cfg.scale_embeddings and not cfg.logit_softcap
            and cfg.sliding_window is None and not cfg.is_glu
            and cfg.layer_window_pattern is None
            and cfg.attn_out_bias is None)


def _is_neox_layout(cfg: DecoderConfig) -> bool:
    """NeoX/Pythia family marker (covers use_parallel_residual False too:
    sequential NeoX still has the layernorm+bias+gelu+rope layout that the
    llama mapping can't express). GQA is excluded — NeoX has no kv-head
    grouping, so a biased GQA falcon must NOT route here (its kv rows
    cannot be re-interleaved into the [H, 3, dh] fused layout)."""
    return (cfg.norm == "layernorm" and cfg.pos_emb == "rope"
            and cfg.use_bias and cfg.activation in ("gelu", "gelu_exact")
            and cfg.has_ln2   # 1-norm parallel models (phi) are NOT neox
            and cfg.kv_heads == cfg.num_heads
            and _no_exotics(cfg) and not cfg.embed_norm
            and not cfg.lm_head_bias)


#: the residual path's keys of ``xing4_0``, with the rounds' clamp
_XING4_HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                  "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def _xing4_config(hf: Dict[str, Any]) -> DecoderConfig:
    """Xing4.0's block (XingChen-AGI Xing4.0-29B-A4B; ``model_type:
    xing4_0``): DeepSeek-V3's latent block (:func:`_deepseek_v3_config`
    reads every width, the YaRN keys — under ``rope_scaling.type`` —, the
    one-group sigmoid router and the shared expert) on a residual stream of
    ``hc_mult`` hidden states a token, mixed by manifold-constrained
    hyper-connections (models/typed_layers.py has the equations):
    ``hc_sinkhorn_iters`` rounds a map, each sum + ``hc_eps``, the logits
    clipped to ``mhc_h_res_clamp_min`` / ``_max``. ``hc_mult`` 1 is the
    parent family's stream and builds no maps; under 1, or over 1 with no
    ``hc_sinkhorn_iters``, is refused by name. Accepted and not built, as
    the parent family's: ``num_nextn_predict_layers``."""
    n = hf.get("hc_mult", 1)
    if not isinstance(n, int) or n < 1:
        raise ValueError(f"xing4_0: hc_mult={n!r} is not built (a whole "
                         f"number of hidden states a token, at least 1)")
    if n > 1 and not hf.get("hc_sinkhorn_iters"):
        raise ValueError(
            f"xing4_0: hc_sinkhorn_iters={hf.get('hc_sinkhorn_iters')!r} "
            f"is not built (hc_mult={n} needs the rounds that make H_res "
            f"doubly stochastic)")
    base = _deepseek_v3_config({k: v for k, v in hf.items()
                                if k not in _XING4_HC_KEYS})
    if n == 1:
        return base
    return dataclasses.replace(
        base, hc_mult=n, hc_sinkhorn_iters=int(hf["hc_sinkhorn_iters"]),
        hc_eps=float(hf.get("hc_eps", 1e-6)),
        hc_res_clamp=(float(hf.get("mhc_h_res_clamp_min", -30.0)),
                      float(hf.get("mhc_h_res_clamp_max", 30.0))))


def config_to_hf(cfg: DecoderConfig) -> Dict[str, Any]:
    def act_name(exact_name="gelu", tanh_name="gelu_new"):
        """HF 'gelu' is exact erf; tanh-approx models must export the
        tanh spelling or transformers reloads with the wrong act."""
        if cfg.activation == "relu":
            return "relu"
        return exact_name if cfg.activation == "gelu_exact" else tanh_name

    if cfg.typed:
        raise NotImplementedError(
            "config_to_hf: a typed layer stack (mimo_v2, deepseek_v3, "
            "cohere2_moe, nemotron_h, granitemoehybrid, jamba, lfm2_moe, "
            "xing4_0, qwen3_next) has no exporter")
    if not cfg.causal or not cfg.prenorm:
        # encoder layouts (BERT/DistilBERT): both flags flip together
        if cfg.causal or cfg.prenorm or cfg.pos_emb != "learned" \
                or cfg.norm != "layernorm" or not cfg.mlm_head \
                or not _no_exotics(cfg) or not cfg.embed_norm:
            raise ValueError(
                "config_to_hf: no HF layout for this encoder config "
                f"(causal={cfg.causal} prenorm={cfg.prenorm} "
                f"pos_emb={cfg.pos_emb}); supported encoder exports: "
                "bert (type_vocab_size>0), distilbert")
        if cfg.type_vocab_size:
            return {
                "model_type": "bert",
                "architectures": ["BertForMaskedLM"],
                "hidden_size": cfg.hidden_size,
                "num_hidden_layers": cfg.num_layers,
                "num_attention_heads": cfg.num_heads,
                "intermediate_size": cfg.ffn_size,
                "vocab_size": cfg.vocab_size,
                "max_position_embeddings": cfg.max_seq_len,
                "type_vocab_size": cfg.type_vocab_size,
                "layer_norm_eps": cfg.norm_eps,
                "hidden_act": act_name(),
                "tie_word_embeddings": True,
                "torch_dtype": "float32",
            }
        return {
            "model_type": "distilbert",
            "architectures": ["DistilBertForMaskedLM"],
            "dim": cfg.hidden_size,
            "n_layers": cfg.num_layers,
            "n_heads": cfg.num_heads,
            "hidden_dim": cfg.ffn_size,
            "vocab_size": cfg.vocab_size,
            "max_position_embeddings": cfg.max_seq_len,
            "activation": act_name(),
            "sinusoidal_pos_embds": False,
            "tie_weights_": True,
            "torch_dtype": "float32",
        }
    if _is_neox_layout(cfg):
        return {
            "model_type": "gpt_neox",
            "architectures": ["GPTNeoXForCausalLM"],
            "hidden_size": cfg.hidden_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "intermediate_size": cfg.ffn_size,
            "vocab_size": cfg.vocab_size,
            "max_position_embeddings": cfg.max_seq_len,
            "rotary_emb_base": cfg.rope_theta,
            "rotary_pct": cfg.rotary_pct,
            "layer_norm_eps": cfg.norm_eps,
            "use_parallel_residual": cfg.parallel_block,
            "tie_word_embeddings": cfg.tie_embeddings,
            "hidden_act": act_name(),
            "torch_dtype": "float32",
        }
    base = {
        "vocab_size": cfg.vocab_size,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": "float32",
    }
    if cfg.layer_window_pattern is not None:
        # GPT-Neo: the only layout with per-layer window alternation
        nz = {w for w in cfg.layer_window_pattern if w}
        if (len(nz) > 1 or cfg.norm != "layernorm"
                or cfg.pos_emb != "learned" or not cfg.use_bias
                or cfg.qkv_bias or not cfg.out_bias
                or cfg.parallel_block or cfg.num_experts):
            raise ValueError(
                "config_to_hf: layer_window_pattern only exports as "
                "gpt_neo (layernorm, learned pos, bias-less qkv + biased "
                "out, one distinct local window size); got "
                f"pattern={cfg.layer_window_pattern}")
        kinds = ["global" if w == 0 else "local"
                 for w in cfg.window_per_layer()]
        return {**base, "model_type": "gpt_neo",
                "architectures": ["GPTNeoForCausalLM"],
                "hidden_size": cfg.hidden_size,
                "num_layers": cfg.num_layers,
                "num_heads": cfg.num_heads,
                "intermediate_size": cfg.ffn_size,
                "max_position_embeddings": cfg.max_seq_len,
                "window_size": next(iter(nz), 256),
                "attention_types": [[[k], 1] for k in kinds],
                "layer_norm_epsilon": cfg.norm_eps,
                "activation_function": act_name()}
    untied_bias = cfg.lm_head_bias and not cfg.tie_embeddings
    if (cfg.norm == "layernorm" and cfg.pos_emb == "learned"
            and cfg.use_bias and not cfg.parallel_block
            and _no_exotics(cfg) and not cfg.embed_norm
            and not untied_bias   # no lm_head.bias slot in these layouts
            and cfg.kv_heads in (1, cfg.num_heads)):
        if cfg.kv_heads == 1 and cfg.num_heads > 1:   # MQA → bigcode
            return {**base, "model_type": "gpt_bigcode",
                    "architectures": ["GPTBigCodeForCausalLM"],
                    "n_embd": cfg.hidden_size, "n_layer": cfg.num_layers,
                    "n_head": cfg.num_heads,
                    "n_positions": cfg.max_seq_len,
                    "n_inner": cfg.ffn_size, "multi_query": True,
                    "layer_norm_epsilon": cfg.norm_eps,
                    "activation_function":
                        act_name("gelu", "gelu_pytorch_tanh")}
        if cfg.activation == "relu":   # OPT lineage
            return {**base, "model_type": "opt",
                    "architectures": ["OPTForCausalLM"],
                    "hidden_size": cfg.hidden_size,
                    "num_hidden_layers": cfg.num_layers,
                    "num_attention_heads": cfg.num_heads,
                    "ffn_dim": cfg.ffn_size,
                    "max_position_embeddings": cfg.max_seq_len,
                    "word_embed_proj_dim": cfg.hidden_size,
                    "do_layer_norm_before": True, "enable_bias": True,
                    "activation_function": "relu"}
        return {**base, "model_type": "gpt2",
                "architectures": ["GPT2LMHeadModel"],
                "n_embd": cfg.hidden_size, "n_layer": cfg.num_layers,
                "n_head": cfg.num_heads, "n_positions": cfg.max_seq_len,
                "n_ctx": cfg.max_seq_len, "n_inner": cfg.ffn_size,
                "layer_norm_epsilon": cfg.norm_eps,
                "activation_function": act_name()}
    if (cfg.pos_emb == "alibi" and cfg.embed_norm and cfg.use_bias
            and cfg.norm == "layernorm" and not cfg.parallel_block
            and _no_exotics(cfg) and not untied_bias):   # BLOOM
        return {**base, "model_type": "bloom",
                "architectures": ["BloomForCausalLM"],
                "hidden_size": cfg.hidden_size, "n_layer": cfg.num_layers,
                "n_head": cfg.num_heads,
                "layer_norm_epsilon": cfg.norm_eps, "seq_length":
                cfg.max_seq_len}
    if (cfg.parallel_block and cfg.norm == "layernorm"
            and not cfg.lm_head_bias and _no_exotics(cfg)
            and not cfg.embed_norm and cfg.rotary_pct == 1.0
            and (not cfg.use_bias or cfg.has_ln2)):
        # Falcon: pick the fused-qkv generation that can express the
        # head layout — old MQA only fits kv=1 + one shared norm. Biased
        # ONE-norm parallel models fall through to the phi branch below
        # (separate biased projections — the same math, an expressible
        # layout); biased 2-norm GQA exports as falcon "bias": true.
        new_arch = cfg.kv_heads > 1 or cfg.parallel_block_norms == 2
        hf = {**base, "model_type": "falcon",
              "architectures": ["FalconForCausalLM"],
              "hidden_size": cfg.hidden_size,
              "num_hidden_layers": cfg.num_layers,
              "num_attention_heads": cfg.num_heads,
              "ffn_hidden_size": cfg.ffn_size,
              "max_position_embeddings": cfg.max_seq_len,
              "layer_norm_epsilon": cfg.norm_eps,
              "rope_theta": cfg.rope_theta,
              "alibi": cfg.pos_emb == "alibi", "bias": cfg.use_bias,
              "activation": act_name("gelu", "gelu_pytorch_tanh"),
              "parallel_attn": True,
              "new_decoder_architecture": new_arch,
              "multi_query": cfg.kv_heads == 1}
        if new_arch:
            hf["num_kv_heads"] = cfg.kv_heads
            hf["num_ln_in_parallel_attn"] = cfg.parallel_block_norms
        return hf
    if (cfg.parallel_block and not cfg.has_ln2 and cfg.use_bias
            and not cfg.qkv_bias and cfg.pos_emb == "rope"
            and cfg.lm_head_bias and not cfg.tie_embeddings
            and cfg.kv_heads == cfg.num_heads
            # GPTJConfig has NO rope-base slot: a non-default theta must
            # fall through to the no-layout error, not silently reload
            # in transformers with the hardcoded 10000
            and cfg.rope_theta == 10000.0
            and _no_exotics(cfg) and not cfg.embed_norm):   # GPT-J
        return {**base, "model_type": "gptj",
                "architectures": ["GPTJForCausalLM"],
                "n_embd": cfg.hidden_size, "n_layer": cfg.num_layers,
                "n_head": cfg.num_heads, "n_positions": cfg.max_seq_len,
                "n_inner": cfg.ffn_size,
                "rotary_dim": cfg.rope_dim,
                "layer_norm_epsilon": cfg.norm_eps,
                "activation_function": act_name()}
    if (cfg.parallel_block and not cfg.has_ln2 and cfg.use_bias
            and cfg.qkv_bias
            and cfg.pos_emb == "rope" and _no_exotics(cfg)
            and not cfg.embed_norm):   # Phi
        return {**base, "model_type": "phi",
                "architectures": ["PhiForCausalLM"],
                "hidden_size": cfg.hidden_size,
                "num_hidden_layers": cfg.num_layers,
                "num_attention_heads": cfg.num_heads,
                "num_key_value_heads": cfg.kv_heads,
                "intermediate_size": cfg.ffn_size,
                "max_position_embeddings": cfg.max_seq_len,
                "partial_rotary_factor": cfg.rotary_pct,
                "layer_norm_eps": cfg.norm_eps,
                "rope_theta": cfg.rope_theta,
                "hidden_act": act_name(),
                "qk_layernorm": False}
    if not (cfg.norm == "rmsnorm" and cfg.pos_emb == "rope"
            and cfg.is_glu and not cfg.parallel_block
            and not cfg.embed_norm and not untied_bias
            and cfg.rotary_pct == 1.0):
        # the llama-family layouts are sequential-residual, full-rotary,
        # bias-less-head — a config outside every branch must RAISE, not
        # write a silently-wrong checkpoint
        raise ValueError(
            f"config_to_hf: no HF layout for norm={cfg.norm} "
            f"pos_emb={cfg.pos_emb} activation={cfg.activation} "
            f"parallel_block={cfg.parallel_block}; supported exports: "
            f"llama/mistral/mixtral/qwen2-like, gemma, gpt_neox, gpt2, "
            f"gpt_bigcode, opt, bloom, falcon, phi")
    if _is_gemma_layout(cfg):
        mt = "gemma"
        arch = ["GemmaForCausalLM"]
    elif cfg.num_experts and cfg.shared_expert_size:
        mt, arch = "qwen2_moe", ["Qwen2MoeForCausalLM"]
    elif cfg.num_experts:
        mt, arch = "mixtral", ["MixtralForCausalLM"]
    elif cfg.qkv_bias and cfg.out_bias and not cfg.use_bias \
            and cfg.sliding_window is None:
        # InternLM shape: biases on all four attention projections but
        # nowhere else — LlamaConfig expresses it exactly via
        # attention_bias=true (o_proj bias INCLUDED, unlike qwen2)
        mt, arch = "llama", ["LlamaForCausalLM"]
    elif cfg.use_bias:
        # qkv biases exist only in the qwen2 layout of this family;
        # exporting as llama/mistral would silently drop them
        mt, arch = "qwen2", ["Qwen2ForCausalLM"]
    elif cfg.sliding_window is not None:
        # LlamaConfig has no sliding-window support — exporting SWA as
        # 'llama' would silently reload full-causal in transformers
        mt, arch = "mistral", ["MistralForCausalLM"]
    else:
        mt, arch = "llama", ["LlamaForCausalLM"]
    hf = {
        "model_type": mt,
        "architectures": arch,
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads,
        "intermediate_size": cfg.ffn_size,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": "float32",
    }
    if mt == "llama" and cfg.qkv_bias:
        hf["attention_bias"] = True   # InternLM round-trip
    if cfg.sliding_window is not None:
        hf["sliding_window"] = cfg.sliding_window
        if mt == "qwen2":
            hf["use_sliding_window"] = True   # qwen2 defaults it OFF
    if _is_gemma_layout(cfg):
        # always explicit: GemmaConfig's DEFAULT head_dim is 256, not
        # hidden//heads — an omitted key reloads with the wrong shape
        hf["head_dim"] = cfg.head_dim
        hf["hidden_act"] = "gelu_pytorch_tanh"
        hf["hidden_activation"] = "gelu_pytorch_tanh"
        if cfg.logit_softcap:
            hf["final_logit_softcapping"] = cfg.logit_softcap
    elif cfg.head_dim_override is not None:
        hf["head_dim"] = cfg.head_dim_override
    if cfg.num_experts and cfg.shared_expert_size:   # qwen2_moe
        hf.update(num_experts=cfg.num_experts,
                  num_experts_per_tok=cfg.num_experts_per_tok,
                  moe_intermediate_size=cfg.ffn_size,
                  intermediate_size=cfg.ffn_size,
                  shared_expert_intermediate_size=cfg.shared_expert_size,
                  norm_topk_prob=cfg.norm_topk_prob,
                  decoder_sparse_step=1, mlp_only_layers=[])
    elif cfg.num_experts:
        hf["num_local_experts"] = cfg.num_experts
        hf["num_experts_per_tok"] = cfg.num_experts_per_tok
    return hf


# ---------------------------------------------------------------------------
# tensor-name mapping
# ---------------------------------------------------------------------------

def _reader(model_dir: str):
    """Yield a get(name)->np.ndarray over all safetensors shards (streamed:
    tensors load lazily, one at a time — the 70B-scale requirement of the
    reference's HuggingFaceCheckpointEngine)."""
    from safetensors import safe_open

    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    if os.path.exists(index_path):
        with open(index_path) as fh:
            weight_map = json.load(fh)["weight_map"]
        handles: Dict[str, Any] = {}

        def get(name: str) -> np.ndarray:
            shard = weight_map[name]
            if shard not in handles:
                handles[shard] = safe_open(
                    os.path.join(model_dir, shard), framework="np")
            return handles[shard].get_tensor(name)

        return get, set(weight_map)
    single = os.path.join(model_dir, "model.safetensors")
    handle = safe_open(single, framework="np")
    names = set(handle.keys())
    return handle.get_tensor, names


def load_hf_checkpoint(model_dir: str, dtype=np.float32
                       ) -> Tuple[DecoderConfig, Params]:
    """Load an HF Llama/Mistral/Mixtral/Qwen2 checkpoint directory into
    (DecoderConfig, params pytree)."""
    with open(os.path.join(model_dir, "config.json")) as fh:
        hf_cfg = json.load(fh)
    cfg = config_from_hf(hf_cfg)
    get, names = _reader(model_dir)
    params = params_from_state(cfg, hf_cfg, get, names, dtype)
    logger.info(f"loaded HF checkpoint from {model_dir}: "
                f"{cfg.num_params() / 1e6:.1f}M params, "
                f"{hf_cfg.get('model_type')}")
    return cfg, params


def params_from_state(cfg: DecoderConfig, hf_cfg: Dict[str, Any], get, names,
                      dtype=np.float32) -> Params:
    """Map HF-convention tensor names → params pytree, source-agnostic.

    ``get(name) -> np.ndarray`` and ``names`` may come from safetensors
    shards (`load_hf_checkpoint`) or from a torch state dict (the
    DeepSpeed-checkpoint importer, `checkpoint/ds_import.py`) — the name
    conventions are identical because the reference engine checkpoints the
    wrapped HF module's own state_dict (reference runtime/engine.py:3621).
    """
    L = cfg.num_layers
    mt = hf_cfg.get("model_type")
    if mt == "qwen3_next":
        return _load_qwen3_next(cfg, get, dtype)
    if cfg.typed:
        raise NotImplementedError(
            f"loading {mt!r} weights into a typed layer stack is not "
            f"built (the published tensor names were not at hand): the "
            f"configuration is read, the weights are random from a seed")
    if mt == "bert":
        return _load_bert(cfg, get, names, dtype)
    if mt == "distilbert":
        return _load_distilbert(cfg, get, names, dtype)
    if mt == "gpt_neox":
        return _load_neox(cfg, get, dtype)
    if mt == "gpt_neo":
        return _load_gptneo(cfg, get, names, dtype)
    if mt == "qwen":
        return _load_qwen(cfg, get, names, dtype)
    if mt == "gpt2":
        return _load_gpt2(cfg, get, names, dtype)
    if mt == "gpt_bigcode":
        return _load_bigcode(cfg, get, names, dtype)
    if mt == "opt":
        return _load_opt(cfg, get, names, dtype)
    if mt == "bloom":
        return _load_bloom(cfg, get, names, dtype)
    if mt == "falcon":
        return _load_falcon(cfg, hf_cfg, get, names, dtype)
    if mt == "phi":
        return _load_phi(cfg, get, dtype)
    if mt == "phi3":
        return _load_phi3(cfg, get, names, dtype)
    if mt == "gptj":
        return _load_gptj(cfg, get, dtype)

    def T(name):
        return np.ascontiguousarray(get(name).astype(dtype).T)

    def stackT(fmt):
        return np.stack([T(fmt.format(i)) for i in range(L)])

    def stack(fmt):
        return np.stack([get(fmt.format(i)).astype(dtype)
                         for i in range(L)])

    p = "model.layers.{}."
    attn = {
        "wq": stackT(p + "self_attn.q_proj.weight"),
        "wk": stackT(p + "self_attn.k_proj.weight"),
        "wv": stackT(p + "self_attn.v_proj.weight"),
        "wo": stackT(p + "self_attn.o_proj.weight"),
    }
    if p.format(0) + "self_attn.q_proj.bias" in names:   # qwen2/internlm
        attn["bq"] = stack(p + "self_attn.q_proj.bias")
        attn["bk"] = stack(p + "self_attn.k_proj.bias")
        attn["bv"] = stack(p + "self_attn.v_proj.bias")
        # internlm ("bias": true) also biases o_proj; qwen2 does not
        attn["bo"] = stack(p + "self_attn.o_proj.bias") \
            if p.format(0) + "self_attn.o_proj.bias" in names \
            else np.zeros((L, cfg.hidden_size), dtype)

    layers: Dict[str, Any] = {
        "attn": attn,
        "ln1": {"scale": stack(p + "input_layernorm.weight")},
        "ln2": {"scale": stack(p + "post_attention_layernorm.weight")},
    }
    if cfg.num_experts:
        E = cfg.num_experts
        is_qwen_moe = hf_cfg.get("model_type") == "qwen2_moe"
        ep = p + ("mlp.experts.{}." if is_qwen_moe
                  else "block_sparse_moe.experts.{}.")

        def estackT(suffix):
            return np.stack([
                np.stack([T(ep.format(i, e) + suffix) for e in range(E)])
                for i in range(L)])
        if is_qwen_moe:
            layers["moe"] = {
                "router": stackT(p + "mlp.gate.weight"),
                "wg": estackT("gate_proj.weight"),
                "wi": estackT("up_proj.weight"),
                "wo": estackT("down_proj.weight"),
                "shared": {
                    "wg": stackT(p + "mlp.shared_expert.gate_proj.weight"),
                    "wi": stackT(p + "mlp.shared_expert.up_proj.weight"),
                    "wo": stackT(p + "mlp.shared_expert.down_proj.weight"),
                    "gate": stackT(p + "mlp.shared_expert_gate.weight"),
                },
            }
        else:
            layers["moe"] = {
                "router": stackT(p + "block_sparse_moe.gate.weight"),
                "wg": estackT("w1.weight"),       # mixtral w1 = gate
                "wo": estackT("w2.weight"),       # w2 = down
                "wi": estackT("w3.weight"),       # w3 = up
            }
    else:
        layers["mlp"] = {
            "wg": stackT(p + "mlp.gate_proj.weight"),
            "wi": stackT(p + "mlp.up_proj.weight"),
            "wo": stackT(p + "mlp.down_proj.weight"),
        }

    params: Params = {
        "embed": {"tokens": get("model.embed_tokens.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {"scale": get("model.norm.weight").astype(dtype)},
    }
    if hf_cfg.get("model_type") == "gemma":
        # HF gemma RMSNorm computes x̂·(1+w); our _norm computes x̂·scale
        for ln in (layers["ln1"], layers["ln2"], params["final_norm"]):
            ln["scale"] = ln["scale"] + 1.0
    if not cfg.tie_embeddings:
        params["lm_head"] = T("lm_head.weight")
    return params


def _load_neox(cfg: DecoderConfig, get, dtype) -> Params:
    """GPT-NeoX/Pythia layout: fused query_key_value with PER-HEAD
    interleaving ([heads, 3, dh] on the out dim), separate input/
    post_attention norms, biases everywhere."""
    L, H, dh, D = (cfg.num_layers, cfg.num_heads, cfg.head_dim,
                   cfg.hidden_size)
    p = "gpt_neox.layers.{}."

    def split_qkv_w(i):
        w = get(p.format(i) + "attention.query_key_value.weight")
        w = w.astype(dtype).reshape(H, 3, dh, D)
        # → our [in, out] einsum layout, out = head-major × dh
        return tuple(np.ascontiguousarray(
            w[:, j].reshape(H * dh, D).T) for j in range(3))

    def split_qkv_b(i):
        b = get(p.format(i) + "attention.query_key_value.bias")
        b = b.astype(dtype).reshape(H, 3, dh)
        return tuple(b[:, j].reshape(-1) for j in range(3))

    qw, kw, vw = zip(*(split_qkv_w(i) for i in range(L)))
    qb, kb, vb = zip(*(split_qkv_b(i) for i in range(L)))

    def stack(fmt):
        return np.stack([get(fmt.format(i)).astype(dtype)
                         for i in range(L)])

    def stackT(fmt):
        return np.stack([np.ascontiguousarray(
            get(fmt.format(i)).astype(dtype).T) for i in range(L)])

    layers = {
        "attn": {
            "wq": np.stack(qw), "wk": np.stack(kw), "wv": np.stack(vw),
            "wo": stackT(p + "attention.dense.weight"),
            "bq": np.stack(qb), "bk": np.stack(kb), "bv": np.stack(vb),
            "bo": stack(p + "attention.dense.bias"),
        },
        "ln1": {"scale": stack(p + "input_layernorm.weight"),
                "bias": stack(p + "input_layernorm.bias")},
        "ln2": {"scale": stack(p + "post_attention_layernorm.weight"),
                "bias": stack(p + "post_attention_layernorm.bias")},
        "mlp": {
            "wi": stackT(p + "mlp.dense_h_to_4h.weight"),
            "bi": stack(p + "mlp.dense_h_to_4h.bias"),
            "wo": stackT(p + "mlp.dense_4h_to_h.weight"),
            "bo": stack(p + "mlp.dense_4h_to_h.bias"),
        },
    }
    params: Params = {
        "embed": {"tokens": get("gpt_neox.embed_in.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {
            "scale": get("gpt_neox.final_layer_norm.weight").astype(dtype),
            "bias": get("gpt_neox.final_layer_norm.bias").astype(dtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = np.ascontiguousarray(
            get("embed_out.weight").astype(dtype).T)
    return params


def _load_gptneo(cfg: DecoderConfig, get, names, dtype) -> Params:
    """GPT-Neo layout (reference: module_inject/containers/gptneo.py):
    separate bias-less q/k/v Linears + biased out_proj, GPT-2-style
    ln/mlp naming but nn.Linear ([out, in]) weights. GPT-Neo computes
    attention WITHOUT the 1/sqrt(dh) scale; we fold sqrt(dh) into wq at
    load so the in-repo scaled kernels match exactly (exported back out
    by _export_gptneo)."""
    import math as _math
    L = cfg.num_layers
    stack, stackT = _stack_helpers(get, L, dtype)
    p = "transformer.h.{}."
    scale = np.asarray(_math.sqrt(cfg.head_dim), dtype)
    layers = {
        "attn": {
            "wq": stackT(p + "attn.attention.q_proj.weight") * scale,
            "wk": stackT(p + "attn.attention.k_proj.weight"),
            "wv": stackT(p + "attn.attention.v_proj.weight"),
            "wo": stackT(p + "attn.attention.out_proj.weight"),
            "bo": stack(p + "attn.attention.out_proj.bias"),
        },
        "ln1": {"scale": stack(p + "ln_1.weight"),
                "bias": stack(p + "ln_1.bias")},
        "ln2": {"scale": stack(p + "ln_2.weight"),
                "bias": stack(p + "ln_2.bias")},
        "mlp": {
            "wi": stackT(p + "mlp.c_fc.weight"),
            "bi": stack(p + "mlp.c_fc.bias"),
            "wo": stackT(p + "mlp.c_proj.weight"),
            "bo": stack(p + "mlp.c_proj.bias"),
        },
    }
    params: Params = {
        "embed": {
            "tokens": get("transformer.wte.weight").astype(dtype),
            "pos": get("transformer.wpe.weight").astype(dtype),
        },
        "layers": layers,
        "final_norm": {
            "scale": get("transformer.ln_f.weight").astype(dtype),
            "bias": get("transformer.ln_f.bias").astype(dtype)},
    }
    return _attach_untied_head(params, cfg, get, names, dtype)


def _load_bert(cfg: DecoderConfig, get, names, dtype) -> Params:
    """BERT encoder layout (reference: module_inject/containers/bert.py).

    Post-LN mapping: HF ``attention.output.LayerNorm`` → our ``ln1``
    (applied after the attention residual), ``output.LayerNorm`` →
    ``ln2``. Works for both ``BertForMaskedLM`` (``bert.``-prefixed +
    ``cls.predictions`` head) and a bare ``BertModel`` checkpoint."""
    L = cfg.num_layers
    pre = "bert." if "bert.embeddings.word_embeddings.weight" in names \
        else ""
    stack, stackT = _stack_helpers(get, L, dtype)
    p = pre + "encoder.layer.{}."
    layers = {
        "attn": {
            "wq": stackT(p + "attention.self.query.weight"),
            "wk": stackT(p + "attention.self.key.weight"),
            "wv": stackT(p + "attention.self.value.weight"),
            "wo": stackT(p + "attention.output.dense.weight"),
            "bq": stack(p + "attention.self.query.bias"),
            "bk": stack(p + "attention.self.key.bias"),
            "bv": stack(p + "attention.self.value.bias"),
            "bo": stack(p + "attention.output.dense.bias"),
        },
        "ln1": {"scale": stack(p + "attention.output.LayerNorm.weight"),
                "bias": stack(p + "attention.output.LayerNorm.bias")},
        "ln2": {"scale": stack(p + "output.LayerNorm.weight"),
                "bias": stack(p + "output.LayerNorm.bias")},
        "mlp": {
            "wi": stackT(p + "intermediate.dense.weight"),
            "bi": stack(p + "intermediate.dense.bias"),
            "wo": stackT(p + "output.dense.weight"),
            "bo": stack(p + "output.dense.bias"),
        },
    }
    e = pre + "embeddings."
    params: Params = {
        "embed": {
            "tokens": get(e + "word_embeddings.weight").astype(dtype),
            "pos": get(e + "position_embeddings.weight").astype(dtype),
            "token_type":
                get(e + "token_type_embeddings.weight").astype(dtype),
        },
        "embed_norm": {"scale": get(e + "LayerNorm.weight").astype(dtype),
                       "bias": get(e + "LayerNorm.bias").astype(dtype)},
        "layers": layers,
    }
    if "cls.predictions.transform.dense.weight" in names:
        t = "cls.predictions.transform."
        params["mlm_head"] = {
            "dense": np.ascontiguousarray(
                get(t + "dense.weight").astype(dtype).T),
            "dense_bias": get(t + "dense.bias").astype(dtype),
            "ln": {"scale": get(t + "LayerNorm.weight").astype(dtype),
                   "bias": get(t + "LayerNorm.bias").astype(dtype)},
            "vocab_bias": get("cls.predictions.bias").astype(dtype),
        }
    return params


def _load_distilbert(cfg: DecoderConfig, get, names, dtype) -> Params:
    """DistilBERT layout (reference: module_inject/containers/
    distil_bert.py): BERT math without token types; the MLM head tensors
    are top-level ``vocab_transform``/``vocab_layer_norm``/
    ``vocab_projector`` (projector weight tied to the embeddings)."""
    L = cfg.num_layers
    pre = "distilbert." \
        if "distilbert.embeddings.word_embeddings.weight" in names else ""
    stack, stackT = _stack_helpers(get, L, dtype)
    p = pre + "transformer.layer.{}."
    layers = {
        "attn": {
            "wq": stackT(p + "attention.q_lin.weight"),
            "wk": stackT(p + "attention.k_lin.weight"),
            "wv": stackT(p + "attention.v_lin.weight"),
            "wo": stackT(p + "attention.out_lin.weight"),
            "bq": stack(p + "attention.q_lin.bias"),
            "bk": stack(p + "attention.k_lin.bias"),
            "bv": stack(p + "attention.v_lin.bias"),
            "bo": stack(p + "attention.out_lin.bias"),
        },
        "ln1": {"scale": stack(p + "sa_layer_norm.weight"),
                "bias": stack(p + "sa_layer_norm.bias")},
        "ln2": {"scale": stack(p + "output_layer_norm.weight"),
                "bias": stack(p + "output_layer_norm.bias")},
        "mlp": {
            "wi": stackT(p + "ffn.lin1.weight"),
            "bi": stack(p + "ffn.lin1.bias"),
            "wo": stackT(p + "ffn.lin2.weight"),
            "bo": stack(p + "ffn.lin2.bias"),
        },
    }
    e = pre + "embeddings."
    params: Params = {
        "embed": {
            "tokens": get(e + "word_embeddings.weight").astype(dtype),
            "pos": get(e + "position_embeddings.weight").astype(dtype),
        },
        "embed_norm": {"scale": get(e + "LayerNorm.weight").astype(dtype),
                       "bias": get(e + "LayerNorm.bias").astype(dtype)},
        "layers": layers,
    }
    if "vocab_transform.weight" in names:
        params["mlm_head"] = {
            "dense": np.ascontiguousarray(
                get("vocab_transform.weight").astype(dtype).T),
            "dense_bias": get("vocab_transform.bias").astype(dtype),
            "ln": {"scale": get("vocab_layer_norm.weight").astype(dtype),
                   "bias": get("vocab_layer_norm.bias").astype(dtype)},
            "vocab_bias": get("vocab_projector.bias").astype(dtype),
        }
    return params


def _attach_untied_head(params: Params, cfg: DecoderConfig, get, names,
                        dtype) -> Params:
    """Untied fine-tunes of normally-tied families (GPT-2/BLOOM/Falcon)
    carry an explicit lm_head.weight; a config/params mismatch here would
    crash later in lm_logits with a bare KeyError."""
    if cfg.tie_embeddings:
        return params
    if "lm_head.weight" not in names:
        raise ValueError("checkpoint says tie_word_embeddings=False but "
                         "has no lm_head.weight tensor")
    params["lm_head"] = np.ascontiguousarray(
        get("lm_head.weight").astype(dtype).T)
    return params


def _stack_helpers(get, L, dtype):
    """(stack, stackT) over per-layer tensor names."""
    def stack(fmt):
        return np.stack([get(fmt.format(i)).astype(dtype)
                         for i in range(L)])

    def stackT(fmt):
        return np.stack([np.ascontiguousarray(
            get(fmt.format(i)).astype(dtype).T) for i in range(L)])
    return stack, stackT


def _load_qwen(cfg: DecoderConfig, get, names, dtype) -> Params:
    """Qwen v1 layout (reference: inference/v2/model_implementations/
    qwen/container.py:54–61): nn.Linear fused ``attn.c_attn`` — contiguous
    q|k|v thirds on the out dim, WITH bias — over RMSNorm ``ln_1``/``ln_2``
    (weight only); ``mlp.w1`` is the UP projection and ``mlp.w2`` the GATE
    (the reference maps w1→up_params, w2→gate_params); ``c_proj`` tensors
    are bias-less; untied ``lm_head``."""
    L = cfg.num_layers
    p = "transformer.h.{}."
    stack, stackT = _stack_helpers(get, L, dtype)

    qw, kw_, vw = (np.ascontiguousarray(a) for a in np.split(
        stackT(p + "attn.c_attn.weight"), 3, axis=2))
    qb, kb, vb = (np.ascontiguousarray(a) for a in np.split(
        stack(p + "attn.c_attn.bias"), 3, axis=1))
    layers = {
        "attn": {"wq": qw, "wk": kw_, "wv": vw,
                 "wo": stackT(p + "attn.c_proj.weight"),
                 "bq": qb, "bk": kb, "bv": vb},
        "ln1": {"scale": stack(p + "ln_1.weight")},
        "ln2": {"scale": stack(p + "ln_2.weight")},
        "mlp": {"wi": stackT(p + "mlp.w1.weight"),    # w1 = up
                "wg": stackT(p + "mlp.w2.weight"),    # w2 = gate
                "wo": stackT(p + "mlp.c_proj.weight")},
    }
    return _attach_untied_head({
        "embed": {"tokens": get("transformer.wte.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {
            "scale": get("transformer.ln_f.weight").astype(dtype)},
    }, cfg, get, names, dtype)


def _load_gpt2(cfg: DecoderConfig, get, names, dtype) -> Params:
    """GPT-2 layout: Conv1D weights already [in, out]; fused c_attn with
    COLUMN-CONCATENATED q|k|v (not head-interleaved), learned positions."""
    L, D = cfg.num_layers, cfg.hidden_size
    p = "transformer.h.{}."
    stack, _ = _stack_helpers(get, L, dtype)

    def split_cols(fmt, axis):
        full = np.stack([get(fmt.format(i)).astype(dtype)
                         for i in range(L)])
        return np.split(full, 3, axis=axis)

    qw, kw_, vw = split_cols(p + "attn.c_attn.weight", axis=2)
    qb, kb, vb = split_cols(p + "attn.c_attn.bias", axis=1)
    layers = {
        "attn": {
            "wq": np.ascontiguousarray(qw), "wk": np.ascontiguousarray(kw_),
            "wv": np.ascontiguousarray(vw),
            "wo": stack(p + "attn.c_proj.weight"),
            "bq": np.ascontiguousarray(qb), "bk": np.ascontiguousarray(kb),
            "bv": np.ascontiguousarray(vb),
            "bo": stack(p + "attn.c_proj.bias"),
        },
        "ln1": {"scale": stack(p + "ln_1.weight"),
                "bias": stack(p + "ln_1.bias")},
        "ln2": {"scale": stack(p + "ln_2.weight"),
                "bias": stack(p + "ln_2.bias")},
        "mlp": {
            "wi": stack(p + "mlp.c_fc.weight"),
            "bi": stack(p + "mlp.c_fc.bias"),
            "wo": stack(p + "mlp.c_proj.weight"),
            "bo": stack(p + "mlp.c_proj.bias"),
        },
    }
    return _attach_untied_head({
        "embed": {"tokens": get("transformer.wte.weight").astype(dtype),
                  "pos": get("transformer.wpe.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {
            "scale": get("transformer.ln_f.weight").astype(dtype),
            "bias": get("transformer.ln_f.bias").astype(dtype)},
    }, cfg, get, names, dtype)


def _load_bigcode(cfg: DecoderConfig, get, names, dtype) -> Params:
    """GPT-BigCode (SantaCoder/StarCoder) layout: GPT-2 names but
    nn.Linear ([out, in]) weights. Fused c_attn packing differs by
    variant: MQA = q | 1-head k | 1-head v concatenated on the out dim;
    MHA (multi_query=False) = NeoX-style HEAD-INTERLEAVED [H, 3, dh]."""
    L, D, H, dh = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                   cfg.head_dim)
    kvd = cfg.kv_heads * dh
    p = "transformer.h.{}."
    stack, stackT = _stack_helpers(get, L, dtype)

    def split_attn(i):
        w = np.ascontiguousarray(
            get(p.format(i) + "attn.c_attn.weight").astype(dtype).T)
        b = get(p.format(i) + "attn.c_attn.bias").astype(dtype)
        if cfg.kv_heads == 1:   # MQA concat
            return ((w[:, :D], w[:, D:D + kvd], w[:, D + kvd:]),
                    (b[:D], b[D:D + kvd], b[D + kvd:]))
        wi = w.reshape(D, H, 3, dh)
        bi = b.reshape(H, 3, dh)
        return (tuple(np.ascontiguousarray(wi[:, :, j].reshape(D, H * dh))
                      for j in range(3)),
                tuple(bi[:, j].reshape(-1) for j in range(3)))

    ws, bs = zip(*(split_attn(i) for i in range(L)))
    layers = {
        "attn": {
            "wq": np.stack([w[0] for w in ws]),
            "wk": np.stack([w[1] for w in ws]),
            "wv": np.stack([w[2] for w in ws]),
            "wo": stackT(p + "attn.c_proj.weight"),
            "bq": np.stack([b[0] for b in bs]),
            "bk": np.stack([b[1] for b in bs]),
            "bv": np.stack([b[2] for b in bs]),
            "bo": stack(p + "attn.c_proj.bias"),
        },
        "ln1": {"scale": stack(p + "ln_1.weight"),
                "bias": stack(p + "ln_1.bias")},
        "ln2": {"scale": stack(p + "ln_2.weight"),
                "bias": stack(p + "ln_2.bias")},
        "mlp": {
            "wi": stackT(p + "mlp.c_fc.weight"),
            "bi": stack(p + "mlp.c_fc.bias"),
            "wo": stackT(p + "mlp.c_proj.weight"),
            "bo": stack(p + "mlp.c_proj.bias"),
        },
    }
    return _attach_untied_head({
        "embed": {"tokens": get("transformer.wte.weight").astype(dtype),
                  "pos": get("transformer.wpe.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {
            "scale": get("transformer.ln_f.weight").astype(dtype),
            "bias": get("transformer.ln_f.bias").astype(dtype)},
    }, cfg, get, names, dtype)


def _load_opt(cfg: DecoderConfig, get, names, dtype) -> Params:
    """OPT layout: separate q/k/v/out projections with biases, ReLU MLP,
    learned positions with the +2 row offset (embed_positions stores
    max_position_embeddings + 2 rows; dense sequences index position+2,
    so the table is loaded with the first two rows dropped)."""
    L = cfg.num_layers
    p = "model.decoder.layers.{}."
    stack, stackT = _stack_helpers(get, L, dtype)
    layers = {
        "attn": {
            "wq": stackT(p + "self_attn.q_proj.weight"),
            "wk": stackT(p + "self_attn.k_proj.weight"),
            "wv": stackT(p + "self_attn.v_proj.weight"),
            "wo": stackT(p + "self_attn.out_proj.weight"),
            "bq": stack(p + "self_attn.q_proj.bias"),
            "bk": stack(p + "self_attn.k_proj.bias"),
            "bv": stack(p + "self_attn.v_proj.bias"),
            "bo": stack(p + "self_attn.out_proj.bias"),
        },
        "ln1": {"scale": stack(p + "self_attn_layer_norm.weight"),
                "bias": stack(p + "self_attn_layer_norm.bias")},
        "ln2": {"scale": stack(p + "final_layer_norm.weight"),
                "bias": stack(p + "final_layer_norm.bias")},
        "mlp": {
            "wi": stackT(p + "fc1.weight"), "bi": stack(p + "fc1.bias"),
            "wo": stackT(p + "fc2.weight"), "bo": stack(p + "fc2.bias"),
        },
    }
    params: Params = {
        "embed": {
            "tokens": get("model.decoder.embed_tokens.weight").astype(dtype),
            "pos": get("model.decoder.embed_positions.weight"
                       ).astype(dtype)[2:],
        },
        "layers": layers,
        "final_norm": {
            "scale": get("model.decoder.final_layer_norm.weight").astype(dtype),
            "bias": get("model.decoder.final_layer_norm.bias").astype(dtype)},
    }
    return _attach_untied_head(params, cfg, get, names, dtype)


def _load_bloom(cfg: DecoderConfig, get, names, dtype) -> Params:
    """BLOOM layout: NeoX-style HEAD-INTERLEAVED fused query_key_value
    ([H, 3, dh] on the out dim), word-embeddings LayerNorm, ALiBi (no
    positional parameters)."""
    L, H, dh = cfg.num_layers, cfg.num_heads, cfg.head_dim
    p = "transformer.h.{}."
    stack, stackT = _stack_helpers(get, L, dtype)

    def split_qkv(i):
        w = get(p.format(i) + "self_attention.query_key_value.weight")
        w = w.astype(dtype).reshape(H, 3, dh, cfg.hidden_size)
        b = get(p.format(i) + "self_attention.query_key_value.bias")
        b = b.astype(dtype).reshape(H, 3, dh)
        return ([np.ascontiguousarray(w[:, j].reshape(H * dh, -1).T)
                 for j in range(3)],
                [b[:, j].reshape(-1) for j in range(3)])

    ws, bs = zip(*(split_qkv(i) for i in range(L)))
    layers = {
        "attn": {
            "wq": np.stack([w[0] for w in ws]),
            "wk": np.stack([w[1] for w in ws]),
            "wv": np.stack([w[2] for w in ws]),
            "wo": stackT(p + "self_attention.dense.weight"),
            "bq": np.stack([b[0] for b in bs]),
            "bk": np.stack([b[1] for b in bs]),
            "bv": np.stack([b[2] for b in bs]),
            "bo": stack(p + "self_attention.dense.bias"),
        },
        "ln1": {"scale": stack(p + "input_layernorm.weight"),
                "bias": stack(p + "input_layernorm.bias")},
        "ln2": {"scale": stack(p + "post_attention_layernorm.weight"),
                "bias": stack(p + "post_attention_layernorm.bias")},
        "mlp": {
            "wi": stackT(p + "mlp.dense_h_to_4h.weight"),
            "bi": stack(p + "mlp.dense_h_to_4h.bias"),
            "wo": stackT(p + "mlp.dense_4h_to_h.weight"),
            "bo": stack(p + "mlp.dense_4h_to_h.bias"),
        },
    }
    return _attach_untied_head({
        "embed": {"tokens":
                  get("transformer.word_embeddings.weight").astype(dtype)},
        "embed_norm": {
            "scale": get("transformer.word_embeddings_layernorm.weight"
                         ).astype(dtype),
            "bias": get("transformer.word_embeddings_layernorm.bias"
                        ).astype(dtype)},
        "layers": layers,
        "final_norm": {"scale": get("transformer.ln_f.weight").astype(dtype),
                       "bias": get("transformer.ln_f.bias").astype(dtype)},
    }, cfg, get, names, dtype)


def _load_falcon(cfg: DecoderConfig, hf_cfg, get, names, dtype) -> Params:
    """Falcon layout: bias-less linears (unless config "bias": true) with
    biased LayerNorms, fused query_key_value whose packing differs by
    generation — MQA (7B: H query heads then one k then one v),
    new_decoder_architecture (40B: per-kv-group [g queries, k, v]
    interleave), or NeoX-style [H, 3, dh] when multi_query=False."""
    L, H, KV, dh, D = (cfg.num_layers, cfg.num_heads, cfg.kv_heads,
                       cfg.head_dim, cfg.hidden_size)
    new_arch = bool(hf_cfg.get("new_decoder_architecture", False))
    p = "transformer.h.{}."
    stack, stackT = _stack_helpers(get, L, dtype)

    def split_fused(m, trailing):
        """Un-pack one fused qkv tensor of shape [fused_out, *trailing]
        into (q, k, v) rows following the generation's packing."""
        if new_arch:
            g = H // KV
            m = m.reshape(KV, g + 2, dh, *trailing)
            return (m[:, :g].reshape(H * dh, *trailing),
                    m[:, g].reshape(KV * dh, *trailing),
                    m[:, g + 1].reshape(KV * dh, *trailing))
        if KV == 1:
            m = m.reshape(H + 2, dh, *trailing)
            return (m[:H].reshape(H * dh, *trailing),
                    m[H].reshape(dh, *trailing),
                    m[H + 1].reshape(dh, *trailing))
        m = m.reshape(H, 3, dh, *trailing)
        return tuple(m[:, j].reshape(H * dh, *trailing) for j in range(3))

    def split_qkv(i):
        w = get(p.format(i) + "self_attention.query_key_value.weight"
                ).astype(dtype)
        return tuple(np.ascontiguousarray(m.T)
                     for m in split_fused(w, (D,)))

    qw, kw_, vw = zip(*(split_qkv(i) for i in range(L)))
    layers = {
        "attn": {
            "wq": np.stack(qw), "wk": np.stack(kw_), "wv": np.stack(vw),
            "wo": stackT(p + "self_attention.dense.weight"),
        },
        "mlp": {
            "wi": stackT(p + "mlp.dense_h_to_4h.weight"),
            "wo": stackT(p + "mlp.dense_4h_to_h.weight"),
        },
    }
    if cfg.use_bias:   # falcon-rw-style "bias": true checkpoints
        def split_qkv_b(i):
            b = get(p.format(i) + "self_attention.query_key_value.bias"
                    ).astype(dtype)
            return split_fused(b, ())

        qb, kb, vb = zip(*(split_qkv_b(i) for i in range(L)))
        layers["attn"].update(
            bq=np.stack(qb), bk=np.stack(kb), bv=np.stack(vb),
            bo=stack(p + "self_attention.dense.bias"))
        layers["mlp"].update(
            bi=stack(p + "mlp.dense_h_to_4h.bias"),
            bo=stack(p + "mlp.dense_4h_to_h.bias"))
    if cfg.parallel_block_norms == 2:
        layers["ln1"] = {"scale": stack(p + "ln_attn.weight"),
                         "bias": stack(p + "ln_attn.bias")}
        layers["ln2"] = {"scale": stack(p + "ln_mlp.weight"),
                         "bias": stack(p + "ln_mlp.bias")}
    else:
        layers["ln1"] = {"scale": stack(p + "input_layernorm.weight"),
                         "bias": stack(p + "input_layernorm.bias")}
    return _attach_untied_head({
        "embed": {"tokens":
                  get("transformer.word_embeddings.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {"scale": get("transformer.ln_f.weight").astype(dtype),
                       "bias": get("transformer.ln_f.bias").astype(dtype)},
    }, cfg, get, names, dtype)


def _load_phi3(cfg: DecoderConfig, get, names, dtype) -> Params:
    """Phi-3 layout: llama-family math with FUSED qkv_proj ([q|k|v] on
    the out dim) and FUSED gate_up_proj ([gate|up]); no biases."""
    L, D = cfg.num_layers, cfg.hidden_size
    qd = cfg.q_dim
    kvd = cfg.kv_heads * cfg.head_dim
    h = cfg.ffn_size
    p = "model.layers.{}."
    stack, stackT = _stack_helpers(get, L, dtype)

    def split_qkv(i):
        # transposed VIEW; np.stack below makes the one contiguous copy
        w = get(p.format(i) + "self_attn.qkv_proj.weight").astype(dtype).T
        return w[:, :qd], w[:, qd:qd + kvd], w[:, qd + kvd:]

    def split_gate_up(i):
        w = get(p.format(i) + "mlp.gate_up_proj.weight").astype(dtype).T
        return w[:, :h], w[:, h:]

    qw, kw_, vw = zip(*(split_qkv(i) for i in range(L)))
    gw, uw = zip(*(split_gate_up(i) for i in range(L)))
    layers = {
        "attn": {
            "wq": np.stack(qw), "wk": np.stack(kw_), "wv": np.stack(vw),
            "wo": stackT(p + "self_attn.o_proj.weight"),
        },
        "ln1": {"scale": stack(p + "input_layernorm.weight")},
        "ln2": {"scale": stack(p + "post_attention_layernorm.weight")},
        "mlp": {
            "wg": np.stack(gw), "wi": np.stack(uw),
            "wo": stackT(p + "mlp.down_proj.weight"),
        },
    }
    params: Params = {
        "embed": {"tokens": get("model.embed_tokens.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {"scale": get("model.norm.weight").astype(dtype)},
    }
    return _attach_untied_head(params, cfg, get, names, dtype)


def _gptj_rope_perm(cfg: DecoderConfig, inverse: bool = False) -> np.ndarray:
    """Per-head column permutation folding GPT-J's INTERLEAVED rotary
    pairing (HF rotate_every_two: pair (2j, 2j+1) gets frequency j) into
    this repo's rotate-half convention (pair (j, j+rot/2) gets frequency
    j): new position j takes original 2j, new j+rot/2 takes 2j+1, tail
    dims pass through. Both conventions then compute identical attention
    scores because q and k share the permutation. Same trick as the
    Meta→HF llama weight conversion, in the other direction."""
    dh, rot = cfg.head_dim, cfg.rope_dim
    perm = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2),
                           np.arange(rot, dh)])
    if inverse:
        perm = np.argsort(perm)
    full = np.concatenate([perm + h * dh for h in range(cfg.num_heads)])
    return full


def _load_gptj(cfg: DecoderConfig, get, dtype) -> Params:
    """GPT-J layout: parallel residual with ONE shared ln_1, bias-less
    q/k/v/out_proj, biased fc_in/fc_out, interleaved partial rotary
    (folded into the q/k permutation above), untied lm_head WITH bias."""
    L = cfg.num_layers
    p = "transformer.h.{}."
    stack, stackT = _stack_helpers(get, L, dtype)
    perm = _gptj_rope_perm(cfg)
    layers = {
        "attn": {
            "wq": stackT(p + "attn.q_proj.weight")[:, :, perm],
            "wk": stackT(p + "attn.k_proj.weight")[:, :, perm],
            "wv": stackT(p + "attn.v_proj.weight"),
            "wo": stackT(p + "attn.out_proj.weight"),
        },
        "ln1": {"scale": stack(p + "ln_1.weight"),
                "bias": stack(p + "ln_1.bias")},
        "mlp": {
            "wi": stackT(p + "mlp.fc_in.weight"),
            "bi": stack(p + "mlp.fc_in.bias"),
            "wo": stackT(p + "mlp.fc_out.weight"),
            "bo": stack(p + "mlp.fc_out.bias"),
        },
    }
    return {
        "embed": {"tokens": get("transformer.wte.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {
            "scale": get("transformer.ln_f.weight").astype(dtype),
            "bias": get("transformer.ln_f.bias").astype(dtype)},
        "lm_head": np.ascontiguousarray(get("lm_head.weight").astype(dtype).T),
        "lm_head_bias": get("lm_head.bias").astype(dtype),
    }


def _load_phi(cfg: DecoderConfig, get, dtype) -> Params:
    """Phi layout: parallel residual with ONE shared input layernorm,
    separate biased q/k/v/dense projections, partial rotary, untied
    lm_head WITH bias."""
    L = cfg.num_layers
    p = "model.layers.{}."
    stack, stackT = _stack_helpers(get, L, dtype)
    layers = {
        "attn": {
            "wq": stackT(p + "self_attn.q_proj.weight"),
            "wk": stackT(p + "self_attn.k_proj.weight"),
            "wv": stackT(p + "self_attn.v_proj.weight"),
            "wo": stackT(p + "self_attn.dense.weight"),
            "bq": stack(p + "self_attn.q_proj.bias"),
            "bk": stack(p + "self_attn.k_proj.bias"),
            "bv": stack(p + "self_attn.v_proj.bias"),
            "bo": stack(p + "self_attn.dense.bias"),
        },
        "ln1": {"scale": stack(p + "input_layernorm.weight"),
                "bias": stack(p + "input_layernorm.bias")},
        "mlp": {
            "wi": stackT(p + "mlp.fc1.weight"), "bi": stack(p + "mlp.fc1.bias"),
            "wo": stackT(p + "mlp.fc2.weight"), "bo": stack(p + "mlp.fc2.bias"),
        },
    }
    return {
        "embed": {"tokens": get("model.embed_tokens.weight").astype(dtype)},
        "layers": layers,
        "final_norm": {
            "scale": get("model.final_layernorm.weight").astype(dtype),
            "bias": get("model.final_layernorm.bias").astype(dtype)},
        "lm_head": np.ascontiguousarray(get("lm_head.weight").astype(dtype).T),
        "lm_head_bias": get("lm_head.bias").astype(dtype),
    }


def export_hf_checkpoint(cfg: DecoderConfig, params: Params,
                         out_dir: str) -> None:
    """Write the pytree back as an HF-layout safetensors checkpoint
    (single shard) + config.json — the reverse mapping, so models trained
    here load in transformers."""
    import jax
    # also key on the params tree: the moe.use_residual config knob folds
    # moe_residual into an internal copy of the model config, so the
    # caller's cfg may still say False while the tree carries the branch
    if cfg.moe_residual or (isinstance(params.get("layers"), dict)
                            and "residual" in params["layers"].get(
                                "moe", {})):
        raise ValueError(
            "export_hf_checkpoint: Residual-MoE (moe_residual) is a "
            "DeepSpeed training feature with no HF layout slot for the "
            "dense branch / coefficient — no transformers architecture "
            "can load it")
    if not cfg.causal or not cfg.prenorm:
        return _export_encoder(cfg, config_to_hf(cfg), params, out_dir)
    if _is_neox_layout(cfg):
        return _export_neox(cfg, params, out_dir)
    cfg_hf = config_to_hf(cfg)   # raises on unsupported layouts
    if cfg_hf["model_type"] in ("gpt2", "opt", "bloom", "falcon", "phi",
                                "gpt_bigcode", "gptj", "gpt_neo"):
        return _export_classic(cfg, cfg_hf, params, out_dir)

    os.makedirs(out_dir, exist_ok=True)
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), np.float32), params)
    if cfg_hf["model_type"] == "gemma":   # reverse the (1+w) fold
        host["final_norm"]["scale"] = host["final_norm"]["scale"] - 1.0
        for ln in ("ln1", "ln2"):
            host["layers"][ln]["scale"] = host["layers"][ln]["scale"] - 1.0
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": host["embed"]["tokens"],
        "model.norm.weight": host["final_norm"]["scale"],
    }
    if not cfg.tie_embeddings:
        out["lm_head.weight"] = np.ascontiguousarray(host["lm_head"].T)
    lyr = host["layers"]
    p = "model.layers.{}."
    for i in range(cfg.num_layers):
        a = lyr["attn"]
        out[p.format(i) + "self_attn.q_proj.weight"] = \
            np.ascontiguousarray(a["wq"][i].T)
        out[p.format(i) + "self_attn.k_proj.weight"] = \
            np.ascontiguousarray(a["wk"][i].T)
        out[p.format(i) + "self_attn.v_proj.weight"] = \
            np.ascontiguousarray(a["wv"][i].T)
        out[p.format(i) + "self_attn.o_proj.weight"] = \
            np.ascontiguousarray(a["wo"][i].T)
        if "bq" in a:
            out[p.format(i) + "self_attn.q_proj.bias"] = a["bq"][i]
            out[p.format(i) + "self_attn.k_proj.bias"] = a["bk"][i]
            out[p.format(i) + "self_attn.v_proj.bias"] = a["bv"][i]
            if cfg_hf.get("attention_bias"):
                # llama attention_bias layout (InternLM): o_proj bias
                # has a real slot
                out[p.format(i) + "self_attn.o_proj.bias"] = a["bo"][i]
            elif "bo" in a and np.abs(a["bo"][i]).max() > 1e-6:
                logger.warning(
                    "export_hf_checkpoint: layer %d o_proj bias is "
                    "nonzero but the qwen2 HF layout has no slot for it "
                    "— dropped (logits will differ)", i)
        out[p.format(i) + "input_layernorm.weight"] = lyr["ln1"]["scale"][i]
        out[p.format(i) + "post_attention_layernorm.weight"] = \
            lyr["ln2"]["scale"][i]
        if cfg.num_experts and cfg.shared_expert_size:   # qwen2_moe
            moe = lyr["moe"]
            out[p.format(i) + "mlp.gate.weight"] = \
                np.ascontiguousarray(moe["router"][i].T)
            for e in range(cfg.num_experts):
                ep = p.format(i) + f"mlp.experts.{e}."
                out[ep + "gate_proj.weight"] = \
                    np.ascontiguousarray(moe["wg"][i, e].T)
                out[ep + "up_proj.weight"] = \
                    np.ascontiguousarray(moe["wi"][i, e].T)
                out[ep + "down_proj.weight"] = \
                    np.ascontiguousarray(moe["wo"][i, e].T)
            sh = moe["shared"]
            sp = p.format(i) + "mlp.shared_expert."
            out[sp + "gate_proj.weight"] = np.ascontiguousarray(sh["wg"][i].T)
            out[sp + "up_proj.weight"] = np.ascontiguousarray(sh["wi"][i].T)
            out[sp + "down_proj.weight"] = np.ascontiguousarray(sh["wo"][i].T)
            out[p.format(i) + "mlp.shared_expert_gate.weight"] = \
                np.ascontiguousarray(sh["gate"][i].T)
        elif cfg.num_experts:
            moe = lyr["moe"]
            out[p.format(i) + "block_sparse_moe.gate.weight"] = \
                np.ascontiguousarray(moe["router"][i].T)
            for e in range(cfg.num_experts):
                ep = p.format(i) + f"block_sparse_moe.experts.{e}."
                out[ep + "w1.weight"] = np.ascontiguousarray(moe["wg"][i, e].T)
                out[ep + "w2.weight"] = np.ascontiguousarray(moe["wo"][i, e].T)
                out[ep + "w3.weight"] = np.ascontiguousarray(moe["wi"][i, e].T)
        else:
            m = lyr["mlp"]
            out[p.format(i) + "mlp.gate_proj.weight"] = \
                np.ascontiguousarray(m["wg"][i].T)
            out[p.format(i) + "mlp.up_proj.weight"] = \
                np.ascontiguousarray(m["wi"][i].T)
            out[p.format(i) + "mlp.down_proj.weight"] = \
                np.ascontiguousarray(m["wo"][i].T)
    _save_hf(out, cfg_hf, out_dir)


def _export_encoder(cfg: DecoderConfig, cfg_hf: Dict[str, Any],
                    params: Params, out_dir: str) -> None:
    """Inverse of ``_load_bert`` / ``_load_distilbert``: write a
    ``BertForMaskedLM`` / ``DistilBertForMaskedLM`` checkpoint
    transformers can reload."""
    import jax
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), np.float32), params)
    C = np.ascontiguousarray
    lyr = host["layers"]
    a, m = lyr["attn"], lyr["mlp"]
    out: Dict[str, np.ndarray] = {}
    bert = cfg_hf["model_type"] == "bert"
    pre = "bert." if bert else "distilbert."
    e = pre + "embeddings."
    out[e + "word_embeddings.weight"] = host["embed"]["tokens"]
    out[e + "position_embeddings.weight"] = host["embed"]["pos"]
    if bert:
        out[e + "token_type_embeddings.weight"] = \
            host["embed"]["token_type"]
    out[e + "LayerNorm.weight"] = host["embed_norm"]["scale"]
    out[e + "LayerNorm.bias"] = host["embed_norm"]["bias"]
    if bert:
        name = {
            "wq": "attention.self.query.weight",
            "bq": "attention.self.query.bias",
            "wk": "attention.self.key.weight",
            "bk": "attention.self.key.bias",
            "wv": "attention.self.value.weight",
            "bv": "attention.self.value.bias",
            "wo": "attention.output.dense.weight",
            "bo": "attention.output.dense.bias",
            "ln1": "attention.output.LayerNorm",
            "ln2": "output.LayerNorm",
            "wi": "intermediate.dense.weight",
            "bi": "intermediate.dense.bias",
            "wmo": "output.dense.weight",
            "bmo": "output.dense.bias",
        }
        p = pre + "encoder.layer.{}."
    else:
        name = {
            "wq": "attention.q_lin.weight", "bq": "attention.q_lin.bias",
            "wk": "attention.k_lin.weight", "bk": "attention.k_lin.bias",
            "wv": "attention.v_lin.weight", "bv": "attention.v_lin.bias",
            "wo": "attention.out_lin.weight",
            "bo": "attention.out_lin.bias",
            "ln1": "sa_layer_norm", "ln2": "output_layer_norm",
            "wi": "ffn.lin1.weight", "bi": "ffn.lin1.bias",
            "wmo": "ffn.lin2.weight", "bmo": "ffn.lin2.bias",
        }
        p = pre + "transformer.layer.{}."
    for i in range(cfg.num_layers):
        q = p.format(i)
        out[q + name["wq"]] = C(a["wq"][i].T)
        out[q + name["bq"]] = a["bq"][i]
        out[q + name["wk"]] = C(a["wk"][i].T)
        out[q + name["bk"]] = a["bk"][i]
        out[q + name["wv"]] = C(a["wv"][i].T)
        out[q + name["bv"]] = a["bv"][i]
        out[q + name["wo"]] = C(a["wo"][i].T)
        out[q + name["bo"]] = a["bo"][i]
        out[q + name["ln1"] + ".weight"] = lyr["ln1"]["scale"][i]
        out[q + name["ln1"] + ".bias"] = lyr["ln1"]["bias"][i]
        out[q + name["ln2"] + ".weight"] = lyr["ln2"]["scale"][i]
        out[q + name["ln2"] + ".bias"] = lyr["ln2"]["bias"][i]
        out[q + name["wi"]] = C(m["wi"][i].T)
        out[q + name["bi"]] = m["bi"][i]
        out[q + name["wmo"]] = C(m["wo"][i].T)
        out[q + name["bmo"]] = m["bo"][i]
    if "mlm_head" in host:
        mh = host["mlm_head"]
        if bert:
            t = "cls.predictions.transform."
            out[t + "dense.weight"] = C(mh["dense"].T)
            out[t + "dense.bias"] = mh["dense_bias"]
            out[t + "LayerNorm.weight"] = mh["ln"]["scale"]
            out[t + "LayerNorm.bias"] = mh["ln"]["bias"]
            out["cls.predictions.bias"] = mh["vocab_bias"]
            out["cls.predictions.decoder.weight"] = host["embed"]["tokens"]
            out["cls.predictions.decoder.bias"] = mh["vocab_bias"]
        else:
            out["vocab_transform.weight"] = C(mh["dense"].T)
            out["vocab_transform.bias"] = mh["dense_bias"]
            out["vocab_layer_norm.weight"] = mh["ln"]["scale"]
            out["vocab_layer_norm.bias"] = mh["ln"]["bias"]
            out["vocab_projector.weight"] = host["embed"]["tokens"]
            out["vocab_projector.bias"] = mh["vocab_bias"]
    _save_hf(out, cfg_hf, out_dir)


def _save_hf(out: Dict[str, np.ndarray], cfg_hf: Dict[str, Any],
             out_dir: str) -> None:
    """Shared export epilogue: safetensors + config.json."""
    from safetensors.numpy import save_file
    os.makedirs(out_dir, exist_ok=True)
    save_file(out, os.path.join(out_dir, "model.safetensors"),
              metadata={"format": "pt"})
    with open(os.path.join(out_dir, "config.json"), "w") as fh:
        json.dump(cfg_hf, fh, indent=2)


def _fuse_interleaved(a: Params, i: int, H: int, dh: int, D: int):
    """Re-pack separate q/k/v (+biases) into the NeoX/BLOOM head-
    interleaved fused layout: [H, 3, dh] on the out dim."""
    fused_w = np.stack(
        [a[k][i].T.reshape(H, dh, D) for k in ("wq", "wk", "wv")],
        axis=1).reshape(3 * H * dh, D)
    fused_b = np.stack(
        [a[k][i].reshape(H, dh) for k in ("bq", "bk", "bv")],
        axis=1).reshape(-1)
    return np.ascontiguousarray(fused_w), fused_b


def _export_classic(cfg: DecoderConfig, cfg_hf: Dict[str, Any],
                    params: Params, out_dir: str) -> None:
    """Reverse mappings for the classic families (GPT-2/GPT-BigCode/OPT/
    BLOOM/Falcon/Phi) — each the inverse of its ``_load_*`` including the fused-qkv
    re-pack and OPT's +2 position rows."""
    import jax
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), np.float32), params)
    mt = cfg_hf["model_type"]
    L, H, KV, dh, D = (cfg.num_layers, cfg.num_heads, cfg.kv_heads,
                       cfg.head_dim, cfg.hidden_size)
    lyr = host["layers"]
    a, m = lyr["attn"], lyr["mlp"]
    out: Dict[str, np.ndarray] = {}
    C = np.ascontiguousarray

    def put_ln(dst, src, i):
        out[dst + ".weight"] = src["scale"][i]
        out[dst + ".bias"] = src["bias"][i]

    if mt == "gpt2":
        out["transformer.wte.weight"] = host["embed"]["tokens"]
        out["transformer.wpe.weight"] = host["embed"]["pos"]
        out["transformer.ln_f.weight"] = host["final_norm"]["scale"]
        out["transformer.ln_f.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"transformer.h.{i}."
            out[p + "attn.c_attn.weight"] = C(np.concatenate(
                [a["wq"][i], a["wk"][i], a["wv"][i]], axis=1))
            out[p + "attn.c_attn.bias"] = np.concatenate(
                [a["bq"][i], a["bk"][i], a["bv"][i]])
            out[p + "attn.c_proj.weight"] = a["wo"][i]
            out[p + "attn.c_proj.bias"] = a["bo"][i]
            out[p + "mlp.c_fc.weight"] = m["wi"][i]
            out[p + "mlp.c_fc.bias"] = m["bi"][i]
            out[p + "mlp.c_proj.weight"] = m["wo"][i]
            out[p + "mlp.c_proj.bias"] = m["bo"][i]
            put_ln(p + "ln_1", lyr["ln1"], i)
            put_ln(p + "ln_2", lyr["ln2"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
    elif mt == "gpt_bigcode":
        out["transformer.wte.weight"] = host["embed"]["tokens"]
        out["transformer.wpe.weight"] = host["embed"]["pos"]
        out["transformer.ln_f.weight"] = host["final_norm"]["scale"]
        out["transformer.ln_f.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"transformer.h.{i}."
            # nn.Linear [out, in]: concat q|k|v on OUT then transpose back
            out[p + "attn.c_attn.weight"] = C(np.concatenate(
                [a["wq"][i], a["wk"][i], a["wv"][i]], axis=1).T)
            out[p + "attn.c_attn.bias"] = np.concatenate(
                [a["bq"][i], a["bk"][i], a["bv"][i]])
            out[p + "attn.c_proj.weight"] = C(a["wo"][i].T)
            out[p + "attn.c_proj.bias"] = a["bo"][i]
            out[p + "mlp.c_fc.weight"] = C(m["wi"][i].T)
            out[p + "mlp.c_fc.bias"] = m["bi"][i]
            out[p + "mlp.c_proj.weight"] = C(m["wo"][i].T)
            out[p + "mlp.c_proj.bias"] = m["bo"][i]
            put_ln(p + "ln_1", lyr["ln1"], i)
            put_ln(p + "ln_2", lyr["ln2"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
    elif mt == "gpt_neo":
        import math as _math
        inv = np.float32(1.0 / _math.sqrt(cfg.head_dim))
        out["transformer.wte.weight"] = host["embed"]["tokens"]
        out["transformer.wpe.weight"] = host["embed"]["pos"]
        out["transformer.ln_f.weight"] = host["final_norm"]["scale"]
        out["transformer.ln_f.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"transformer.h.{i}."
            # un-fold the sqrt(dh) loaded into wq (see _load_gptneo)
            out[p + "attn.attention.q_proj.weight"] = C((a["wq"][i] * inv).T)
            out[p + "attn.attention.k_proj.weight"] = C(a["wk"][i].T)
            out[p + "attn.attention.v_proj.weight"] = C(a["wv"][i].T)
            out[p + "attn.attention.out_proj.weight"] = C(a["wo"][i].T)
            out[p + "attn.attention.out_proj.bias"] = a["bo"][i]
            out[p + "mlp.c_fc.weight"] = C(m["wi"][i].T)
            out[p + "mlp.c_fc.bias"] = m["bi"][i]
            out[p + "mlp.c_proj.weight"] = C(m["wo"][i].T)
            out[p + "mlp.c_proj.bias"] = m["bo"][i]
            put_ln(p + "ln_1", lyr["ln1"], i)
            put_ln(p + "ln_2", lyr["ln2"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
    elif mt == "opt":
        out["model.decoder.embed_tokens.weight"] = host["embed"]["tokens"]
        # rows 0/1 are the padding-position slots HF indexes below the
        # +2 offset; they are never read for dense (full-mask) sequences
        out["model.decoder.embed_positions.weight"] = np.concatenate(
            [np.zeros((2, D), np.float32), host["embed"]["pos"]])
        out["model.decoder.final_layer_norm.weight"] = \
            host["final_norm"]["scale"]
        out["model.decoder.final_layer_norm.bias"] = \
            host["final_norm"]["bias"]
        for i in range(L):
            p = f"model.decoder.layers.{i}."
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"),
                                 ("v", "v_proj"), ("o", "out_proj")):
                key = "wo" if ours == "o" else "w" + ours
                bkey = "bo" if ours == "o" else "b" + ours
                out[p + f"self_attn.{theirs}.weight"] = C(a[key][i].T)
                out[p + f"self_attn.{theirs}.bias"] = a[bkey][i]
            out[p + "fc1.weight"] = C(m["wi"][i].T)
            out[p + "fc1.bias"] = m["bi"][i]
            out[p + "fc2.weight"] = C(m["wo"][i].T)
            out[p + "fc2.bias"] = m["bo"][i]
            put_ln(p + "self_attn_layer_norm", lyr["ln1"], i)
            put_ln(p + "final_layer_norm", lyr["ln2"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
    elif mt == "bloom":
        out["transformer.word_embeddings.weight"] = host["embed"]["tokens"]
        out["transformer.word_embeddings_layernorm.weight"] = \
            host["embed_norm"]["scale"]
        out["transformer.word_embeddings_layernorm.bias"] = \
            host["embed_norm"]["bias"]
        out["transformer.ln_f.weight"] = host["final_norm"]["scale"]
        out["transformer.ln_f.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"transformer.h.{i}."
            fused_w, fused_b = _fuse_interleaved(a, i, H, dh, D)
            out[p + "self_attention.query_key_value.weight"] = fused_w
            out[p + "self_attention.query_key_value.bias"] = fused_b
            out[p + "self_attention.dense.weight"] = C(a["wo"][i].T)
            out[p + "self_attention.dense.bias"] = a["bo"][i]
            out[p + "mlp.dense_h_to_4h.weight"] = C(m["wi"][i].T)
            out[p + "mlp.dense_h_to_4h.bias"] = m["bi"][i]
            out[p + "mlp.dense_4h_to_h.weight"] = C(m["wo"][i].T)
            out[p + "mlp.dense_4h_to_h.bias"] = m["bo"][i]
            put_ln(p + "input_layernorm", lyr["ln1"], i)
            put_ln(p + "post_attention_layernorm", lyr["ln2"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
    elif mt == "falcon":
        new_arch = cfg_hf["new_decoder_architecture"]
        out["transformer.word_embeddings.weight"] = host["embed"]["tokens"]
        out["transformer.ln_f.weight"] = host["final_norm"]["scale"]
        out["transformer.ln_f.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"transformer.h.{i}."
            q = a["wq"][i].T.reshape(H, dh, D)
            k = a["wk"][i].T.reshape(KV, dh, D)
            v = a["wv"][i].T.reshape(KV, dh, D)
            if new_arch:
                g = H // KV
                fused = np.concatenate(
                    [q.reshape(KV, g, dh, D), k[:, None], v[:, None]],
                    axis=1).reshape(KV * (g + 2) * dh, D)
            else:   # old MQA: H query heads then k then v
                fused = np.concatenate([q, k, v]).reshape((H + 2) * dh, D)
            out[p + "self_attention.query_key_value.weight"] = C(fused)
            out[p + "self_attention.dense.weight"] = C(a["wo"][i].T)
            out[p + "mlp.dense_h_to_4h.weight"] = C(m["wi"][i].T)
            out[p + "mlp.dense_4h_to_h.weight"] = C(m["wo"][i].T)
            if cfg.use_bias:   # "bias": true — inverse of split_fused
                qb = a["bq"][i].reshape(H, dh)
                kb = a["bk"][i].reshape(KV, dh)
                vb = a["bv"][i].reshape(KV, dh)
                if new_arch:
                    fb = np.concatenate(
                        [qb.reshape(KV, H // KV, dh), kb[:, None],
                         vb[:, None]], axis=1).reshape(-1)
                else:
                    fb = np.concatenate([qb, kb, vb]).reshape(-1)
                out[p + "self_attention.query_key_value.bias"] = fb
                out[p + "self_attention.dense.bias"] = a["bo"][i]
                out[p + "mlp.dense_h_to_4h.bias"] = m["bi"][i]
                out[p + "mlp.dense_4h_to_h.bias"] = m["bo"][i]
            if cfg.parallel_block_norms == 2:
                put_ln(p + "ln_attn", lyr["ln1"], i)
                put_ln(p + "ln_mlp", lyr["ln2"], i)
            else:
                put_ln(p + "input_layernorm", lyr["ln1"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
    elif mt == "gptj":
        inv = _gptj_rope_perm(cfg, inverse=True)
        out["transformer.wte.weight"] = host["embed"]["tokens"]
        out["transformer.ln_f.weight"] = host["final_norm"]["scale"]
        out["transformer.ln_f.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"transformer.h.{i}."
            out[p + "attn.q_proj.weight"] = C(a["wq"][i][:, inv].T)
            out[p + "attn.k_proj.weight"] = C(a["wk"][i][:, inv].T)
            out[p + "attn.v_proj.weight"] = C(a["wv"][i].T)
            out[p + "attn.out_proj.weight"] = C(a["wo"][i].T)
            out[p + "mlp.fc_in.weight"] = C(m["wi"][i].T)
            out[p + "mlp.fc_in.bias"] = m["bi"][i]
            out[p + "mlp.fc_out.weight"] = C(m["wo"][i].T)
            out[p + "mlp.fc_out.bias"] = m["bo"][i]
            put_ln(p + "ln_1", lyr["ln1"], i)
        out["lm_head.weight"] = C(host["lm_head"].T)
        out["lm_head.bias"] = host.get(
            "lm_head_bias", np.zeros(cfg.vocab_size, np.float32))
    else:   # phi
        out["model.embed_tokens.weight"] = host["embed"]["tokens"]
        out["model.final_layernorm.weight"] = host["final_norm"]["scale"]
        out["model.final_layernorm.bias"] = host["final_norm"]["bias"]
        for i in range(L):
            p = f"model.layers.{i}."
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"),
                                 ("v", "v_proj"), ("o", "dense")):
                key = "wo" if ours == "o" else "w" + ours
                bkey = "bo" if ours == "o" else "b" + ours
                out[p + f"self_attn.{theirs}.weight"] = C(a[key][i].T)
                out[p + f"self_attn.{theirs}.bias"] = a[bkey][i]
            out[p + "mlp.fc1.weight"] = C(m["wi"][i].T)
            out[p + "mlp.fc1.bias"] = m["bi"][i]
            out[p + "mlp.fc2.weight"] = C(m["wo"][i].T)
            out[p + "mlp.fc2.bias"] = m["bo"][i]
            put_ln(p + "input_layernorm", lyr["ln1"], i)
        if not cfg.tie_embeddings:
            out["lm_head.weight"] = C(host["lm_head"].T)
            out["lm_head.bias"] = host.get(
                "lm_head_bias", np.zeros(cfg.vocab_size, np.float32))
    _save_hf(out, cfg_hf, out_dir)


def _export_neox(cfg: DecoderConfig, params: Params, out_dir: str) -> None:
    """Reverse of _load_neox (re-interleaves the fused qkv)."""
    import jax
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), np.float32), params)
    H, dh, D = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "gpt_neox.embed_in.weight": host["embed"]["tokens"],
        "gpt_neox.final_layer_norm.weight": host["final_norm"]["scale"],
        "gpt_neox.final_layer_norm.bias": host["final_norm"]["bias"],
    }
    if not cfg.tie_embeddings:
        out["embed_out.weight"] = np.ascontiguousarray(host["lm_head"].T)
    lyr = host["layers"]
    p = "gpt_neox.layers.{}."
    for i in range(cfg.num_layers):
        a = lyr["attn"]
        fused_w, fused_b = _fuse_interleaved(a, i, H, dh, D)
        pi = p.format(i)
        out[pi + "attention.query_key_value.weight"] = fused_w
        out[pi + "attention.query_key_value.bias"] = fused_b
        out[pi + "attention.dense.weight"] = \
            np.ascontiguousarray(a["wo"][i].T)
        out[pi + "attention.dense.bias"] = a["bo"][i]
        out[pi + "input_layernorm.weight"] = lyr["ln1"]["scale"][i]
        out[pi + "input_layernorm.bias"] = lyr["ln1"]["bias"][i]
        out[pi + "post_attention_layernorm.weight"] = lyr["ln2"]["scale"][i]
        out[pi + "post_attention_layernorm.bias"] = lyr["ln2"]["bias"][i]
        m = lyr["mlp"]
        out[pi + "mlp.dense_h_to_4h.weight"] = \
            np.ascontiguousarray(m["wi"][i].T)
        out[pi + "mlp.dense_h_to_4h.bias"] = m["bi"][i]
        out[pi + "mlp.dense_4h_to_h.weight"] = \
            np.ascontiguousarray(m["wo"][i].T)
        out[pi + "mlp.dense_4h_to_h.bias"] = m["bo"][i]
    _save_hf(out, config_to_hf(cfg), out_dir)
