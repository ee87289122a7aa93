"""Qwen3-Next's stack (Qwen3-Next-80B-A3B; ``hf_loader``: ``qwen3_next``) on
the typed stack: EVERY layer a mixer — a GATED DELTA RULE (kind 6: a matrix a
value head that a step decays and corrects, a 4-tap convolution with no bias
and a SiLU, a gated norm that norms FIRST) or gated full attention (256-wide
heads at the published size, q / k head norms, rotary on a quarter of the
head, an output gate) — AND softmax-routed experts beside a gated shared
expert under two ZERO-CENTRED norms, an untied head: the program against the
benchmark's plain float32 reference (``benchmark/reference/
qwen3_next_decoder.py``: the recurrence token by token) on seeded random
weights at a small size, with controls that are wrong in one way each and
must not pass.

Tolerances (largest |logit difference|; the logits spread by 0.23 at this
size). ``F32_TOL`` 5e-6 — both sides float32 at ``highest`` precision; the
two differ in the ORDER of float32 sums (the program's delta rule is the
chunk form — a triangular solve inside the chunk — where the reference steps
the recurrence; its attention is blocked another way, its experts are
dispatched), readings 3e-7 to 1.4e-6. Every control reads above 50x that (a
state rounded to bf16 between launches the least, 6e-4):
bf16 weights, a state rounded to bf16, a dropped ``e^g``, the gate on the
wrong side of the mixer's norm, rotary over the whole head, a norm without
its ``1 +``, a stale slot. ``BF16_TOL`` 0.06 — bf16 weights, stream inputs,
cache and convolution tails against the float32 reference (a sanity bound:
what tells the precisions apart is ``F32_TOL``'s bf16 controls)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.models import hf_loader
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 5e-6
BF16_TOL = 0.06
CPU = jax.devices("cpu")[0]
VOCAB = 96


def published() -> dict:
    """The source's ``config.json`` (the catalog row, letter for letter)."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "qwen3-next-80b-a3b-instruct.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: hidden 128, ONE period ``delta
    delta delta full`` — four query heads of 32 over two KV heads (rotary on
    the first 8 dims), two key heads of 16 serving four value heads of 8 —,
    8 experts of 48, 2 a token, a shared expert of 40."""
    hf = published()
    hf.update(hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
              num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
              linear_num_value_heads=4, linear_key_head_dim=16,
              linear_value_head_dim=8, moe_intermediate_size=48,
              shared_expert_intermediate_size=40, num_experts=8,
              num_experts_per_tok=2, vocab_size=VOCAB)
    hf.update(over)
    return hf


def randomised(params, seed: int = 5):
    """What the init makes vacuous, made to count: every norm's scale (ones
    at init: a zero-centred ``w`` of 0, and RMSNorm then commutes with the
    rotation) drawn; the queries, both gates and ``[b | a]`` x 20 (at hidden
    128 the init's 0.02 leaves the softmax flat, the sigmoids at 1/2 and β
    and g the same for every token); ``A`` small enough that the state
    REMEMBERS (``U(0, 16)`` forgets within a token)."""
    rng = np.random.default_rng(seed)
    draw = lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
    scale = lambda n: {"scale": draw(n["scale"])}
    grown = {"attn": {"wq": lambda a: a * 20, "wq_gate": lambda a: a * 20,
                      "q_norm": scale, "k_norm": scale},
             "ssm": {"w_ba": lambda a: a * 20, "norm": scale,
                     "A_log": lambda a: jnp.log(jnp.asarray(
                         rng.uniform(0.01, 1.0, a.shape), jnp.float32))},
             "shared": {"gate": lambda a: a * 20}}
    layers = []
    for lp in params["layers"]:
        lp = dict(lp, ln1=scale(lp["ln1"]), ln2=scale(lp["ln2"]))
        for part, leaves in grown.items():
            if part in lp:
                lp[part] = dict(lp[part], **{k: f(lp[part][k])
                                             for k, f in leaves.items()})
        layers.append(lp)
    return dict(params, layers=layers,
                final_norm=scale(params["final_norm"]))


@pytest.fixture(scope="module")
def tiny():
    hf = small()
    cfg = config_from_hf(hf)
    params = randomised(tf.init_params(cfg, jax.random.PRNGKey(7),
                                       jnp.float32))
    tokens = np.random.default_rng(3).integers(0, VOCAB, 420)
    want = ref.logits_of(ref.Widths.from_hf(hf), params, tokens, CPU)
    return hf, cfg, params, tokens, want


def uncached(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(tf.forward(cfg, params, jnp.asarray(tokens)[None])
                          [0], np.float32)


ENGINE = dict(dtype="float32", max_sequences=8, num_blocks=64, block_size=16,
              max_seq_len=512, max_batch_tokens=256, prefill_chunk=128)


def engine(cfg, params, **over):
    return RaggedInferenceEngineTPU(cfg, dict(ENGINE, **over), params=params)


# -- the reader -----------------------------------------------------------------

def test_reader_builds_the_published_config():
    cfg = config_from_hf(published())
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, cfg.v_dim, cfg.vocab_size, cfg.intermediate_size) \
        == (2048, 48, 16, 2, 256, 256, 151936, 512)
    kinds = cfg.layer_kinds
    assert (kinds.count(6), kinds.count(0)) == (36, 12) and \
        [l for l, k in enumerate(kinds) if k == 0] == list(range(3, 48, 4))
    assert cfg.layer_sparse == (1,) * 48
    assert cfg.recurrent and cfg.delta_rule and not cfg.selective and \
        not cfg.short_conv and cfg.qk_head_norm and cfg.attn_output_gate \
        and cfg.shared_expert_gate
    assert cfg.kind_rope_theta(0) == 1e7 and cfg.kind_rope_theta(6) is None \
        and cfg.rope_dim == 64
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_inner,
            cfg.ssm_conv_dim) == (32, 128, 16, 128, 4, 4096, 8192) and \
        ssm.state_shape(cfg) == (32, 128, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.router_scoring,
            cfg.norm_topk_prob, cfg.router_select_bias,
            cfg.shared_expert_size, cfg.experts_held) == \
        (512, 10, "softmax", True, False, 512, None)
    assert cfg.norm_eps == 1e-6 and not cfg.tie_embeddings and \
        cfg.max_seq_len == 262144
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    d, a = shapes["layers"][0], shapes["layers"][3]
    assert set(d) == {"ln1", "ssm", "ln2", "moe", "shared"} and \
        set(a) == {"ln1", "attn", "ln2", "moe", "shared"}
    assert {k: v.shape for k, v in d["ssm"].items() if k != "norm"} == {
        "w_in": (2048, 12288), "w_ba": (2048, 64), "conv_w": (8192, 4),
        "dt_bias": (32,), "A_log": (32,), "w_out": (4096, 2048)} and \
        d["ssm"]["norm"]["scale"].shape == (128,)
    assert {k: v.shape for k, v in a["attn"].items()
            if not k.endswith("norm")} == {
        "wq": (2048, 4096), "wq_gate": (2048, 4096), "wk": (2048, 512),
        "wv": (2048, 512), "wo": (4096, 2048)} and \
        a["attn"]["q_norm"]["scale"].shape == (256,)
    assert a["moe"]["wg"].shape == (512, 2048, 512) and \
        a["moe"]["router"].shape == (2048, 512) and \
        a["shared"]["gate"].shape == (2048, 1) and \
        shapes["lm_head"].shape == (2048, 151936)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count - 79_674e6) < 2e6 and \
        abs(cfg.num_params() - count) < 1e6        # the name's 80B
    # one chip's share of the EP-8 deployment at three periods: ISSUE 62's
    # 3,474M
    held = config_from_hf(dict(published(), num_hidden_layers=12,
                               expert_share={"router_experts": 512,
                                             "first_expert": 0,
                                             "held_experts": 64}))
    assert held.num_experts == 512 and held.experts_held == (0, 64)
    share = jax.eval_shape(lambda r: tf.init_params(held, r),
                           jax.random.PRNGKey(0))
    assert abs(sum(int(np.prod(a.shape)) for a in jax.tree.leaves(share))
               - 3_474e6) < 1e6
    # a token multiplies 33.7M a delta-rule mixer, 27.3M a full one (q AND
    # its gate), the router, the shared expert with its gate and 10 x 64 /
    # 512 of its ten experts of 3.1M, the head
    w = ref.Widths.from_hf(dict(published(), num_hidden_layers=12,
                                expert_share={"router_experts": 512,
                                              "first_expert": 0,
                                              "held_experts": 64}))
    assert ref.matmul_params_per_token(w) == \
        9 * (2048 * (12288 + 64) + 4096 * 2048) + \
        3 * (3 * 2048 * 4096 + 2 * 2048 * 512) + \
        12 * (2048 * 512 + 3 * 2048 * 512 + 2048 +
              round(10 * 64 / 512 * 3 * 2048 * 512)) + 2048 * 151936


def test_reader_holds_every_key_the_harness_checks():
    from benchmark.lib import model as model_lib
    hf = published()
    cfg = config_from_hf(hf)
    held = [key for key in model_lib.BUILT_AS if key in hf]
    assert set(held) >= {"hidden_size", "num_attention_heads",
                         "num_key_value_heads", "num_hidden_layers",
                         "vocab_size", "num_experts", "num_experts_per_tok",
                         "moe_intermediate_size", "rope_theta",
                         "rms_norm_eps", "shared_expert_intermediate_size"}
    for key in set(held) - {"intermediate_size"}:    # of mlp-only layers
        assert getattr(cfg, model_lib.BUILT_AS[key]) == hf[key], key


def test_reader_builds_the_file_whole():
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("qwen3-next-80b-a3b-l12-e64-serve")
    assert conf["reduced"] == ["num_hidden_layers", "expert_share"]
    model = model_lib.build_model(conf)
    assert model == dataclasses.replace(
        config_from_hf(published()), num_layers=12,
        layer_kinds=(6, 6, 6, 0) * 3, layer_sparse=(1,) * 12,
        experts_held=(0, 64))
    assert model.vocab_size == 151936 and model.num_held_experts == 64
    tiny_model = model_lib.build_model(conf, rehearse=True)
    assert tiny_model.layer_kinds == (6, 6, 6, 0) and \
        tiny_model.head_dim == 128 and tiny_model.experts_held == (0, 8)


def test_layer_types_are_read_where_the_file_has_them():
    """``layer_types`` (HF's derived list) stands in for the interval."""
    hf = small(layer_types=["linear_attention", "full_attention",
                            "linear_attention", "linear_attention"])
    assert config_from_hf(hf).layer_kinds == (6, 0, 6, 6)


@pytest.mark.parametrize("over,named", [
    (dict(attention_bias=True), "attention_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
     "rope_scaling"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(shared_expert_intermediate_size=0),
     "shared_expert_intermediate_size"),
    (dict(layer_types=["linear_attention", "sliding_attention",
                       "linear_attention", "full_attention"]),
     "sliding_attention"),
    (dict(linear_num_key_heads=3), "linear_num_key_heads"),
    (dict(expert_share={"router_experts": 16, "first_expert": 0,
                        "held_experts": 2}), "router_experts"),
])
def test_reader_refuses_by_name_what_is_not_built(over, named):
    with pytest.raises(ValueError, match=named):
        config_from_hf(small(**over))


def test_one_stack_holds_one_kind_of_recurrent_mixer():
    cfg = config_from_hf(small())
    for other in (3, 4, 5):
        with pytest.raises(ValueError, match="kind 6"):
            dataclasses.replace(cfg, layer_kinds=(6, other, 6, 0),
                                ssm_inner_size=64, ssm_dt_rank=4)
    with pytest.raises(ValueError, match="kind 6"):
        dataclasses.replace(cfg, ssm_groups=3)


# -- the loader: the published tensors' layout ----------------------------------

def _published_state(cfg, params, w_of):
    """The tree as ``Qwen3NextForCausalLM``'s state dict: ``[out, in]``
    matrices, ``q_proj`` as ``[q | gate]`` a head, ``in_proj_qkvz`` /
    ``in_proj_ba`` interleaved a KEY head, zero-centred norms as ``w =
    scale − 1`` (``w_of``)."""
    d, H, dk = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    hv, hk, n, p = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state_size, \
        cfg.ssm_head_dim
    r, kd = hv // hk, hk * n
    T = lambda a: np.asarray(a).T
    state = {"model.embed_tokens.weight": np.asarray(
        params["embed"]["tokens"]),
        "model.norm.weight": w_of(params["final_norm"]["scale"]),
        "lm_head.weight": T(params["lm_head"])}
    for l, lp in enumerate(params["layers"]):
        pre = f"model.layers.{l}."
        state[pre + "input_layernorm.weight"] = w_of(lp["ln1"]["scale"])
        state[pre + "post_attention_layernorm.weight"] = \
            w_of(lp["ln2"]["scale"])
        if "ssm" in lp:
            s, a = lp["ssm"], pre + "linear_attn."
            w_in = np.asarray(s["w_in"])
            parts = [w_in[:, :kd].reshape(d, hk, n),
                     w_in[:, kd:2 * kd].reshape(d, hk, n),
                     w_in[:, 2 * kd:2 * kd + hv * p].reshape(d, hk, r * p),
                     w_in[:, 2 * kd + hv * p:].reshape(d, hk, r * p)]
            state[a + "in_proj_qkvz.weight"] = np.concatenate(
                parts, axis=-1).reshape(d, -1).T
            ba = np.asarray(s["w_ba"])
            state[a + "in_proj_ba.weight"] = np.concatenate(
                [ba[:, :hv].reshape(d, hk, r), ba[:, hv:].reshape(d, hk, r)],
                axis=-1).reshape(d, -1).T
            state[a + "conv1d.weight"] = np.asarray(s["conv_w"])[:, None]
            state[a + "dt_bias"] = np.asarray(s["dt_bias"])
            state[a + "A_log"] = np.asarray(s["A_log"])
            state[a + "norm.weight"] = np.asarray(s["norm"]["scale"])
            state[a + "out_proj.weight"] = T(s["w_out"])
        else:
            s, a = lp["attn"], pre + "self_attn."
            state[a + "q_proj.weight"] = np.concatenate(
                [np.asarray(s["wq"]).reshape(d, H, dk),
                 np.asarray(s["wq_gate"]).reshape(d, H, dk)],
                axis=-1).reshape(d, -1).T
            for ours, theirs in (("wk", "k_proj"), ("wv", "v_proj"),
                                 ("wo", "o_proj")):
                state[a + theirs + ".weight"] = T(s[ours])
            state[a + "q_norm.weight"] = w_of(s["q_norm"]["scale"])
            state[a + "k_norm.weight"] = w_of(s["k_norm"]["scale"])
        m = pre + "mlp."
        state[m + "gate.weight"] = T(lp["moe"]["router"])
        for e in range(cfg.num_experts):
            for ours, theirs in (("wg", "gate_proj"), ("wi", "up_proj"),
                                 ("wo", "down_proj")):
                state[f"{m}experts.{e}.{theirs}.weight"] = \
                    T(lp["moe"][ours][e])
        for ours, theirs in (("wg", "gate_proj"), ("wi", "up_proj"),
                             ("wo", "down_proj")):
            state[f"{m}shared_expert.{theirs}.weight"] = \
                T(lp["shared"][ours])
        state[m + "shared_expert_gate.weight"] = T(lp["shared"]["gate"])
    return state


def test_the_loader_folds_a_nonzero_w_and_regroups_the_interleaved_rows(tiny):
    """A state dict in the PUBLISHED layout with non-zero ``w`` in every
    zero-centred norm loads into the tree it was written from: ``scale = 1
    + w`` (the hand-written line), ``linear_attn.norm`` left as it is, the
    ``[q | gate]`` and ``[q | k | v | z]`` interleavings undone; a share
    loads its own experts."""
    hf, cfg, params, tokens, want = tiny
    state = _published_state(cfg, params,
                             lambda scale: np.asarray(scale) - 1.0)
    w = state["model.layers.0.input_layernorm.weight"]
    assert np.abs(w).max() > 0.1        # not the initialiser's zeros
    assert np.array_equal(hf_loader.fold_zero_centred(w), 1.0 + w)
    got = hf_loader.params_from_state(cfg, hf, state.__getitem__, set(state))
    flat_got, tree = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(params)
    assert tree == tree_want
    for a, b in zip(flat_got, flat_want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    # the gated norm is NOT zero-centred: loaded as published
    assert np.array_equal(
        np.asarray(got["layers"][0]["ssm"]["norm"]["scale"]),
        state["model.layers.0.linear_attn.norm.weight"])
    assert np.abs(uncached(cfg, got, tokens[:64]) - want[:64]).max() < F32_TOL
    share = {"router_experts": 8, "first_expert": 2, "held_experts": 4}
    cfg_s = config_from_hf(dict(hf, expert_share=share))
    held = hf_loader.params_from_state(cfg_s, hf, state.__getitem__,
                                       set(state))
    assert held["layers"][1]["moe"]["wg"].shape[0] == 4 and np.array_equal(
        np.asarray(held["layers"][1]["moe"]["wo"]),
        np.asarray(params["layers"][1]["moe"]["wo"][2:6]))


# -- the uncached forward -------------------------------------------------------

def test_uncached_forward_is_the_reference(tiny):
    _, cfg, params, tokens, want = tiny
    got = uncached(cfg, params, tokens)
    assert np.abs(got - want).max() < F32_TOL
    assert want.std() > 0.1


def _in_layers(params, part, **leaves):
    return dict(params, layers=[
        dict(lp, **{part: dict(lp[part], **{
            k: f(lp[part][k]) for k, f in leaves.items()})})
        if part in lp else lp for lp in params["layers"]])


def _no_one_plus(params):
    """Every zero-centred norm multiplying by ``w`` alone."""
    less = lambda n: {"scale": n["scale"] - 1.0}
    out = _in_layers(params, "attn", q_norm=less, k_norm=less)
    return dict(out, final_norm=less(params["final_norm"]), layers=[
        dict(lp, ln1=less(lp["ln1"]), ln2=less(lp["ln2"]))
        for lp in out["layers"]])


WRONG = {
    "bf16_weights": (lambda cfg: cfg, lambda p: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
        if a.ndim >= 2 else a, p)),
    "rotary_over_the_whole_head": (
        lambda cfg: dataclasses.replace(cfg, rotary_pct=1.0), lambda p: p),
    "a_norm_without_its_one_plus": (lambda cfg: cfg, _no_one_plus),
    "no_output_gate": (
        lambda cfg: dataclasses.replace(cfg, attn_output_gate=False),
        lambda p: p),
    "shared_expert_ungated": (lambda cfg: cfg, lambda p: dict(p, layers=[
        dict(lp, shared={k: v for k, v in lp["shared"].items()
                         if k != "gate"}) for lp in p["layers"]])),
    "head_norms_dropped": (
        lambda cfg: dataclasses.replace(cfg, qk_head_norm=False),
        lambda p: p),
    "a_softmax_over_the_kept_alone_unnormalised": (
        lambda cfg: dataclasses.replace(cfg, norm_topk_prob=False),
        lambda p: p),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_program_wrong_in_one_way_is_caught(name, tiny):
    _, cfg, params, tokens, want = tiny
    change_cfg, change = WRONG[name]
    diff = np.abs(uncached(change_cfg(cfg), change(params), tokens[:200])
                  - want[:200]).max()
    assert diff > 50 * F32_TOL, diff


def _patched(monkeypatch, name):
    if name == "decay_dropped":
        real = ssm.delta_inputs

        def no_decay(cfg, p, u, ba, counts):
            q, k, v, beta, g = real(cfg, p, u, ba, counts)
            return q, k, v, beta, jnp.zeros_like(g)
        monkeypatch.setattr(ssm, "delta_inputs", no_decay)
    elif name == "gate_before_the_norm":
        real = ssm.gated_norm

        def gate_first(cfg, p, y, z, dtype, groups, gate_first):
            scale = jnp.tile(p["norm"]["scale"], groups)
            return real(cfg, dict(p, norm={"scale": scale}), y, z, dtype,
                        groups, True)
        monkeypatch.setattr(ssm, "gated_norm", gate_first)
    elif name == "no_activation_after_the_taps":
        real = ssm.conv_rows
        monkeypatch.setattr(
            ssm, "conv_rows", lambda *a, **kw: real(*a[:6], silu=False))
    elif name == "state_in_bf16":
        for form in ("delta_step", "delta_chunk"):
            def rounded(*a, _scan=getattr(ssm, form), **kw):
                y, s = _scan(*a, **kw)
                return y, s.astype(jnp.bfloat16).astype(s.dtype)
            monkeypatch.setattr(ssm, form, rounded)
    elif name == "stale_slots":
        monkeypatch.setattr(ssm, "fresh_rows",
                            lambda starts: jnp.zeros(starts.shape, bool))
    else:
        raise KeyError(name)


@pytest.mark.parametrize("name", ["decay_dropped", "gate_before_the_norm",
                                  "no_activation_after_the_taps"])
def test_a_mixer_wrong_in_one_way_is_caught(name, tiny, monkeypatch):
    """What the KIND says and the tree does not: the decay, the side of the
    gate, the SiLU after taps that have no bias."""
    _, cfg, params, tokens, want = tiny
    _patched(monkeypatch, name)
    diff = np.abs(uncached(cfg, params, tokens[:200]) - want[:200]).max()
    assert diff > 50 * F32_TOL, diff


# -- hand-written lines ---------------------------------------------------------

def test_partial_rotary_turns_the_first_quarter_of_a_head(tiny):
    """``typed_qkv`` against a hand-written rotate-half on dims 0-7 of a
    head of 32 (θ 1e7), dims 8-31 passed through; the head norms come
    first, with their ``1 + w`` folded."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][3]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 128), jnp.float32)
    pos = jnp.asarray([[0, 1, 7, 100, 4000]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        q, k, v = tl.typed_qkv(cfg, 0, p, x, *tl.rope_tables(cfg, pos)[0])
        raw = (x[0] @ p["wq"]).reshape(5, 4, 32)
    assert cfg.rope_dim == 8 and q.shape == (1, 5, 4, 32)
    normed = raw * jax.lax.rsqrt(jnp.mean(raw ** 2, -1, keepdims=True)
                                 + 1e-6) * p["q_norm"]["scale"]
    inv = 1e7 ** (-np.arange(0, 8, 2) / 8)
    ang = np.asarray(pos[0], np.float64)[:, None] * inv[None]
    x1, x2 = np.asarray(normed[..., :4]), np.asarray(normed[..., 4:8])
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           np.asarray(normed[..., 8:])], axis=-1)
    # positions of thousands turn float32 angles by 1e-4
    assert np.abs(np.asarray(q[0]) - want).max() < 2e-3
    assert np.array_equal(np.asarray(q[0, :, :, 8:]),
                          np.asarray(normed[..., 8:]))
    assert np.abs(np.asarray(q[0, 0]) - np.asarray(normed[0])).max() < 1e-6


def test_the_output_gate_is_a_sigmoid_a_head_and_dim(tiny):
    _, cfg, params, _, _ = tiny
    p = params["layers"][3]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 128), jnp.float32)
    o = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 4, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = tl.typed_attn_out(cfg, p, o, h)
        want = (o.reshape(6, 128) * jax.nn.sigmoid(h[0] @ p["wq_gate"])) \
            @ p["wo"]
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-6


def test_the_shared_expert_is_behind_one_sigmoid_a_token(tiny):
    _, cfg, params, _, _ = tiny
    lp = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 9, 128), jnp.float32)
    sh = lp["shared"]
    with jax.default_matmul_precision("highest"):
        both = tl.typed_ffn(cfg, lp, h, None)
        routed = tl.typed_ffn(cfg, {k: v for k, v in lp.items()
                                    if k != "shared"}, h, None)
        glu = (jax.nn.silu(h[0] @ sh["wg"]) * (h[0] @ sh["wi"])) @ sh["wo"]
        want = jax.nn.sigmoid(h[0] @ sh["gate"]) * glu
    assert sh["gate"].shape == (128, 1)
    assert np.abs(np.asarray((both - routed)[0]) - np.asarray(want)).max() \
        < 1e-6


def test_the_gated_norm_norms_first_then_gates(tiny):
    """``w ⊙ (o·rsqrt(mean(o²) + eps)) ⊙ silu(z)`` a head, by hand; the
    Mamba-2 side of the same function gates FIRST."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
    got = ssm.gated_norm(cfg, p, y, z, jnp.float32, cfg.ssm_heads, False)
    yh = np.asarray(y).reshape(3, 4, 8)
    want = yh / np.sqrt((yh ** 2).mean(-1, keepdims=True) + 1e-6) * \
        np.asarray(p["norm"]["scale"]) * \
        np.asarray(jax.nn.silu(z)).reshape(3, 4, 8)
    assert np.abs(np.asarray(got) - want.reshape(3, 32)).max() < 1e-6
    wide = {"norm": {"scale": jnp.tile(p["norm"]["scale"], 4)}}
    first = ssm.gated_norm(cfg, wide, y, z, jnp.float32, cfg.ssm_heads, True)
    assert np.abs(np.asarray(first) - np.asarray(got)).max() > 0.1


def test_the_mixers_projections_take_their_inputs_unrounded():
    """``_linear_wide``: a float32 input as two bf16 halves against bf16
    weights — the float32 product to 2^-16 of it, where the rounded input
    reads 2^-9; float32 weights take one product."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(7, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 64)), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(x @ w.astype(jnp.float32))
        wide = np.asarray(tl._linear_wide(x, {"w": w}, "w"))
        narrow = np.asarray(tl._linear_f32(x.astype(jnp.bfloat16), {"w": w},
                                           "w"))
        same = np.asarray(tl._linear_wide(x, {"w": w.astype(jnp.float32)},
                                          "w"))
    scale = np.abs(want).max()
    assert wide.dtype == np.float32
    assert np.abs(wide - want).max() < 1e-4 * scale
    assert np.abs(narrow - want).max() > 1e-3 * scale
    assert np.abs(same - want).max() < 1e-6 * scale
    assert tl.mixer_forms(6).wide_input and not tl.mixer_forms(3).wide_input


def test_the_taps_have_no_bias_and_a_silu(tiny):
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    assert "conv_b" not in p and tl.mixer_forms(6).conv_silu and \
        not tl.mixer_forms(5).conv_silu
    rng = np.random.default_rng(4)
    tail = jnp.asarray(rng.normal(size=(2, 3, 96)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, 1, 96)), jnp.float32)
    y, after = ssm.conv_rows(cfg, p, u, tail, jnp.asarray([1, 0], jnp.int32))
    w = np.asarray(p["conv_w"])
    acc = sum(w[:, i] * tail[0, i] for i in range(3)) + w[:, 3] * u[0, 0]
    assert np.abs(np.asarray(y[0, 0]) - np.asarray(jax.nn.silu(acc))).max() \
        < 1e-6
    assert np.array_equal(np.asarray(after[1]), np.asarray(tail[1]))


# -- the two forms against the recurrence ---------------------------------------

def _recurrence(sel, state, counts):
    """The module docstring's four lines in float64, token by token."""
    q, k, v, beta, g = (np.asarray(t, np.float64) for t in sel)
    m, c, G, R = g.shape
    s = np.asarray(state, np.float64).reshape(m, G, R, q.shape[-1], -1).copy()
    out = np.zeros((m, c, G, R, v.shape[-1]))
    for r in range(m):
        for t in range(int(counts[r])):
            for gi in range(G):
                for ri in range(R):
                    S = s[r, gi, ri] * np.exp(g[r, t, gi, ri])
                    read = S.T @ k[r, t, gi]
                    S = S + np.outer(k[r, t, gi], beta[r, t, gi, ri] *
                                     (v[r, t, gi, ri] - read))
                    s[r, gi, ri] = S
                    out[r, t, gi, ri] = S.T @ q[r, t, gi]
    return out.reshape(m, c, -1), s.reshape(np.asarray(state).shape)


def _mixer_inputs(cfg, p, seed, m, c, counts, channels=False):
    """(``sel``, state, counts) of seeded rows; ``channels``: the convolved
    channels ``sel`` was made from, first."""
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(0, 1, (m, c, cfg.ssm_conv_dim)), jnp.float32)
    ba = tuple(jnp.asarray(rng.normal(0, 1, (m, c, cfg.ssm_heads)),
                           jnp.float32) for _ in range(2))
    state = jnp.asarray(rng.normal(0, 1, (m,) + ssm.state_shape(cfg)),
                        jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    rows = ssm.delta_inputs(cfg, p, u, ba, counts), state, counts
    return (u,) + rows if channels else rows


@pytest.mark.parametrize("c", [2, 16, 37, 128])
def test_chunk_form_is_the_recurrence_token_by_token(c, tiny):
    """``delta_chunk`` from a CARRIED state on ragged rows — one full, one
    cut, one of ONE live position, one with NO live position — against the
    float64 recurrence: outputs at the live positions, the state after each
    row's last live one (a row with none keeps its own). 1e-5 of values of
    order 1: float32 sums in another order, through a triangular solve."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    counts = [c, max(c // 2, 1), 1, 0]
    sel, state, counts = _mixer_inputs(cfg, p, c, 4, c, counts)
    with jax.default_matmul_precision("highest"):
        o, s = ssm.delta_chunk(cfg, p, None, sel, state, counts)
    want_o, want_s = _recurrence(sel, state, counts)
    for r in range(4):
        n = int(counts[r])
        assert np.abs(np.asarray(o[r, :n]) - want_o[r, :n]).max(initial=0) \
            < 1e-5
    assert np.abs(np.asarray(s) - want_s).max() < 1e-5
    assert np.array_equal(np.asarray(s[3]), np.asarray(state[3]))
    assert np.abs(want_s[0] - np.asarray(state[0])).max() > 0.1


def test_step_form_is_the_recurrence_and_resets_in_its_decay(tiny):
    """``delta_step`` on rows of one position: a live row, a RESET row (its
    stale state counts for nothing), a row with no live position (state
    kept, whatever its inputs)."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    sel, state, counts = _mixer_inputs(cfg, p, 9, 3, 1, [1, 1, 0])
    reset = jnp.asarray([False, True, False])
    o, s = ssm.delta_step(cfg, p, None, sel, state, counts, reset)
    zeroed = state.at[1].set(0.0)
    want_o, want_s = _recurrence(sel, zeroed, counts)
    assert np.abs(np.asarray(o[:2]) - want_o[:2]).max() < 1e-5
    assert np.abs(np.asarray(s) - want_s).max() < 1e-5
    assert np.array_equal(np.asarray(s[2]), np.asarray(state[2]))


def test_the_two_forms_agree_through_a_chain_of_chunks(tiny):
    """Three chunks of 32 from the state each left, against 96 steps."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    sel, state, _ = _mixer_inputs(cfg, p, 2, 2, 96, [96, 96])
    s_chunk, outs = state, []
    full = jnp.asarray([32, 32], jnp.int32)
    for i in range(3):
        part = tuple(t[:, 32 * i:32 * i + 32] for t in sel)
        o, s_chunk = ssm.delta_chunk(cfg, p, None, part, s_chunk, full)
        outs.append(o)
    s_step, steps = state, []
    one = jnp.asarray([1, 1], jnp.int32)
    for t in range(96):
        o, s_step = ssm.delta_step(cfg, p, None,
                                   tuple(x[:, t:t + 1] for x in sel),
                                   s_step, one)
        steps.append(o)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1)) -
                  np.asarray(jnp.concatenate(steps, 1))).max() < 1e-5
    assert np.abs(np.asarray(s_chunk) - np.asarray(s_step)).max() < 1e-5


# -- the chunk form's kernel (interpret mode: what it computes, not whether it
# lowers — tests/test_tpu_compile.py) ------------------------------------------

@pytest.fixture(scope="module")
def wide():
    """The delta rule at heads the kernel takes: ONE key head of 128 serving
    two value heads of 128 (the published ``R`` = 2), an ``A`` that
    remembers."""
    cfg = config_from_hf(small(
        linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=128, linear_value_head_dim=128))
    rng = np.random.default_rng(11)
    return cfg, {"A_log": jnp.log(jnp.asarray(rng.uniform(0.01, 1.0, (2,)),
                                              jnp.float32)),
                 "dt_bias": jnp.ones((2,), jnp.float32)}


@pytest.fixture()
def interpreted(monkeypatch):
    """``delta_chunk(kernel=True)`` reaches the kernel in interpret mode,
    and counts its calls."""
    calls = []

    def kernel(*args, **kw):
        calls.append(kw)
        return run(*args, interpret=True, **kw)
    run = ssm.delta_chunk_kernel
    monkeypatch.setattr(ssm, "delta_chunk_kernel", kernel)
    return calls


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("whole", [False, True])
def test_both_chunk_forms_are_the_recurrence_at_lane_wide_heads(
        kernel, whole, wide, interpreted):
    """The existing test's rows at ``d_k = d_v = 128``, chunk 128, from a
    CARRIED state — one full, one of 23 live positions, one of 1, one of 0
    — through the XLA form and the kernel, the values read from the
    convolved channels as they lie (``whole``) or handed alone. The row
    with no live position keeps its state BIT for bit; the kernel leaves
    its outputs, and every position past a row's last turn, zero."""
    cfg, p = wide
    u, sel, state, counts = _mixer_inputs(cfg, p, 128, 4, 128,
                                          [128, 23, 1, 0], channels=True)
    with jax.default_matmul_precision("highest"):
        o, s = ssm.delta_chunk(cfg, p, u if whole else None, sel, state,
                               counts, kernel=kernel)
    assert len(interpreted) == kernel
    want_o, want_s = _recurrence(sel, state, counts)
    for r in range(4):
        n = int(counts[r])
        assert np.abs(np.asarray(o[r, :n]) - want_o[r, :n]).max(initial=0) \
            < 1e-5
    assert np.abs(np.asarray(s) - want_s).max() < 1e-5
    assert np.array_equal(np.asarray(s[3]), np.asarray(state[3]))
    assert np.abs(want_s[0] - np.asarray(state[0])).max() > 0.1
    if kernel:
        turn = ssm.DELTA_KERNEL_SUB
        assert not np.asarray(o[3]).any() and not np.asarray(o[2, turn:]).any()
        assert not np.asarray(o[1, -(-23 // turn) * turn:]).any()


@pytest.mark.parametrize("sub", [32, 64])
def test_the_kernel_is_the_xla_form_at_every_turn_width(sub, wide):
    """A chunk walked in turns of 32 or 64 positions is the SAME
    recurrence: a turn starts from the state the turn before left."""
    cfg, p = wide
    u, sel, state, counts = _mixer_inputs(cfg, p, sub, 3, 128, [128, 70, 0],
                                          channels=True)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = ssm.delta_chunk(cfg, p, u, sel, state, counts)
    q, k, _, beta, g = (t.reshape(3, 128, -1) for t in sel)
    o, s = ssm.delta_chunk_kernel(q, k, u, beta, g, state, counts, sub=sub,
                                  interpret=True)
    live = (np.arange(128)[None] < np.asarray(counts)[:, None])[..., None]
    assert np.abs(np.where(live, np.asarray(o) - np.asarray(want_o), 0)
                  ).max() < 2e-6
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 1e-5


def test_the_kernel_agrees_with_the_steps_through_a_chain_of_chunks(
        wide, interpreted):
    """Three chunks of 64 through the KERNEL from the state each left,
    against 192 steps of ``delta_step``."""
    cfg, p = wide
    sel, state, _ = _mixer_inputs(cfg, p, 2, 2, 192, [192, 192])
    s_chunk, outs = state, []
    full = jnp.asarray([64, 64], jnp.int32)
    for i in range(3):
        part = tuple(t[:, 64 * i:64 * i + 64] for t in sel)
        o, s_chunk = ssm.delta_chunk(cfg, p, None, part, s_chunk, full,
                                     kernel=True)
        outs.append(o)
    assert len(interpreted) == 3
    s_step, steps = state, []
    one = jnp.asarray([1, 1], jnp.int32)
    for t in range(192):
        o, s_step = ssm.delta_step(cfg, p, None,
                                   tuple(x[:, t:t + 1] for x in sel),
                                   s_step, one)
        steps.append(o)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1)) -
                  np.asarray(jnp.concatenate(steps, 1))).max() < 1e-5
    assert np.abs(np.asarray(s_chunk) - np.asarray(s_step)).max() < 1e-5


def test_what_takes_the_kernel_is_the_heads_width_and_the_engines_word(
        tiny, wide, interpreted):
    """``mixer_forms(6, kernel)`` binds the engine's ``use_pallas`` as kind
    4 does; with it, heads of whole lane tiles and a chunk of whole turns
    take the kernel — the rehearsal widths (``d_k`` 16 here) and a chunk of
    96 keep the XLA form, as every call without it does."""
    for kind in (4, 6):
        assert tl.mixer_forms(kind).chunk.keywords == {"kernel": False}
        assert tl.mixer_forms(kind, True).chunk.keywords == {"kernel": True}
    assert tl.mixer_forms(6, True).step is ssm.delta_step
    assert ssm.delta_kernel_takes(128, 128, 128) and \
        not ssm.delta_kernel_takes(32, 128, 128) and \
        not ssm.delta_kernel_takes(128, 64, 128) and \
        not ssm.delta_kernel_takes(128, 128, 96)
    _, cfg, params, _, _ = tiny
    narrow = params["layers"][0]["ssm"]
    for at, p, c, kernel in ((cfg, narrow, 128, True), (wide[0], wide[1], 96,
                                                       True),
                             (wide[0], wide[1], 128, False)):
        sel, state, counts = _mixer_inputs(at, p, 4, 2, c, [c, 5])
        o, s = ssm.delta_chunk(at, p, None, sel, state, counts, kernel=kernel)
        want_o, want_s = ssm.delta_chunk(at, p, None, sel, state, counts)
        assert np.array_equal(np.asarray(o), np.asarray(want_o)) and \
            np.array_equal(np.asarray(s), np.asarray(want_s))
    assert not interpreted


def test_the_unit_lower_inverse_is_an_inverse():
    rng = np.random.default_rng(0)
    # (entries of the size a chunk makes: β·γ·k_t·k_s of unit keys)
    a = np.tril(rng.normal(0, 0.1, (3, 2, 64, 64)), -1).astype(np.float32)
    inv = np.asarray(ssm._unit_lower_inverse(jnp.asarray(a)))
    eye = np.eye(64, dtype=np.float32)
    assert np.abs(inv @ (eye + a) - eye).max() < 1e-4
    assert np.abs(np.triu(inv, 1)).max() == 0.0


# -- the engine -----------------------------------------------------------------

def _walk(eng, tokens, prompt_len, uid=0):
    """Prefill ``tokens[:prompt_len]`` (chunks of 128), then feed the rest
    a token a step: the logits that predicted each position from the
    prompt's last on."""
    out = eng.put([uid], [list(tokens[:prompt_len])])
    rows = [np.asarray(out[uid], np.float32)]
    for t in tokens[prompt_len:]:
        rows.append(np.asarray(eng.put([uid], [[int(t)]])[uid], np.float32))
    return np.stack(rows)


@pytest.mark.parametrize("prompt_len", [1, 127, 128, 129, 400])
def test_prefill_then_decode_is_the_reference(prompt_len, tiny):
    """The delta rule WITH the experts through the fresh, the split and the
    decode programs: the state across chunk edges (127 / 128 / 129) and
    across launches (400: a fresh chunk and three split ones), then six
    decode steps through the pools and the pages. LOGITS, not tokens."""
    _, cfg, params, tokens, want = tiny
    with jax.default_matmul_precision("highest"):
        got = _walk(engine(cfg, params), tokens[:prompt_len + 6], prompt_len)
    assert np.abs(got - want[prompt_len - 1:prompt_len + 6]).max() < F32_TOL


def test_bf16_serving_keeps_a_float32_state(tiny):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, dtype="bfloat16")
    # a pool a delta-rule layer: a slot a sequence, and the trash; [H_v,
    # d_k, d_v] of state, K - 1 rows of the [q | k | v] channels, float32
    # both (the mixer's norms pass an input's rounding on three times over)
    for i in range(3):
        assert eng.arena[f"ssm{i}"].dtype == jnp.float32 and \
            eng.arena[f"conv{i}"].dtype == jnp.float32
        assert eng.arena[f"ssm{i}"].shape == (9, 4, 16, 8) and \
            eng.arena[f"conv{i}"].shape == (9, 3 * 96)
    # the full layer's pools: a head is NOT padded or split
    assert "ssm3" not in eng.arena and eng.arena["k"].shape[-1] == 2 * 32
    got = _walk(eng, tokens[:140], 130)
    assert np.abs(got - want[129:140]).max() < BF16_TOL


def test_rows_of_both_forms_in_one_launch(tiny):
    """Four sequences at once, 4-row programs at capacities 64 / 128: a
    prompt of 400 arrives while three sequences decode, a step at a time;
    its later chunks ride GROUPED split steps — one row in the chunk form
    from the state the earlier launches left, three rows of one query
    stepping the recurrence by slot, the state pools carried through the
    capacity switch. The long prompt's last logits and every decode row's
    are the reference's; the counters count the new kind as they count
    kinds 3 and 4."""
    from deepspeed_tpu.telemetry.registry import registry
    hf, cfg, params, tokens, want = tiny
    w = ref.Widths.from_hf(hf)
    rng = np.random.default_rng(9)
    seqs = {u: rng.integers(0, VOCAB, 40 + 3 * u) for u in range(1, 4)}
    eng = engine(cfg, params, max_sequences=4, max_batch_tokens=128)
    count = {name: registry.counter("dispatch/" + name) for name in (
        "steps.split", "split_grouped_steps", "state_rows", "state_resets",
        "ssm_chunk_tokens")}
    before = {name: c.value for name, c in count.items()}
    got = {u: [] for u in seqs}
    with jax.default_matmul_precision("highest"):
        eng.put(list(seqs), [list(s[:-8]) for s in seqs.values()])
        eng._put_validated([0], [list(tokens[:400])])
        for step in range(8):
            eng._put_validated(list(seqs), [[int(s[len(s) - 8 + step])]
                                            for s in seqs.values()])
            out = eng.step_with_budget(mode=None,
                                       budget=None if step == 0 else 56)
            for u in seqs:
                got[u].append(np.asarray(out[u], np.float32))
            if 0 in out:
                long_logits = np.asarray(out[0], np.float32)
    moved = {name: c.value - before[name] for name, c in count.items()}
    assert eng._token_capacities(4, 128, "split") == (64, 128)
    assert moved["steps.split"] == 7 and \
        moved["split_grouped_steps"] == 6 and moved["state_resets"] == 4
    assert moved["state_rows"] == 3 + 8 * 3 + 7 and \
        moved["ssm_chunk_tokens"] > 400
    assert np.abs(long_logits - want[399]).max() < F32_TOL
    for u, s in seqs.items():
        full = ref.logits_of(w, params, s, CPU)
        assert np.abs(np.stack(got[u]) - full[-8:]).max() < F32_TOL


def test_a_reused_slot_starts_from_zero(tiny):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, max_sequences=1)
    with jax.default_matmul_precision("highest"):
        _walk(eng, np.random.default_rng(4).integers(0, VOCAB, 150), 140)
        slot = eng.state.seqs[0].slot
        eng.flush(0)
        stale = np.asarray(eng.arena["ssm0"])[slot]
        assert np.abs(stale).max() > 1e-3       # the pool is NOT cleaned
        got = _walk(eng, tokens[:40], 33, uid=1)
    assert eng.state.seqs[1].slot == slot
    assert np.abs(got - want[32:40]).max() < F32_TOL


@pytest.mark.parametrize("control", ["state_in_bf16", "stale_slots",
                                     "decay_dropped"])
def test_the_engine_wrong_in_one_way_is_caught(control, tiny, monkeypatch):
    """Through the pools: a state rounded to bf16 between launches, a
    reused slot read as it was left, a state that never decays."""
    _, cfg, params, tokens, want = tiny
    _patched(monkeypatch, control)
    eng = engine(cfg, params, max_sequences=1)
    with jax.default_matmul_precision("highest"):
        _walk(eng, np.random.default_rng(4).integers(0, VOCAB, 150), 140)
        eng.flush(0)
        got = _walk(eng, tokens[:140], 130, uid=1)
    assert np.abs(got - want[129:140]).max() > 50 * F32_TOL


def test_the_frontend_serves_it_and_refuses_what_a_state_forbids(tiny):
    from deepspeed_tpu.serving import ServingFrontend
    _, cfg, params, tokens, _ = tiny
    eng = engine(cfg, params)
    fe = ServingFrontend(eng)
    assert fe.cache is None                 # a recurrent stack gets none
    reqs = [fe.submit(tokens[i:i + 20 + 7 * i].tolist(), max_new_tokens=5)
            for i in range(3)]
    fe.run_until_idle()
    fe.close()
    for i, r in enumerate(reqs):
        prompt = tokens[i:i + 20 + 7 * i].tolist()
        full = prompt + list(r.tokens_out)
        logits = uncached(cfg, params, full[:-1])
        assert r.finish_reason == "length" and list(r.tokens_out) == \
            logits[len(prompt) - 1:].argmax(-1).tolist()
    with pytest.raises(NotImplementedError, match="recurrent stack"):
        eng.export_pages(0)


# -- the 256-wide head through the paged readers --------------------------------

def test_paged_kernel_reads_a_head_of_two_lane_tiles():
    """``paged_attn_lse`` in interpret mode at the published head: 16 query
    heads on 2 KV heads of 256 (8 queries a KV head; K and V pools 512
    lanes a token, a head NOT padded or split), chunks of 8 live queries
    over pages of 8, and rows of ONE query: against the XLA history
    reader."""
    n, c, h, kvh, d, bs, mb = 3, 8, 16, 2, 256, 8, 6
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((n, c, h, d)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((n * mb + 1, bs, kvh * d)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((n * mb + 1, bs, kvh * d)),
                         jnp.float32)
    pt = jnp.asarray(rng.permutation(n * mb).reshape(n, mb), jnp.int32)
    starts = jnp.asarray([40, 13, 0], jnp.int32)
    assert pa.heads_per_program(8 * c, kvh, 2 * d, 2 * d, bs, 4) >= 1
    got, lse = pa.paged_attention_with_lse(
        q, pool_k, pool_v, pt, starts, jnp.zeros_like(starts),
        interpret=True, scale=d ** -0.5)
    want, want_lse = pa.paged_attention_hist_xla(
        q, pool_k, pool_v, pt, starts, scale=d ** -0.5)
    live = np.asarray(want_lse) > -1e29
    assert live[0].all() and live[1].all() and not live[2].any()
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < 2e-5
    assert np.abs(np.asarray(lse) - np.asarray(want_lse))[live].max() < 2e-5
    one, lse1 = pa.paged_attention_with_lse(
        q[:, :1], pool_k, pool_v, pt, starts, jnp.zeros_like(starts),
        interpret=True, scale=d ** -0.5)
    assert np.abs(np.asarray(one[:2]) - np.asarray(want[:2, :1])).max() < 2e-5


# -- the share ------------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold one of the router's 8 experts each. Their routed
    parts (the program's ``held_experts_moe_layer`` on each share's slice of
    the weights), with the mixer and the GATED shared expert counted ONCE,
    add up to the reference's uncut layer."""
    hf = small(num_hidden_layers=1)
    cfg = config_from_hf(hf)
    w = ref.Widths.from_hf(hf)
    assert w.held_experts == 8 and cfg.experts_held is None
    lp = randomised(tf.init_params(cfg, jax.random.PRNGKey(11),
                                   jnp.float32))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(12), (256, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._layer(x, lp, w, False)
        mixed = x + ref.delta_mixer(w, lp["ssm"], ref.rms0(
            x, lp["ln1"]["scale"], w.eps))
        hin = ref.rms0(mixed, lp["ln2"]["scale"], w.eps)
        routed = jnp.zeros_like(x)
        for chip in range(8):
            share = {"router_experts": 8, "first_expert": chip,
                     "held_experts": 1}
            cfg_i = config_from_hf(small(expert_share=share,
                                         num_hidden_layers=1))
            p_i = dict(lp["moe"], **{k: lp["moe"][k][chip:chip + 1]
                                     for k in ("wg", "wi", "wo")})
            part = moe.held_experts_moe_layer(cfg_i, p_i, hin[None])[0][0]
            w_i = ref.Widths.from_hf(small(expert_share=share,
                                           num_hidden_layers=1))
            assert float(jnp.abs(
                part - ref.experts_part(hin, p_i, w_i)).max()) < 1e-5
            routed = routed + part
        once = mixed + ref.shared_part(hin, lp["shared"])
    assert float(jnp.abs(once + routed - whole).max()) < 1e-4
    assert float(jnp.abs(routed).max()) > 1e-3      # ... and not vacuously
