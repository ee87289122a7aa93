"""LFM2-MoE's stack (Liquid AI LFM2-24B-A2B; ``hf_loader``: ``lfm2_moe``) on
the typed stack: EVERY layer a mixer — a GATED SHORT CONVOLUTION (kind 5:
three taps between two gates, a carried tail and no state) or 64-wide GQA
under q / k head norms and RoPE — AND a dense SiLU-GLU or sigmoid-routed
experts under two norms, a tied head: the program against the benchmark's
plain float32 reference (``benchmark/reference/lfm2_moe_decoder.py``) on
seeded random weights at a small size, with controls that are wrong in one
way each and must not pass.

Tolerances (largest |logit difference|; the logits spread by 0.3 at this
size). ``F32_TOL`` 5e-6 — both sides float32 at ``highest`` precision; the
two differ in the ORDER of float32 sums alone (the program's attention is
blocked another way, its experts are dispatched), readings 5e-7 to 1.2e-6.
Every control reads above 50x that: bf16 weights 2e-3 (the least), a stale
slot, the norms swapped with RoPE, the gates' order and the rest 0.01-0.3.
``BF16_TOL`` 0.06 — bf16 weights, stream inputs, cache and convolution
tails against the float32 reference, reading 0.027: rounding alone is
0.001-0.003 on most rows, and the rows past a position where bf16 flipped
one of the top-2-of-8 experts read 0.01-0.03 (a sanity bound: what tells
the precisions apart is ``F32_TOL``'s bf16-weights control)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
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
                           "lfm2-24b-a2b.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: hidden 128 (two heads of the
    published 64 over two KV heads), layers ``conv conv attention conv``
    with ONE leading dense layer — convolution + dense, convolution +
    experts, attention + experts —, 8 experts, 2 a token."""
    hf = published()
    hf.update(hidden_size=128, num_hidden_layers=4,
              layer_types=["conv", "conv", "full_attention", "conv"],
              num_attention_heads=2, num_key_value_heads=2,
              intermediate_size=96, moe_intermediate_size=48, num_experts=8,
              num_experts_per_tok=2, num_dense_layers=1, vocab_size=VOCAB)
    hf.update(over)
    return hf


def randomised(params, seed: int = 5):
    """What the init makes vacuous, made to count: the q / k head norms'
    scales (ones at init: RMSNorm then commutes with the rotation) and the
    selection bias (zero at init) drawn; the queries x 20 (at hidden 128 the
    init's 0.02 leaves the softmax flat)."""
    rng = np.random.default_rng(seed)
    draw = lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
    scale = lambda n: {"scale": draw(n["scale"])}
    grown = {"attn": {"wq": lambda a: a * 20, "q_norm": scale,
                      "k_norm": scale},
             "moe": {"router_bias": lambda a: jnp.asarray(
                 rng.normal(0, 0.05, a.shape), jnp.float32)}}
    return dict(params, layers=[
        dict(lp, **{part: dict(lp[part], **{k: f(lp[part][k])
                                            for k, f in leaves.items()})
                    for part, leaves in grown.items() if part in lp})
        for lp in params["layers"]])


@pytest.fixture(scope="module")
def tiny():
    hf = small()
    cfg = config_from_hf(hf)
    params = randomised(tf.init_params(cfg, jax.random.PRNGKey(7),
                                       jnp.float32))
    tokens = np.random.default_rng(3).integers(0, VOCAB, 320)
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
            cfg.head_dim, cfg.vocab_size, cfg.intermediate_size,
            cfg.dense_intermediate_size) == \
        (2048, 40, 32, 8, 64, 65536, 1536, 11776)
    kinds = cfg.layer_kinds
    assert (kinds.count(5), kinds.count(0)) == (30, 10) and \
        [l for l, k in enumerate(kinds) if k == 0] == list(range(2, 40, 4))
    assert cfg.layer_sparse == (0, 0) + (1,) * 38
    assert cfg.recurrent and cfg.short_conv and not cfg.selective and \
        cfg.qk_head_norm and cfg.kind_rope_theta(0) == 1e6 and \
        cfg.kind_rope_theta(5) is None
    assert (cfg.ssm_conv_kernel, cfg.ssm_conv_dim) == (3, 2048) and \
        ssm.state_shape(cfg) == (0,)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.router_scoring,
            cfg.router_select_bias, cfg.router_norm_eps, cfg.routed_scale,
            cfg.shared_expert_size, cfg.experts_held) == \
        (64, 4, "sigmoid", True, 1e-6, 1.0, 0, None)
    assert cfg.norm_eps == 1e-5 and cfg.tie_embeddings and \
        cfg.max_seq_len == 128000
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    assert "lm_head" not in shapes
    c, a = shapes["layers"][0], shapes["layers"][2]
    assert set(c) == {"ln1", "conv", "ln2", "mlp"} and \
        set(a) == {"ln1", "attn", "ln2", "moe"}
    assert {k: v.shape for k, v in c["conv"].items()} == {
        "w_in": (2048, 6144), "conv_w": (2048, 3), "w_out": (2048, 2048)}
    assert a["attn"]["q_norm"]["scale"].shape == (64,) and \
        a["attn"]["wk"].shape == (2048, 512) and \
        a["moe"]["wg"].shape == (64, 2048, 1536) and \
        a["moe"]["router_bias"].shape == (64,) and \
        c["mlp"]["wg"].shape == (2048, 11776)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count - 23_843e6) < 2e6          # the name's 24B
    # one chip's share of the EP-8 deployment: ISSUE 56's 3,761M
    held = config_from_hf(dict(published(), expert_share={
        "router_experts": 64, "first_expert": 0, "held_experts": 8}))
    assert held.num_experts == 64 and held.experts_held == (0, 8)
    share = jax.eval_shape(lambda r: tf.init_params(held, r),
                           jax.random.PRNGKey(0))
    assert abs(sum(int(np.prod(a.shape)) for a in jax.tree.leaves(share))
               - 3_761e6) < 1e6
    # a token multiplies 16.8M a convolution mixer, 10.5M an attention
    # mixer, 72.3M a dense layer, the router and 4 experts of 9.4M, the head
    w = ref.Widths.from_hf(published())
    assert ref.matmul_params_per_token(w) == \
        30 * 4 * 2048 ** 2 + 10 * (2 * 2048 * 2048 + 2 * 2048 * 512) + \
        2 * 3 * 2048 * 11776 + 38 * (2048 * 64 + 4 * 3 * 2048 * 1536) + \
        2048 * 65536


def test_reader_holds_every_key_the_harness_checks():
    from benchmark.lib import model as model_lib
    hf = published()
    cfg = config_from_hf(hf)
    held = [key for key in model_lib.BUILT_AS if key in hf]
    assert set(held) >= {"hidden_size", "num_attention_heads",
                         "num_key_value_heads", "num_hidden_layers",
                         "vocab_size", "num_experts", "num_experts_per_tok",
                         "moe_intermediate_size"}
    for key in set(held) - {"intermediate_size"}:    # the dense layers'
        assert getattr(cfg, model_lib.BUILT_AS[key]) == hf[key], key


def test_reader_builds_the_file_whole():
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("lfm2-24b-a2b-l40-e8-serve")
    assert conf["reduced"] == ["expert_share"]
    want = dataclasses.replace(config_from_hf(published()),
                               experts_held=(0, 8))
    assert model_lib.build_model(conf) == want
    tiny_model = model_lib.build_model(conf, rehearse=True)
    assert tiny_model.layer_kinds == (5, 5, 0, 5) and \
        tiny_model.head_dim == 64 and tiny_model.experts_held == (4, 4)


@pytest.mark.parametrize("over,named", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(layer_types=["conv", "sliding_attention", "conv", "conv"]),
     "sliding_attention"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type"),
    (dict(expert_share={"router_experts": 16, "first_expert": 0,
                        "held_experts": 8}), "router_experts")])
def test_reader_refuses_by_name_what_is_not_built(over, named):
    with pytest.raises(ValueError, match="lfm2_moe.*" + named):
        config_from_hf(small(**over))


def test_one_stack_holds_one_kind_of_recurrent_mixer():
    cfg = config_from_hf(small())
    with pytest.raises(ValueError, match="kind 5.*without layers of kinds"):
        dataclasses.replace(cfg, layer_kinds=(5, 0, 3, 5), ssm_heads=4,
                            ssm_head_dim=32, ssm_state_size=8)


# -- the equations --------------------------------------------------------------

def test_uncached_forward_is_the_reference(tiny):
    """320 tokens over all three layer shapes: three steps of the uncached
    mixer, the convolution's tail carried between them."""
    _, cfg, params, tokens, want = tiny
    assert np.abs(uncached(cfg, params, tokens) - want).max() < F32_TOL


def _in_layers(params, part, **leaves):
    return dict(params, layers=[
        dict(lp, **{part: dict(lp[part], **{
            k: f(lp[part][k]) for k, f in leaves.items()})})
        if part in lp else lp for lp in params["layers"]])


def _gates_swapped(w_in):
    """``[B | C | x̃]`` read as ``[C | B | x̃]``: the OUT gate in the
    convolution's input."""
    d = w_in.shape[0]
    return jnp.concatenate([w_in[:, d:2 * d], w_in[:, :d], w_in[:, 2 * d:]],
                           axis=1)


CONTROLS = {
    "gates_in_another_order": lambda cfg, p: (
        cfg, _in_layers(p, "conv", w_in=_gates_swapped)),
    "taps_reversed": lambda cfg, p: (
        cfg, _in_layers(p, "conv", conv_w=lambda w: w[:, ::-1])),
    "head_norm_scale_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "attn", q_norm=lambda n: {
            "scale": jnp.ones_like(n["scale"])})),
    "head_norms_dropped": lambda cfg, p: (
        dataclasses.replace(cfg, qk_head_norm=False), p),
    "selection_bias_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "moe", router_bias=jnp.zeros_like)),
    "rotary_base_of_another_model": lambda cfg, p: (
        dataclasses.replace(cfg, rope_theta=1e4), p),
    "second_norm_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ln2", scale=lambda s: s * 1.5)),
    "softmax_router": lambda cfg, p: (
        dataclasses.replace(cfg, router_scoring="softmax",
                            router_select_bias=False),
        _in_layers(p, "moe", router_bias=jnp.zeros_like)),
    "bf16_weights": lambda cfg, p: (cfg, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_program_wrong_in_one_way_is_caught(name, tiny):
    _, cfg, params, tokens, want = tiny
    wrong_cfg, wrong_params = CONTROLS[name](cfg, params)
    if name == "softmax_router":    # (a softmax router takes no bias leaf)
        wrong_params = dict(wrong_params, layers=[
            dict(lp, moe={k: v for k, v in lp["moe"].items()
                          if k != "router_bias"}) if "moe" in lp else lp
            for lp in wrong_params["layers"]])
    diff = np.abs(uncached(wrong_cfg, wrong_params, tokens) - want).max()
    assert diff > 50 * F32_TOL, diff


def test_head_norms_come_before_the_rotation(tiny, monkeypatch):
    """``RMSNorm`` with a learned scale does not commute with RoPE: a
    program that rotates first is another model."""
    _, cfg, params, tokens, want = tiny
    real = tl.typed_qkv

    def rotated_first(cfg, kind, p, x, sin, cos):
        plain = dataclasses.replace(cfg, qk_head_norm=False)
        q, k, v = real(plain, kind, p, x, sin, cos)
        return tf._norm(cfg, p["q_norm"], q), tf._norm(cfg, p["k_norm"], k), v

    monkeypatch.setattr(tl, "typed_qkv", rotated_first)
    assert np.abs(uncached(cfg, params, tokens) - want).max() > 50 * F32_TOL


def test_the_bias_moves_the_pick_and_never_the_weight():
    """``route_tokens`` against a hand count: scores so small that the
    ``1e-6`` of the normalisation shows (a sum of 4e-6 beside it), a bias
    that lifts two experts into the pick whose scores are NOT the
    largest."""
    cfg = dataclasses.replace(config_from_hf(small()), num_experts_per_tok=2)
    d = cfg.hidden_size
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (5, d)).astype(np.float32)
    router = rng.normal(0, 0.02, (d, 8)).astype(np.float32)
    logits = x @ router - 13.0            # sigmoid ≈ 2e-6
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    bias = np.zeros(8, np.float32)
    bias[[3, 6]] = 1.0
    # the program's router reads x·W_r: fold the shift into a constant input
    p = {"router": jnp.asarray(np.concatenate([router, -13.0 * np.ones(
        (1, 8), np.float32)])), "router_bias": jnp.asarray(bias)}
    xf = jnp.asarray(np.concatenate([x, np.ones((5, 1), np.float32)], 1))
    with jax.default_matmul_precision("highest"):
        topw, topi = moe.route_tokens(cfg, p, xf)
    assert set(map(tuple, np.sort(np.asarray(topi), -1))) == {(3, 6)}
    kept = np.take_along_axis(scores, np.asarray(topi), -1)
    want = kept / (kept.sum(-1, keepdims=True) + 1e-6)
    assert np.abs(np.asarray(topw) - want).max() < 1e-5
    assert want.sum(-1).max() < 0.9       # the 1e-6 is a fifth of the sum
    # ... and 1e-20 (the other sigmoid families') is another weight
    loose = moe.route_tokens(dataclasses.replace(
        cfg, router_norm_eps=1e-20), p, xf)[0]
    assert np.abs(np.asarray(loose).sum(-1) - 1).max() < 1e-5


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One sparse layer at the router's published width 64, top-4: the
    parts that the shares ``first_expert`` 0, 8, .., 56 compute (no shared
    expert: nothing every chip computes alike) add up to the reference's
    uncut 64-expert layer."""
    hf = small(num_experts=64, num_experts_per_tok=4, moe_intermediate_size=16)
    whole = config_from_hf(hf)
    lp = tf.init_params(whole, jax.random.PRNGKey(1),
                        jnp.float32)["layers"][1]["moe"]
    lp = dict(lp, router_bias=jnp.asarray(np.random.default_rng(2).normal(
        0, 0.05, 64), jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 50, 128), jnp.float32)
    w = ref.Widths.from_hf(hf)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts_part(x[0], lp, w))
        total = 0.0
        for first in range(0, 64, 8):
            cfg = config_from_hf(dict(hf, expert_share={
                "router_experts": 64, "first_expert": first,
                "held_experts": 8}))
            part = {k: (v[first:first + 8] if k in ("wg", "wi", "wo") else v)
                    for k, v in lp.items()}
            mine = moe.held_experts_moe_layer(cfg, part, x)[0]
            share = ref.experts_part(x[0], part, ref.Widths.from_hf(dict(
                hf, expert_share={"router_experts": 64,
                                  "first_expert": first,
                                  "held_experts": 8})))
            assert np.abs(np.asarray(mine[0]) - np.asarray(share)).max() \
                < 1e-6
            total = total + np.asarray(mine[0])
    assert np.abs(want).max() > 1e-3 and np.abs(total - want).max() < 1e-6


# -- two forms of one convolution ---------------------------------------------

@pytest.mark.parametrize("cut", [1, 2, 64, 127, 128, 129, 299])
def test_the_carried_tail_is_the_whole_interface(cut, tiny):
    """The mixer over a prompt of 300 cut at ANY boundary — inside the
    taps' reach, at a chunk's edge, one to either side — gives the outputs
    and the tail of one pass: the tail is all a launch hands the next."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["conv"]
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 300, cfg.hidden_size),
                          jnp.float32)
    forms = tl.mixer_forms(5)
    none = jnp.zeros((1, 0), jnp.float32)

    def run(h_part, tail):
        z, u, dt = forms.project(cfg, p, h_part)
        y, tail, state = tl.ssm_rows(
            forms, cfg, p, u, dt, tail, none,
            jnp.asarray([h_part.shape[1]], jnp.int32))
        assert state is none
        return forms.out(cfg, p, y, z), tail

    zero = jnp.zeros((1, 2, cfg.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, tail_w = run(h, zero)
        first, tail = run(h[:, :cut], zero)
        second, tail = run(h[:, cut:], tail)
    assert float(jnp.abs(jnp.concatenate([first, second], 1) -
                         whole).max()) < 1e-6
    assert np.array_equal(np.asarray(tail), np.asarray(tail_w))


def test_one_token_form_is_three_multiply_adds(tiny):
    """``c == 1``: ``w₀·u_{t−2} + w₁·u_{t−1} + w₂·u_t`` from the carried
    tail, the tail shifted by one; a row with no live token keeps its
    own."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["conv"]
    rng = np.random.default_rng(4)
    tail = jnp.asarray(rng.normal(size=(2, 2, 128)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, 1, 128)), jnp.float32)
    y, after = ssm.conv_rows(cfg, p, u, tail, jnp.asarray([1, 0], jnp.int32),
                             silu=False)
    w = np.asarray(p["conv_w"])
    want = w[:, 0] * tail[0, 0] + w[:, 1] * tail[0, 1] + w[:, 2] * u[0, 0]
    assert np.abs(np.asarray(y[0, 0]) - want).max() < 1e-6
    assert np.array_equal(np.asarray(after[0]),
                          np.stack([tail[0, 1], u[0, 0]])) and \
        np.array_equal(np.asarray(after[1]), np.asarray(tail[1]))


# -- the engine -------------------------------------------------------------------

def _walk(eng, tokens, prompt_len, uid=0):
    """Prefill ``tokens[:prompt_len]`` (chunks of 128), then feed the rest
    a token a step: the logits that predicted each position from the
    prompt's last on."""
    out = eng.put([uid], [list(tokens[:prompt_len])])
    rows = [np.asarray(out[uid], np.float32)]
    for t in tokens[prompt_len:]:
        rows.append(np.asarray(eng.put([uid], [[int(t)]])[uid], np.float32))
    return np.stack(rows)


def _poison(eng):
    """Every slot of every convolution pool holds what no sequence left:
    a reused slot is never cleaned, so the PROGRAM resets a fresh row."""
    for name in list(eng.arena):
        if ssm.is_state_pool(name):
            eng.arena[name] = jnp.full_like(eng.arena[name], 7.0)


@pytest.mark.parametrize("prompt_len", [1, 127, 128, 129, 300])
def test_prefill_then_decode_is_the_reference(prompt_len, tiny):
    """All three layer shapes through the fresh, the split and the decode
    programs from POISONED pools: the tail across chunk edges (127 / 128 /
    129) and across launches (300: a fresh chunk and two split ones), then
    six decode steps through the pools and the pages. LOGITS, not
    tokens."""
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params)
    assert sorted(n for n in eng.arena if ssm.is_state_pool(n)) == \
        ["conv0", "conv1", "conv2"]         # a tail a layer, and NO state
    assert eng.arena["conv0"].shape == (9, 2 * 128) and \
        eng.arena["k"].shape[-1] == 2 * 64  # no head is padded in the pool
    _poison(eng)
    with jax.default_matmul_precision("highest"):
        got = _walk(eng, tokens[:prompt_len + 6], prompt_len)
    assert np.abs(got - want[prompt_len - 1:prompt_len + 6]).max() < F32_TOL


def test_a_reused_slot_starts_from_zero(tiny):
    """A sequence ends, its slot is handed to the next: the second
    sequence's logits are the reference's from position 0 (a stale tail:
    0.05 at this size), through the decode program too (a prompt of ONE
    token is a decode-shaped launch at position 0)."""
    hf, cfg, params, tokens, want = tiny
    other = np.random.default_rng(11).integers(0, VOCAB, 40)
    full = ref.logits_of(ref.Widths.from_hf(hf), params, other, CPU)
    eng = engine(cfg, params, max_sequences=1)
    with jax.default_matmul_precision("highest"):
        _walk(eng, tokens[:140], 135, uid=0)
        eng.flush(0)
        got = _walk(eng, other, 1, uid=1)
    assert np.abs(got - full).max() < F32_TOL


def test_bf16_serving(tiny):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, dtype="bfloat16")
    assert eng.arena["conv1"].dtype == jnp.bfloat16
    got = _walk(eng, tokens[:140], 130)
    assert np.abs(got - want[129:140]).max() < BF16_TOL


def test_rows_of_both_forms_in_one_launch(tiny):
    """Four sequences at once, 4-row programs at capacities 64 / 128: a
    prompt of 300 arrives while three sequences decode, a step at a time;
    its later chunks ride GROUPED split steps — one row in the chunk form
    from the tail the earlier launches left, three rows of one query
    stepping their tails, the pools carried through the capacity switch.
    The long prompt's last logits and every decode row's are the
    reference's; the counters count the new kind as they count kinds 3 and
    4: ``state_rows`` grows by the launch's rows x 1."""
    from deepspeed_tpu.telemetry.registry import registry
    hf, cfg, params, tokens, want = tiny
    w = ref.Widths.from_hf(hf)
    rng = np.random.default_rng(9)
    seqs = {u: rng.integers(0, VOCAB, 40 + 3 * u) for u in range(1, 4)}
    eng = engine(cfg, params, max_sequences=4, max_batch_tokens=128)
    _poison(eng)
    count = {name: registry.counter("dispatch/" + name) for name in (
        "steps.split", "split_grouped_steps", "state_rows", "state_resets",
        "ssm_chunk_tokens", "host_calls")}
    before = {name: c.value for name, c in count.items()}
    got = {u: [] for u in seqs}
    with jax.default_matmul_precision("highest"):
        eng.put(list(seqs), [list(s[:-8]) for s in seqs.values()])
        eng._put_validated([0], [list(tokens[:300])])
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
    # the prompt's 300 tokens ride five launches: 125 beside three rows of
    # one query fill the 128 slots of the row form, then 53 a step in the
    # grouped instance at 64 slots (one chunk row)
    assert moved["steps.split"] == 5 and \
        moved["split_grouped_steps"] == 4 and moved["state_resets"] == 4
    assert moved["state_rows"] == 3 + 8 * 3 + 5 and \
        moved["ssm_chunk_tokens"] > 300
    assert np.abs(long_logits - want[299]).max() < F32_TOL
    for u, s in seqs.items():
        full = ref.logits_of(w, params, s, CPU)
        assert np.abs(np.stack(got[u]) - full[-8:]).max() < F32_TOL


def test_the_new_scopes_are_in_the_programs(tiny):
    """``conv_mixer`` and ``conv_state`` are vocabulary words, and
    ``compile_monitor.scopes`` finds them in the split and the decode
    programs."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import explain
    assert {"conv_mixer", "conv_state"} <= set(explain.SCOPE_VOCABULARY)
    _, cfg, params, _, _ = tiny
    eng = engine(cfg, params, max_sequences=4)
    for cb, fresh in ((128, "split"), (1, False)):
        name = eng._step_fn(4, cb, None, fresh).__name__
        found = {e["scope"] for e in
                 telemetry.compile_monitor.scopes(name).values()}
        assert {"conv_mixer", "conv_state", "moe", "mlp",
                "attn_qkv"} <= found, (name, found)
        assert not {s for s in found if s and s.startswith("ssm_")}


# -- 64-wide heads through the paged readers --------------------------------------

def _paged_case(seed, n, c, starts, h=8, kvh=4, d=64, bs=16, mb=6):
    """Random pools ``[pages, bs, kvh·64]`` (a token's heads side by side,
    unpadded), a shuffled page table, queries ``[n, c, h, 64]``."""
    rng = np.random.default_rng(seed)
    pages = n * mb
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(pages + 1, bs, kvh * d)),
                                  jnp.float32) for _ in range(2))
    table = jnp.asarray(rng.permutation(pages).reshape(n, mb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(n, c, h, d)), jnp.float32)
    return q, k_pool, v_pool, table, jnp.asarray(starts, jnp.int32)


def _dense(q, k_pool, v_pool, table, row, upto, qpos=None):
    """Plain attention of ONE row's queries over its first ``upto`` cached
    tokens (``qpos``: causal positions of the queries, else all visible)
    → (out [c, h, d], lse [c, h])."""
    kvh = k_pool.shape[-1] // q.shape[-1]
    keys = np.asarray(k_pool)[np.asarray(table[row])].reshape(
        -1, kvh, q.shape[-1])[:upto]
    vals = np.asarray(v_pool)[np.asarray(table[row])].reshape(
        -1, kvh, q.shape[-1])[:upto]
    rep = q.shape[2] // kvh
    s = np.einsum("chd,khd->chk", np.asarray(q[row]),
                  np.repeat(keys, rep, 1)) / 8.0
    if qpos is not None:
        s = np.where(np.arange(upto)[None, None] <= qpos[:, None, None], s,
                     -np.inf)
    m = s.max(-1, keepdims=True)
    p = np.exp(s - m)
    lse = (m + np.log(p.sum(-1, keepdims=True)))[..., 0]
    return np.einsum("chk,khd->chd", p / p.sum(-1, keepdims=True),
                     np.repeat(vals, rep, 1)), lse


@pytest.mark.parametrize("c,qcounts", [(1, [1, 1, 1]), (8, [8, 1, 3])])
def test_half_tile_heads_through_the_history_readers(c, qcounts):
    """A split step's history, rows of one query and of a chunk: the Pallas
    kernel (interpret mode) reads two KV heads as one 128-lane tile and the
    XLA reader gathers; both are dense attention over ``[0, start)``."""
    starts = [37, 5, 80]
    q, k_pool, v_pool, table, st = _paged_case(1, 3, c, starts)
    assert pa.pairs_heads(64, 64, 4) and not pa.pairs_heads(64, 64, 3) and \
        not pa.pairs_heads(128, 128, 4)
    live = jnp.asarray(qcounts, jnp.int32)
    kernel = pa.paged_attention_with_lse(
        q, k_pool, v_pool, table, st, jnp.zeros_like(st), interpret=True,
        scale=0.125, qcounts=live)
    xla = pa.paged_history_with_lse(q, k_pool, v_pool, table, st, live,
                                    kernel=False, scale=0.125)
    for row, start in enumerate(starts):
        out, lse = _dense(q, k_pool, v_pool, table, row, start)
        n_live = qcounts[row]
        for got in (kernel, xla):
            assert np.abs(np.asarray(got[0][row, :n_live]) -
                          out[:n_live]).max() < 2e-5
            assert np.abs(np.asarray(got[1][row, :n_live]) -
                          lse[:n_live]).max() < 2e-5


def test_half_tile_heads_through_the_decode_readers():
    """A decode step's read (the key just written included): the Pallas
    kernel (interpret mode) and the XLA reader against dense attention."""
    starts = [37, 5, 80]
    q, k_pool, v_pool, table, st = _paged_case(2, 3, 1, starts)
    ones = jnp.ones_like(st)
    kernel = pa.paged_attention(q, k_pool, v_pool, table, st, ones,
                                interpret=True)
    xla = pa.paged_attention_xla(q, k_pool, v_pool, table, st, ones,
                                 scale=0.125)
    for row, start in enumerate(starts):
        out, _ = _dense(q, k_pool, v_pool, table, row, start + 1)
        for got in (kernel, xla):
            assert np.abs(np.asarray(got[row]) - out).max() < 2e-5


def test_the_pairing_is_exact():
    """``_pair_queries`` / ``_unpair_outputs``: a head's dot with its
    pair's 128 lanes is its dot with its own KV head (the zeros add 0.0),
    and it keeps its own half of ``p·V``."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 3, 8, 64)), jnp.float32)
    k = rng.normal(size=(4, 64)).astype(np.float32)    # one token's 4 heads
    paired = np.asarray(pa._pair_queries(q, 4))        # [2, 3, 8, 128]
    for h in range(8):
        kv = h // 2
        lanes = k.reshape(2, 128)[kv // 2]
        assert np.array_equal(paired[..., h, :] @ lanes,
                              np.asarray(q[..., h, :]) @ k[kv])
    out = jnp.asarray(rng.normal(size=(2, 3, 8, 128)), jnp.float32)
    kept = np.asarray(pa._unpair_outputs(out, 4))
    for h in range(8):
        half = (h // 2) % 2
        assert np.array_equal(kept[..., h, :], np.asarray(
            out[..., h, half * 64:(half + 1) * 64]))


def test_the_engine_pairs_the_heads_only_where_it_can(tiny, monkeypatch):
    """On a TPU backend the 64-wide stack takes the kernels with its pools
    UNPADDED; a head the kernel cannot pair or tile keeps the XLA readers;
    a stack of 128-wide heads is as it was."""
    from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceConfig,
                                                   _paged_reader)
    _, cfg, _, _, _ = tiny
    conf = RaggedInferenceConfig(**dict(ENGINE, block_size=128))
    assert _paged_reader(cfg, conf) == (False, 64)          # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _paged_reader(cfg, conf) == (True, 64)
    odd = dataclasses.replace(cfg, num_heads=3, num_kv_heads=3,
                              head_dim_override=64)
    assert _paged_reader(odd, conf) == (False, 64)
    wide = dataclasses.replace(cfg, head_dim_override=128)
    assert _paged_reader(wide, conf) == (True, 128)
    padded = dataclasses.replace(cfg, head_dim_override=192, v_head_dim=128)
    assert _paged_reader(padded, conf) == (True, 256)
