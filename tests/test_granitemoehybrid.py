"""GraniteMoeHybrid's stack (Granite 4.0-H Small; ``hf_loader``:
``granitemoehybrid``) on the typed stack: EVERY layer a mixer (a Mamba-2
state-space mixer with one group, or attention with no positional term and
a stated softmax scale) AND top-k small experts beside a shared one, under
two norms; four scalar multipliers; a softmax over the kept router logits;
a tied head — the program against the benchmark's plain float32 reference
(``benchmark/reference/granitemoehybrid_decoder.py``: the per-token
recurrence) on seeded random weights at a small size, with controls that
are wrong in one way each and must not pass.

Tolerances (largest |logit difference|; the logits are DIVIDED by 16, of
spread 0.01 at this size): ``F32_TOL`` 2e-7 — both sides float32 at
``highest`` precision, readings 1.5e-8 to 4e-8 (the chunk form against the
recurrence; chunks, pages, the merge; ISSUE 45 asks 1e-4 or better); every
control reads above 50x that (the least: a softmax over all 12 experts,
2.5e-5). ``BF16_TOL`` 0.01 — bf16 weights, stream inputs,
cache and convolution tails (the STATE stays float32) against the float32
reference."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granitemoehybrid_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 2e-7
BF16_TOL = 0.01
CPU = jax.devices("cpu")[0]
VOCAB = 96


def published() -> dict:
    """The source's ``config.json`` (the catalog row, letter for letter)."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "granite-4.0-h-small.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: both kinds of layer, 8 query
    heads on 2 KV heads of 6 (``attention_multiplier`` 1/12: NOT 1/√6),
    8 state-space heads in ONE group, a router of 12 with experts 3-8
    held, 4 a token."""
    hf = published()
    hf.update(hidden_size=48, num_hidden_layers=4,
              layer_types=["mamba", "mamba", "attention", "mamba"],
              num_attention_heads=8, num_key_value_heads=2,
              attention_multiplier=1.0 / 12, vocab_size=VOCAB,
              mamba_n_heads=12, mamba_d_head=8, mamba_d_state=16,
              intermediate_size=24, shared_intermediate_size=40,
              num_local_experts=12, num_experts_per_tok=4,
              expert_share={"first_expert": 3, "held_experts": 6})
    hf.update(over)
    return hf


def randomised(params, seed: int = 5):
    """What the tiny size makes vacuous, made to count: the skip ``D``
    (ones at init) drawn; the queries x 40 and the router x 10, so that
    the attention scores and the router logits have a spread of order 1 as
    at the published widths (at hidden 48 the init's 0.02 leaves both
    softmaxes flat, and a wrong scale or a wrong gate would not show);
    the attention's and the experts' output matrices x 8."""
    rng = np.random.default_rng(seed)
    grown = {"ssm": {"D": lambda a: jnp.asarray(
                 rng.uniform(0.5, 1.5, a.shape), jnp.float32)},
             "attn": {"wq": lambda a: a * 40, "wo": lambda a: a * 8},
             "moe": {"router": lambda a: a * 10, "wo": lambda a: a * 8}}
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


# -- the reader ---------------------------------------------------------------

def test_reader_builds_the_published_config():
    cfg = config_from_hf(published())
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size) == (4096, 40, 32, 8, 128, 100352)
    kinds = cfg.layer_kinds
    assert (kinds.count(3), kinds.count(0)) == (36, 4) and \
        [l for l, k in enumerate(kinds) if k == 0] == [5, 15, 25, 35]
    assert cfg.layer_sparse == (1,) * 40
    assert cfg.recurrent and not cfg.full_attn_rope and \
        cfg.kind_rope_theta(0) is None and cfg.rope_theta == 10000.0
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel) == (128, 64, 1, 128, 4)
    assert cfg.ssm_inner == 8192 and cfg.ssm_conv_dim == 8448
    assert (cfg.num_experts, cfg.num_held_experts, cfg.num_experts_per_tok,
            cfg.intermediate_size, cfg.shared_expert_size) == \
        (72, 72, 10, 768, 1536)
    assert cfg.router_scoring == "softmax" and cfg.norm_topk_prob and \
        not cfg.router_select_bias and cfg.routed_scale == 1.0
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == \
        (12.0, 0.22, 16.0, 0.0078125)
    assert cfg.attn_scale == 1.0 / 128 != 128 ** -0.5
    assert cfg.activation == "silu_glu" and cfg.norm == "rmsnorm" and \
        cfg.norm_eps == 1e-5 and cfg.tie_embeddings


def test_reader_holds_every_key_the_harness_checks():
    from benchmark.lib import model as model_lib
    hf = published()
    cfg = config_from_hf(hf)
    held = [key for key in model_lib.BUILT_AS if key in hf]
    assert set(held) >= {"hidden_size", "intermediate_size",
                         "num_attention_heads", "num_key_value_heads",
                         "num_hidden_layers", "vocab_size", "rms_norm_eps",
                         "rope_theta", "num_local_experts",
                         "num_experts_per_tok"}
    for key in held:
        assert getattr(cfg, model_lib.BUILT_AS[key]) == hf[key], key


def test_reader_builds_the_cut_file_and_its_share():
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("granite-4.0-h-small-l10-e36-serve")
    cfg = model_lib.build_model(conf)
    assert cfg.layer_kinds == (3, 3, 3, 3, 3, 0, 3, 3, 3, 3)
    assert cfg.num_experts == 72 and cfg.experts_held == (0, 36) and \
        cfg.vocab_size == 50176
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    assert "lm_head" not in shapes
    m, a = shapes["layers"][0], shapes["layers"][5]
    assert set(m) == {"ln1", "ssm", "ln2", "moe", "shared"} and \
        set(a) == {"ln1", "attn", "ln2", "moe", "shared"}
    assert m["ssm"]["w_in"].shape == (4096, 16768) and \
        m["ssm"]["conv_w"].shape == (8448, 4) and \
        m["ssm"]["w_out"].shape == (8192, 4096)
    assert set(m["moe"]) == {"router", "wg", "wi", "wo"} and \
        m["moe"]["router"].shape == (4096, 72) and \
        m["moe"]["wg"].shape == (36, 4096, 768) and \
        m["shared"]["wo"].shape == (1536, 4096) and \
        a["attn"]["wk"].shape == (4096, 1024)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count - 4_757e6) < 1e6          # ISSUE 45's 4,757M
    w = ref.Widths.from_hf(model_lib.published_keys(conf))
    # a token multiplies 10 of 72 experts (half of them here), the shared
    # expert and the router in EVERY layer
    per_layer = 4096 * 72 + 3 * 4096 * 1536 + round(
        10 * 36 / 72 * 3 * 4096 * 768)
    mamba = 4096 * 16768 + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert ref.matmul_params_per_token(w) == \
        9 * mamba + attn + 10 * per_layer + 4096 * 50176


@pytest.mark.parametrize("key,value,name", [
    ("layer_types", ["mamba", "mlp", "attention", "mamba"],
     "layer type 'mlp'"),
    ("layer_types", ["mamba"], "layer_types has 1"),
    ("position_embedding_type", "rope", "position_embedding_type"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("mamba_conv_bias", False, "mamba_conv_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("normalization_function", "layernorm", "normalization_function"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("shared_intermediate_size", 0, "shared_intermediate_size"),
    ("mamba_d_head", 16, "mamba_expand"),
    ("expert_share", {"router_experts": 24, "first_expert": 0,
                      "held_experts": 6}, "expert_share.router_experts"),
])
def test_reader_refuses_by_name_what_is_not_built(key, value, name):
    with pytest.raises(ValueError, match="granitemoehybrid.*" + name):
        config_from_hf(small(**{key: value}))


def test_more_than_one_group_is_built():
    """``mamba_n_groups`` > 1 is not refused: the mixer's code is the
    grouped one (Nemotron-H's), and the reference takes groups too."""
    hf = small(mamba_n_groups=2)
    cfg = config_from_hf(hf)
    params = randomised(tf.init_params(cfg, jax.random.PRNGKey(7),
                                       jnp.float32))
    tokens = np.random.default_rng(3).integers(0, VOCAB, 40)
    want = ref.logits_of(ref.Widths.from_hf(hf), params, tokens, CPU)
    assert np.abs(uncached(cfg, params, tokens) - want).max() < F32_TOL


# -- the router ---------------------------------------------------------------

def _numpy_gate(logits, k):
    """Ten lines of numpy: the k largest logits (ties: the lower index
    first, as ``lax.top_k``), the softmax over those alone."""
    order = np.argsort(-logits, axis=-1, kind="stable")[:, :k]
    kept = np.take_along_axis(logits, order, axis=-1)
    e = np.exp(kept - kept.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True), order


def test_softmax_over_the_kept_logits_is_the_numpy_gate():
    cfg = config_from_hf(small())
    rng = np.random.default_rng(0)
    logits = rng.normal(0, 1.3, (50, 12)).astype(np.float32)
    logits[0, [2, 7]] = logits[0].max() + 1.0       # a tie at the top
    logits[1, :] = 0.25                             # all tied
    logits[2, [4, 9]] = np.sort(logits[2])[-4]      # a tie at the boundary
    # one-hot inputs: the router's logits are the matrix's rows
    p = {"router": jnp.asarray(logits)}
    topw, topi = moe.route_tokens(cfg, p, jnp.eye(50, dtype=jnp.float32))
    want_w, want_i = _numpy_gate(logits, 4)
    assert np.array_equal(np.asarray(topi), want_i)
    np.testing.assert_allclose(np.asarray(topw), want_w, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(topw).sum(-1), 1.0, rtol=1e-6)
    # the softmax over ALL experts, not renormalised, is another gate
    allw, alli = moe.route_tokens(
        dataclasses.replace(cfg, norm_topk_prob=False), p,
        jnp.eye(50, dtype=jnp.float32))
    full = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    assert np.array_equal(np.asarray(alli), want_i)
    np.testing.assert_allclose(
        np.asarray(allw), np.take_along_axis(full, want_i, -1), rtol=2e-6)
    assert np.abs(np.asarray(allw) - want_w).max() > 0.05


def test_the_share_drops_the_kept_experts_it_does_not_hold():
    """``experts_held`` (3, 6): a token's kept experts outside 3..8 add
    nothing, and the gate of those inside is still the softmax over ALL
    its kept logits (not renormalised over the held ones)."""
    hf = small()
    cfg = config_from_hf(hf)
    w = ref.Widths.from_hf(hf)
    lp = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)[
        "layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 48), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = moe.held_experts_moe_layer(cfg, lp["moe"], x)[0][0]
        want = ref.experts_part(x[0], lp["moe"], w)
        gate, sel = ref.route(x[0], lp["moe"], w)
    assert float(jnp.abs(got - want).max()) < 1e-6
    outside = (np.asarray(sel) < 3) | (np.asarray(sel) >= 9)
    assert outside.any() and not outside.all()
    np.testing.assert_allclose(np.asarray(gate).sum(-1), 1.0, rtol=1e-6)


def test_route_tokens_names_the_scorings_it_builds():
    cfg = dataclasses.replace(config_from_hf(small()),
                              router_scoring="tanh")
    with pytest.raises(NotImplementedError,
                       match="'sigmoid' and 'softmax'.*'tanh'"):
        moe.route_tokens(cfg, {"router": jnp.zeros((48, 12))},
                         jnp.zeros((2, 48)))


# -- the equations ------------------------------------------------------------

def test_uncached_forward_is_the_reference(tiny):
    """320 tokens: three steps of the uncached scan, the state and the
    convolution's tail carried between them."""
    _, cfg, params, tokens, want = tiny
    assert np.abs(uncached(cfg, params, tokens) - want).max() < F32_TOL


def _in_layers(params, part, **leaves):
    return dict(params, layers=[
        dict(lp, **{part: dict(lp[part], **{
            k: f(lp[part][k]) for k, f in leaves.items()})})
        if part in lp else lp for lp in params["layers"]])


CONTROLS = {
    "residual_multiplier_dropped": lambda cfg, p: (
        dataclasses.replace(cfg, residual_multiplier=1.0), p),
    "embedding_multiplier_dropped": lambda cfg, p: (
        dataclasses.replace(cfg, embedding_multiplier=1.0), p),
    "logits_scaling_dropped": lambda cfg, p: (
        dataclasses.replace(cfg, logits_scaling=1.0), p),
    "scores_over_root_head_size": lambda cfg, p: (
        dataclasses.replace(cfg, attention_multiplier=None), p),
    "softmax_over_all_experts": lambda cfg, p: (
        dataclasses.replace(cfg, norm_topk_prob=False), p),
    "skip_term_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", D=jnp.zeros_like)),
    "convolution_bias_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", conv_b=jnp.zeros_like)),
    "shared_expert_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "shared", wo=jnp.zeros_like)),
    "second_norm_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ln2", scale=lambda s: s * 1.5)),
    "bf16_weights": lambda cfg, p: (cfg, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_program_wrong_in_one_way_is_caught(name, tiny):
    _, cfg, params, tokens, want = tiny
    wrong_cfg, wrong_params = CONTROLS[name](cfg, params)
    diff = np.abs(uncached(wrong_cfg, wrong_params, tokens) - want).max()
    assert diff > 50 * F32_TOL, diff


def test_attention_has_no_positional_term():
    """Shift every position by 5: a stack of attention layers cannot
    tell."""
    cfg = config_from_hf(small(num_hidden_layers=2,
                               layer_types=["attention"] * 2))
    params = tf.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, VOCAB, (1, 20)))
    at = jnp.arange(20)[None]
    a = tl.forward_hidden_typed(cfg, params, tokens, positions=at)
    b = tl.forward_hidden_typed(cfg, params, tokens, positions=at * 3 + 5)
    assert float(jnp.abs(a - b).max()) == 0.0


def test_a_multiplier_of_one_adds_no_operation(tiny):
    """The four scalars at 1 / None leave the older stacks' programs as
    they were: no multiplication by a constant in the lowered text."""
    _, cfg, params, tokens, _ = tiny
    plain = dataclasses.replace(
        cfg, embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0, attention_multiplier=None)

    def text(c):
        return jax.jit(lambda p, t: tf.forward(c, p, t)).lower(
            params, jnp.asarray(tokens[:16])[None]).as_text()

    scaled, base = text(cfg), text(plain)
    for constant in ("2.200000e-01", "1.200000e+01", "1.600000e+01"):
        assert constant in scaled and constant not in base, constant


# -- the engine ---------------------------------------------------------------

def _walk(eng, tokens, prompt_len, uid=0):
    """Prefill ``tokens[:prompt_len]`` (chunks of 128), then feed the rest
    a token a step: the logits that predicted each position from the
    prompt's last on."""
    out = eng.put([uid], [list(tokens[:prompt_len])])
    rows = [np.asarray(out[uid], np.float32)]
    for t in tokens[prompt_len:]:
        rows.append(np.asarray(eng.put([uid], [[int(t)]])[uid], np.float32))
    return np.stack(rows)


@pytest.mark.parametrize("prompt_len", [1, 127, 128, 129, 300])
def test_prefill_then_decode_is_the_reference(prompt_len, tiny):
    """A state-space layer WITH a feed-forward part through the fresh, the
    split and the decode programs: the state across chunk edges (127 / 128
    / 129) and across launches (300: a fresh chunk and two split ones),
    then six decode steps through the pools and the pages."""
    _, cfg, params, tokens, want = tiny
    with jax.default_matmul_precision("highest"):
        got = _walk(engine(cfg, params), tokens[:prompt_len + 6], prompt_len)
    assert np.abs(got - want[prompt_len - 1:prompt_len + 6]).max() < F32_TOL


def test_bf16_serving_keeps_a_float32_state(tiny):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, dtype="bfloat16")
    # a pool a state-space layer: a slot a sequence, and the trash
    for i in range(3):
        assert eng.arena[f"ssm{i}"].dtype == jnp.float32 and \
            eng.arena[f"conv{i}"].dtype == jnp.bfloat16
        assert eng.arena[f"ssm{i}"].shape == (9, 12, 8, 16) and \
            eng.arena[f"conv{i}"].shape == (9, 3 * 128)
    assert "ssm3" not in eng.arena and "ssm" not in eng.arena
    got = _walk(eng, tokens[:140], 130)
    assert np.abs(got - want[129:140]).max() < BF16_TOL


def test_rows_of_both_forms_in_one_launch(tiny):
    """Four sequences at once, 4-row programs at capacities 64 / 128: a
    prompt of 300 arrives while three sequences decode, a step at a time;
    its later chunks ride GROUPED split steps — one row in the chunk form
    from the state the earlier launches left, three rows of one query
    stepping the recurrence, the state pools carried through the capacity
    switch, every row's second part (norm, router, experts, shared expert)
    on the packed tokens. The long prompt's last logits and every decode
    row's are the reference's."""
    from deepspeed_tpu.telemetry.registry import registry
    hf, cfg, params, tokens, want = tiny
    w = ref.Widths.from_hf(hf)
    rng = np.random.default_rng(9)
    seqs = {u: rng.integers(0, VOCAB, 40 + 3 * u) for u in range(1, 4)}
    eng = engine(cfg, params, max_sequences=4, max_batch_tokens=128)
    count = {name: registry.counter("dispatch/" + name) for name in (
        "steps.split", "split_grouped_steps", "state_resets",
        "moe_assignments", "tokens")}
    before = {name: c.value for name, c in count.items()}
    got = {u: [] for u in seqs}
    with jax.default_matmul_precision("highest"):
        eng.put(list(seqs), [list(s[:-8]) for s in seqs.values()])
        eng._put_validated([0], [list(tokens[:300])])
        for step in range(8):
            eng._put_validated(list(seqs), [[int(s[len(s) - 8 + step])]
                                            for s in seqs.values()])
            out = eng.step_with_budget(mode=None,
                                       budget=None if step == 0 else 40)
            for u in seqs:
                got[u].append(np.asarray(out[u], np.float32))
            if 0 in out:
                long_logits = np.asarray(out[0], np.float32)
    moved = {name: c.value - before[name] for name, c in count.items()}
    assert moved["steps.split"] == 6 and \
        moved["split_grouped_steps"] == 5 and moved["state_resets"] == 4
    # fed tokens x 4 experts a token x 4 sparse layers
    assert moved["moe_assignments"] == moved["tokens"] * 16 > 0
    assert np.abs(long_logits - want[299]).max() < F32_TOL
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


def _state_in_bf16(monkeypatch):
    step, chunk = ssm.scan_step, ssm.scan_chunk

    def rounded(scan):
        def wrapped(*args):
            y, s = scan(*args)
            # (``reduce_precision``: a pair of converts is folded away)
            return y, jax.lax.reduce_precision(s, 8, 7)
        return wrapped

    monkeypatch.setattr(ssm, "scan_step", rounded(step))
    monkeypatch.setattr(ssm, "scan_chunk", rounded(chunk))


def _stale_slots(monkeypatch):
    """The program never zeroes a row that starts at position 0."""
    monkeypatch.setattr(ssm, "fresh_rows",
                        lambda starts: jnp.zeros(starts.shape, bool))


@pytest.mark.parametrize("control", [_state_in_bf16, _stale_slots])
def test_the_engine_wrong_in_one_way_is_caught(control, tiny, monkeypatch):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, max_sequences=1)
    with jax.default_matmul_precision("highest"):
        _walk(eng, np.random.default_rng(4).integers(0, VOCAB, 150), 140)
        eng.flush(0)
        eng._step_fns.clear()
        control(monkeypatch)
        got = _walk(eng, tokens[:140], 130, uid=1)
    # (the state rounded to bf16 reads 1.4e-6 at this size, where 0.22 and
    # the head's 1/16 shrink it: 47x the sound engine's 3e-8)
    assert np.abs(got - want[129:140]).max() > 5 * F32_TOL


def test_generate_serves_it_running_ahead(tiny):
    from deepspeed_tpu.telemetry.registry import registry
    _, cfg, params, tokens, _ = tiny
    eng = engine(cfg, params)
    ahead = registry.counter("dispatch/launches_ahead")
    before = ahead.value
    (out,) = eng.generate([tokens[:140].tolist()], max_new_tokens=6)
    assert len(out) == 146 and ahead.value > before
    assert not eng.state.seqs and len(eng.state._slots) == 8
    logits = uncached(cfg, params, out[:-1])
    assert out[140:].tolist() == logits[139:].argmax(-1).tolist()


def test_generate_is_the_stepwise_greedy_run_with_an_eos_inside(tiny):
    from tests.test_paged import generate_against_stepwise_with_an_eos
    _, cfg, params, tokens, _ = tiny
    generate_against_stepwise_with_an_eos(
        lambda: engine(cfg, params),
        [tokens[a:b].tolist() for a, b in ((0, 70), (70, 75), (80, 113))],
        9)


def test_the_frontend_serves_it_through_the_run_ahead_pump(tiny):
    from deepspeed_tpu.serving import ServingFrontend
    _, cfg, params, tokens, _ = tiny
    fe = ServingFrontend(engine(cfg, params))
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


#: a plain stack, with no experts
DENSE = dict(hidden_size=32, num_layers=1, num_heads=2, intermediate_size=64,
             vocab_size=VOCAB)


def _launched(cfg, program, n, nb, chunk, slots=None):
    """The work of one launch, counted: ``n`` rows of one token each in
    a ``nb x chunk`` program that ran over ``slots`` token slots (None: the
    row form's)."""
    from deepspeed_tpu.inference import launch_work
    form = launch_work.Form(nb, (), slots or nb * chunk, nb, nb * chunk)
    work = launch_work.launch_work(
        launch_work.Site(cfg, 8, 32, False), program, form, chunk,
        np.full(n, 29, np.int32), np.ones(n, np.int32))
    launch_work.count_launch(work)
    return work


def test_dispatch_counts_the_experts_assignments(tiny):
    _, cfg, _, _, _ = tiny
    work = _launched(cfg, "decode", 3, 4, 1)
    assert work["moe_assignments"] == 3 * 4 * 4
    assert "moe_assignments" not in _launched(
        tf.DecoderConfig(**DENSE), "decode", 3, 4, 1)


@pytest.mark.parametrize("launch, rows", [
    # (program, rows, row bucket, chunk) + token_slots -> buffer rows
    (("decode", 3, 4, 1, None), 0),
    (("split", 3, 4, 128, 128), 0),           # 128 slots: the few-token path
    (("split", 3, 4, 128, 512), 6 * 128 * 4),
    (("split", 3, 8, 128, None), 6 * 128 * 4),     # the row form: 8 x 128
    (("fresh", 2, 4, 128, 256), 6 * 128 * 4)])
def test_dispatch_counts_the_buffer_rows_of_a_many_token_launch(
        tiny, launch, rows):
    """``dispatch/moe_buffer_rows``: 6 held experts x ``HELD_ROUND_ROWS`` x
    4 sparse layers for a launch whose token-wise sublayers run over more
    than ``HELD_ROUND_ROWS`` slots (the many-token dispatch's first
    round), 0 for a decode launch and a split launch of 128 slots."""
    from deepspeed_tpu.parallel.moe import HELD_ROUND_ROWS
    from deepspeed_tpu.telemetry.registry import registry
    assert HELD_ROUND_ROWS == 128
    _, cfg, _, _, _ = tiny
    counter = registry.counter("dispatch/moe_buffer_rows")
    before = counter.value
    work = _launched(cfg, *launch)
    assert work["moe_buffer_rows"] == rows == counter.value - before


def test_a_stack_without_experts_counts_no_buffer_rows():
    from deepspeed_tpu.telemetry.registry import registry
    counter = registry.counter("dispatch/moe_buffer_rows")
    before = counter.value
    work = _launched(tf.DecoderConfig(**DENSE), "split", 3, 8, 128, 512)
    assert "moe_buffer_rows" not in work and counter.value == before


# -- the share ----------------------------------------------------------------

def test_two_shares_add_up_to_the_uncut_layer():
    """Two chips hold six of the router's 12 experts each. Their routed
    parts (the program's ``held_experts_moe_layer`` on each share's slice
    of the weights), with the shared expert and the mixer counted ONCE,
    add up to the reference's uncut layer — each branch sum times the
    residual multiplier."""
    whole_hf = small(expert_share=None, num_hidden_layers=1,
                     layer_types=["mamba"])
    cfg = config_from_hf(whole_hf)
    w = ref.Widths.from_hf(whole_hf)
    assert w.held_experts == 12 and cfg.experts_held is None
    lp = randomised(tf.init_params(cfg, jax.random.PRNGKey(11),
                                   jnp.float32))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(12), (256, 48), jnp.float32)
    r = w.residual_multiplier
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._layer(x, lp, w, "mamba")
        hin = ref.dense._rms_norm(x, lp["ln1"]["scale"], w.eps)
        x1 = x + r * ref.hybrid.mamba_mixer(w, lp["ssm"], hin)  # ONCE
        h2 = ref.dense._rms_norm(x1, lp["ln2"]["scale"], w.eps)
        routed = jnp.zeros_like(x)
        for chip in range(2):
            hf_i = small(expert_share={"first_expert": 6 * chip,
                                       "held_experts": 6},
                         num_hidden_layers=1, layer_types=["mamba"])
            mine = slice(6 * chip, 6 * chip + 6)
            p_i = dict(lp["moe"], **{k: lp["moe"][k][mine]
                                     for k in ("wg", "wi", "wo")})
            part = moe.held_experts_moe_layer(config_from_hf(hf_i), p_i,
                                              h2[None])[0][0]
            assert float(jnp.abs(part - ref.experts_part(
                h2, p_i, ref.Widths.from_hf(hf_i))).max()) < 1e-5
            assert float(jnp.abs(part).max()) > 1e-3    # each share adds
            routed = routed + part
        sh = lp["shared"]
        shared = ref._glu_unit(h2, sh["wg"], sh["wi"], sh["wo"])
        # the program's shared expert is the same unit
        assert float(jnp.abs(moe._shared_expert(sh, h2) -
                             shared).max()) < 1e-5
    assert float(jnp.abs(x1 + r * (routed + shared) - whole).max()) < 1e-5


# -- no operation of the step programs is unscoped ---------------------------

@pytest.mark.parametrize("cb,fresh", [(128, "split"), (1, False)])
def test_every_heavy_operation_of_the_step_programs_is_scoped(cb, fresh,
                                                              tiny):
    """The split and the decode program of the stack, lowered: every
    ``dot_general`` and every multiplication by one of the four scalars
    lies under a scope the benchmark's readers know."""
    import re
    _, cfg, params, _, _ = tiny
    eng = engine(cfg, params, max_sequences=4, max_batch_tokens=128)
    packed = jnp.zeros((eng._packed_len(4, cb),), jnp.int32)
    lowered = eng._step_fn(4, cb, ("argmax",), fresh).lower(
        eng.params, eng.arena, packed, eng._rng_dev)
    text = lowered.as_text(debug_info=True)
    known = ("embed", "lm_head", "sample", "norm", "attn_qkv", "attn_core",
             "attn_history", "attn_merge", "attn_out", "kv_write", "moe",
             "moe_router", "moe_experts", "moe_shared", "ssm_in",
             "ssm_conv", "ssm_scan", "ssm_state", "ssm_norm", "ssm_out")
    locs = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))

    def scope_of(loc):
        seen = set()
        while loc in locs and loc not in seen:
            seen.add(loc)
            body = locs[loc]
            for name in known:
                if f"/{name}/" in body or f"({name})" in body or \
                        f"/{name}\"" in body:
                    return name
            inner = re.findall(r'#loc\d+', body)
            if not inner:
                return None
            loc = inner[-1]
        return None

    dots = re.findall(r'stablehlo\.dot_general.*?loc\((#loc\d+)\)', text)
    assert len(dots) > 20
    unscoped = [d for d in dots if scope_of(d) is None]
    assert not unscoped, [locs[d] for d in unscoped[:5]]
