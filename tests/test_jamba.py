"""Jamba's stack (AI21 Jamba2-3B; ``hf_loader``: ``jamba``) on the typed
stack: EVERY layer a mixer — a Mamba-1 SELECTIVE-SCAN mixer (kind 4: a step
size a channel through a bottleneck, RMSNorms on the step, ``B`` and ``C``,
a gate and no norm) or 2-over-1 attention with no positional term — AND a
dense SiLU-GLU under two norms, a tied head: the program against the
benchmark's plain float32 reference (``benchmark/reference/
jamba_decoder.py``: the per-token recurrence) on seeded random weights at a
small size, with controls that are wrong in one way each and must not pass.

Tolerances (largest |logit difference|; the logits spread by 0.2 at this
size). ``F32_TOL`` 3e-6 — both sides float32 at ``highest`` precision; the
two differ in the ORDER of float32 sums alone (the program's convolution
adds its bias first, its attention is blocked another way), readings
1.2e-7 to 3.6e-7. Every control reads above 50x that: ``S`` rounded to bf16
between launches 3.9e-4 (the least), bf16 weights 1.6e-3, a norm's scale
dropped 0.012-0.071, a stale slot 0.016, the step's norm dropped 0.028, the
rest 0.08-0.25. ``BF16_TOL`` 0.01 — bf16 weights, stream inputs, cache and
convolution tails (the STATE stays float32) against the float32 reference,
reading 0.0017."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf
from deepspeed_tpu.ops import ssm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 3e-6
BF16_TOL = 0.01
CPU = jax.devices("cpu")[0]
VOCAB = 96


def published() -> dict:
    """The source's ``config.json`` (the catalog row, letter for letter)."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "ai21-jamba2-3b.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: hidden 64 (inner 128), state 8,
    ``dt_rank`` 8, 2 query heads over ONE KV head, layers ``m a m``."""
    hf = published()
    hf.update(hidden_size=64, num_hidden_layers=3, attn_layer_period=2,
              attn_layer_offset=1, num_attention_heads=2,
              num_key_value_heads=1, mamba_d_state=8, mamba_dt_rank=8,
              intermediate_size=96, vocab_size=VOCAB)
    hf.update(over)
    return hf


def randomised(params, seed: int = 5):
    """What the init makes vacuous, made to count: the skip ``D`` and the
    three inner norms' scales (ones at init) drawn; the queries x 20 (at
    hidden 64 the init's 0.02 leaves the softmax flat)."""
    rng = np.random.default_rng(seed)
    draw = lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
    scale = lambda n: {"scale": draw(n["scale"])}
    grown = {"ssm": {"D": draw, "dt_norm": scale, "b_norm": scale,
                     "c_norm": scale},
             "attn": {"wq": lambda a: a * 20}}
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


# -- (d) the reader -------------------------------------------------------------

def test_reader_builds_the_published_config():
    cfg = config_from_hf(published())
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.intermediate_size) == \
        (2560, 28, 20, 1, 128, 65536, 8192)
    kinds = cfg.layer_kinds
    assert (kinds.count(4), kinds.count(0)) == (26, 2) and \
        [l for l, k in enumerate(kinds) if k == 0] == [7, 21]
    assert cfg.layer_sparse == (0,) * 28 and not any(
        cfg.layer_is_sparse(l) for l in range(28))
    assert cfg.recurrent and cfg.selective and not cfg.full_attn_rope and \
        cfg.kind_rope_theta(0) is None and cfg.kind_rope_theta(4) is None
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_state_size,
            cfg.ssm_dt_rank, cfg.ssm_conv_kernel) == (5120, 5120, 16, 160, 4)
    assert ssm.state_shape(cfg) == (16, 5120)
    assert cfg.attn_scale == 128 ** -0.5 and cfg.norm_eps == 1e-6 and \
        cfg.activation == "silu_glu" and cfg.norm == "rmsnorm" and \
        cfg.tie_embeddings and cfg.max_seq_len == 262144
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    assert "lm_head" not in shapes
    m, a = shapes["layers"][0], shapes["layers"][7]
    assert set(m) == {"ln1", "ssm", "ln2", "mlp"} and \
        set(a) == {"ln1", "attn", "ln2", "mlp"}
    assert set(m["ssm"]) == {"w_in", "conv_w", "conv_b", "w_x", "dt_norm",
                             "b_norm", "c_norm", "w_dt", "dt_bias", "A_log",
                             "D", "w_out"}
    assert m["ssm"]["w_in"].shape == (2560, 10240) and \
        m["ssm"]["conv_w"].shape == (5120, 4) and \
        m["ssm"]["w_x"].shape == (5120, 192) and \
        m["ssm"]["w_dt"].shape == (160, 5120) and \
        m["ssm"]["A_log"].shape == (16, 5120) and \
        a["attn"]["wq"].shape == (2560, 2560) and \
        a["attn"]["wk"].shape == (2560, 128) and \
        m["mlp"]["wg"].shape == (2560, 8192)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count - 3_029e6) < 1e6          # ISSUE 49's 3,029M
    assert cfg.num_params() == count == cfg.num_active_params()
    # a token multiplies a mamba layer's 41.1M or an attention layer's
    # 13.8M, the MLP's 62.9M in every layer, and the head
    w = ref.Widths.from_hf(published())
    mamba = 3 * 2560 * 5120 + 5120 * 192 + 160 * 5120
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert ref.matmul_params_per_token(w) == 26 * mamba + 2 * attn + \
        28 * 3 * 2560 * 8192 + 2560 * 65536


def test_reader_holds_every_key_the_harness_checks():
    from benchmark.lib import model as model_lib
    hf = published()
    cfg = config_from_hf(hf)
    held = [key for key in model_lib.BUILT_AS if key in hf]
    assert set(held) >= {"hidden_size", "intermediate_size",
                         "num_attention_heads", "num_key_value_heads",
                         "num_hidden_layers", "vocab_size", "rms_norm_eps",
                         "num_experts", "num_experts_per_tok"}
    for key in held:
        assert getattr(cfg, model_lib.BUILT_AS[key]) == hf[key], key


def test_reader_builds_the_file_whole():
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("jamba2-3b-l28-serve")
    assert conf["reduced"] == [] and conf["changed"] == {}
    assert model_lib.build_model(conf) == config_from_hf(published())
    tiny_model = model_lib.build_model(conf, rehearse=True)
    assert tiny_model.layer_kinds == (4, 0, 4) and \
        tiny_model.ssm_inner == 512


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("num_experts_per_tok", 2),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("hidden_act", "gelu"), ("sliding_window", 4096)])
def test_reader_refuses_by_name_what_is_not_built(key, value):
    with pytest.raises(ValueError, match="jamba.*" + key):
        config_from_hf(small(**{key: value}))


def test_one_stack_holds_one_kind_of_scan():
    cfg = config_from_hf(small())
    with pytest.raises(ValueError, match="kind 4.*without layers of kind 3"):
        dataclasses.replace(cfg, layer_kinds=(4, 0, 3), ssm_heads=4,
                            ssm_head_dim=32)
    with pytest.raises(ValueError, match="kind 4.*ssm_dt_rank"):
        dataclasses.replace(cfg, ssm_dt_rank=0)


# -- (a) the equations ----------------------------------------------------------

def test_uncached_forward_is_the_reference(tiny):
    """420 tokens: four steps of the uncached scan, the state and the
    convolution's tail carried between them."""
    _, cfg, params, tokens, want = tiny
    assert np.abs(uncached(cfg, params, tokens) - want).max() < F32_TOL


def _in_layers(params, part, **leaves):
    return dict(params, layers=[
        dict(lp, **{part: dict(lp[part], **{
            k: f(lp[part][k]) for k, f in leaves.items()})})
        if part in lp else lp for lp in params["layers"]])


_ones = lambda n: {"scale": jnp.ones_like(n["scale"])}
CONTROLS = {
    # (a norm whose learned scale is dropped: with the scale AND the
    # normalisation gone B and C would shrink by orders, a cruder fault)
    "step_norm_scale_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", dt_norm=_ones)),
    "b_norm_scale_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", b_norm=_ones)),
    "c_norm_scale_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", c_norm=_ones)),
    "skip_term_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", D=jnp.zeros_like)),
    "convolution_bias_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", conv_b=jnp.zeros_like)),
    "step_bias_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", dt_bias=jnp.zeros_like)),
    "second_norm_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ln2", scale=lambda s: s * 1.5)),
    "scores_unscaled": lambda cfg, p: (
        dataclasses.replace(cfg, attention_multiplier=1.0), p),
    "bf16_weights": lambda cfg, p: (cfg, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_program_wrong_in_one_way_is_caught(name, tiny):
    _, cfg, params, tokens, want = tiny
    wrong_cfg, wrong_params = CONTROLS[name](cfg, params)
    diff = np.abs(uncached(wrong_cfg, wrong_params, tokens) - want).max()
    assert diff > 50 * F32_TOL, diff


def test_an_inner_norm_normalises(tiny, monkeypatch):
    """The three inner norms DIVIDE by the root mean square: a mixer that
    only multiplies by their scales is another model."""
    _, cfg, params, tokens, want = tiny
    monkeypatch.setattr(ssm, "_rms", lambda x, scale, eps:
                        x.astype(jnp.float32) * scale)
    assert np.abs(uncached(cfg, params, tokens) - want).max() > 0.01


# -- (c) two forms of one scan ----------------------------------------------------

def _mixer_inputs(cfg, seed, m, c):
    """Random rows for the scan alone: (u, (Δ, B, C), state), the state NOT
    zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    d, n = cfg.ssm_inner, cfg.ssm_state_size
    u = jax.random.normal(ks[0], (m, c, d), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (m, c, d)) - 2.0)
    b, cc = (jax.random.normal(k, (m, c, n), jnp.float32) for k in ks[2:4])
    state = jax.random.normal(ks[4], (m,) + ssm.state_shape(cfg),
                              jnp.float32)
    return u, (delta, b, cc), state


def test_chunk_form_is_the_recurrence_token_by_token(tiny):
    """From a NON-ZERO carried state, rows of different live lengths: the
    chunk form's outputs and final state are the one-token form applied a
    position at a time."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    counts = jnp.asarray([40, 17, 0], jnp.int32)
    u, (delta, b, c), state = _mixer_inputs(cfg, 1, 3, 40)
    live = jnp.arange(40)[None] < counts[:, None]
    delta = jnp.where(live[..., None], delta, 0.0)
    y, s_out = ssm.selective_chunk(cfg, p, u, (delta, b, c), state, counts)
    s, ys = state, []
    for t in range(40):
        one = jnp.asarray(live[:, t], jnp.int32)
        y_t, s = ssm.selective_step(
            cfg, p, u[:, t:t + 1],
            tuple(a[:, t:t + 1] for a in (delta, b, c)), s, one)
        ys.append(y_t)
    assert float(jnp.abs(s_out - s).max()) < 1e-5
    assert float(jnp.abs(jnp.where(live[..., None], y - jnp.concatenate(
        ys, axis=1), 0.0)).max()) < 1e-5
    # a row with no live position carries its state on, bit for bit
    assert np.array_equal(np.asarray(s_out[2]), np.asarray(state[2]))
    # a reset row starts from zero whatever it held
    _, fresh = ssm.selective_step(cfg, p, u[:, :1], tuple(
        a[:, :1] for a in (delta, b, c)), state, jnp.ones(3, jnp.int32),
        reset=jnp.asarray([True, False, False]))
    _, zero = ssm.selective_step(cfg, p, u[:, :1], tuple(
        a[:, :1] for a in (delta, b, c)), jnp.zeros_like(state),
        jnp.ones(3, jnp.int32))
    assert np.array_equal(np.asarray(fresh[0]), np.asarray(zero[0])) and \
        not np.array_equal(np.asarray(fresh[1]), np.asarray(zero[1]))


@pytest.mark.parametrize("cut", [1, 3, 64, 127, 128, 129, 200, 299])
def test_the_carried_state_is_the_whole_interface(cut, tiny):
    """The mixer over a prompt of 300 cut at ANY boundary — inside the
    convolution's reach, at a chunk's edge, one to either side — gives the
    outputs, the state and the tail of one pass: (state, tail) is all a
    launch hands the next."""
    _, cfg, params, _, _ = tiny
    p = params["layers"][0]["ssm"]
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 300, cfg.hidden_size),
                          jnp.float32)

    def run(h_part, tail, state):
        forms = tl.mixer_forms(4)
        z, x, dt = forms.project(cfg, p, h_part)
        n = h_part.shape[1]
        y, tail, state = tl.ssm_rows(forms, cfg, p, x, dt, tail, state,
                                     jnp.asarray([n], jnp.int32))
        return forms.out(cfg, p, y, z), tail, state

    zero = (jnp.zeros((1, 3, cfg.ssm_inner), jnp.float32),
            jnp.zeros((1,) + ssm.state_shape(cfg), jnp.float32))
    with jax.default_matmul_precision("highest"):
        whole, tail_w, state_w = run(h, *zero)
        first, tail, state = run(h[:, :cut], *zero)
        second, tail, state = run(h[:, cut:], tail, state)
    assert float(jnp.abs(jnp.concatenate([first, second], 1) -
                         whole).max()) < 1e-5
    assert float(jnp.abs(state - state_w).max()) < 1e-5 and \
        np.array_equal(np.asarray(tail), np.asarray(tail_w))


# -- (b) the engine -------------------------------------------------------------

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
    """The selective scan WITH a dense MLP through the fresh, the split and
    the decode programs: the state across chunk edges (127 / 128 / 129) and
    across launches (400: a fresh chunk and three split ones), then six
    decode steps through the pools and the pages. LOGITS, not tokens."""
    _, cfg, params, tokens, want = tiny
    with jax.default_matmul_precision("highest"):
        got = _walk(engine(cfg, params), tokens[:prompt_len + 6], prompt_len)
    assert np.abs(got - want[prompt_len - 1:prompt_len + 6]).max() < F32_TOL


def test_bf16_serving_keeps_a_float32_state(tiny):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, dtype="bfloat16")
    # a pool a selective-scan layer: a slot a sequence, and the trash;
    # [N, d] of state, K - 1 rows of the d channels alone
    for i in range(2):
        assert eng.arena[f"ssm{i}"].dtype == jnp.float32 and \
            eng.arena[f"conv{i}"].dtype == jnp.bfloat16
        assert eng.arena[f"ssm{i}"].shape == (9, 8, 128) and \
            eng.arena[f"conv{i}"].shape == (9, 3 * 128)
    assert "ssm2" not in eng.arena and eng.arena["k"].shape[-1] == 32
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
    kind 3."""
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
    # the prompt's 400 tokens ride seven launches: 125 beside three rows of
    # one query fill the 128 slots of the row form, then 53 a step in the
    # grouped instance at 64 slots (one chunk row)
    assert eng._token_capacities(4, 128, "split") == (64, 128)
    assert moved["steps.split"] == 7 and \
        moved["split_grouped_steps"] == 6 and moved["state_resets"] == 4
    assert moved["state_rows"] == 3 + 8 * 3 + 7 and \
        moved["ssm_chunk_tokens"] > 400
    assert np.abs(long_logits - want[399]).max() < F32_TOL
    for u, s in seqs.items():
        full = ref.logits_of(w, params, s, CPU)
        assert np.abs(np.stack(got[u]) - full[-8:]).max() < F32_TOL


def test_rows_ride_along_with_no_live_query(tiny):
    """A sequence that is given nothing in a step rides in no launch and
    keeps its state: two sequences prefilled, one of them decoded alone
    for four steps, then the other — both read the reference's logits."""
    hf, cfg, params, tokens, want = tiny
    other = np.random.default_rng(11).integers(0, VOCAB, 150)
    full = ref.logits_of(ref.Widths.from_hf(hf), params, other, CPU)
    eng = engine(cfg, params, max_sequences=4)
    with jax.default_matmul_precision("highest"):
        eng.put([0, 1], [list(tokens[:200]), list(other[:140])])
        got0 = [np.asarray(eng.put([0], [[int(t)]])[0], np.float32)
                for t in tokens[200:204]]
        got1 = [np.asarray(eng.put([1], [[int(t)]])[1], np.float32)
                for t in other[140:144]]
    assert np.abs(np.stack(got0) - want[200:204]).max() < F32_TOL
    assert np.abs(np.stack(got1) - full[140:144]).max() < F32_TOL


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


# -- (e) the engine's controls ----------------------------------------------------

def _state_in_bf16(monkeypatch):
    step, chunk = ssm.selective_step, ssm.selective_chunk

    def rounded(scan):
        def wrapped(*args, **kwargs):
            y, s = scan(*args, **kwargs)
            # (``reduce_precision``: a pair of converts is folded away)
            return y, jax.lax.reduce_precision(s, 8, 7)
        return wrapped

    monkeypatch.setattr(ssm, "selective_step", rounded(step))
    monkeypatch.setattr(ssm, "selective_chunk", rounded(chunk))


def _stale_slots(monkeypatch):
    """The program never zeroes a row that starts at position 0."""
    monkeypatch.setattr(ssm, "fresh_rows",
                        lambda starts: jnp.zeros(starts.shape, bool))


def _no_step_norm(monkeypatch):
    """The step's bottleneck goes to ``W_dt`` un-normalised."""
    norms = ssm.select_norms

    def wrong(cfg, p, dbc, dtype):
        _, b, c = norms(cfg, p, dbc, dtype)
        return dbc[..., :cfg.ssm_dt_rank].astype(dtype), b, c

    monkeypatch.setattr(ssm, "select_norms", wrong)


@pytest.mark.parametrize("control", [_state_in_bf16, _stale_slots,
                                     _no_step_norm])
def test_the_engine_wrong_in_one_way_is_caught(control, tiny, monkeypatch):
    """(b) again on an engine that holds ``S`` in bf16 between launches,
    keeps a reused slot's state, or drops an inner norm: each fails (b)'s
    tolerance by 50x."""
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, max_sequences=1)
    with jax.default_matmul_precision("highest"):
        _walk(eng, np.random.default_rng(4).integers(0, VOCAB, 150), 140)
        eng.flush(0)
        eng._step_fns.clear()
        control(monkeypatch)
        got = _walk(eng, tokens[:140], 130, uid=1)
    assert np.abs(got - want[129:140]).max() > 50 * F32_TOL


# -- through the pump -----------------------------------------------------------

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


# -- no operation of the step programs is unscoped -------------------------------

@pytest.mark.parametrize("cb,fresh", [(128, "split"), (1, False)])
def test_every_heavy_operation_of_the_step_programs_is_scoped(cb, fresh,
                                                              tiny):
    """The split and the decode program of the stack, lowered: every
    ``dot_general`` lies under a scope the benchmark's readers know, and
    ``W_x`` / ``W_dt`` under the new ``ssm_select``."""
    import re
    from deepspeed_tpu.telemetry import explain
    _, cfg, params, _, _ = tiny
    assert "ssm_select" in explain.SCOPE_VOCABULARY
    eng = engine(cfg, params, max_sequences=4, max_batch_tokens=128)
    packed = jnp.zeros((eng._packed_len(4, cb),), jnp.int32)
    lowered = eng._step_fn(4, cb, ("argmax",), fresh).lower(
        eng.params, eng.arena, packed, eng._rng_dev)
    text = lowered.as_text(debug_info=True)
    known = ("embed", "lm_head", "sample", "norm", "attn_qkv", "attn_core",
             "attn_history", "attn_merge", "attn_out", "kv_write", "mlp",
             "ssm_in", "ssm_conv", "ssm_select", "ssm_scan", "ssm_state",
             "ssm_norm", "ssm_out")
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
    assert len(dots) > 12
    scopes = [scope_of(d) for d in dots]
    assert None not in scopes, [locs[d] for d, s in zip(dots, scopes)
                                if s is None][:5]
    # two matmuls a selective layer and instance, under the new scope
    assert scopes.count("ssm_select") >= 4
