"""Nemotron-H's hybrid stack (Nemotron 3 Nano; ``hf_loader``:
``nemotron_h``) on the typed stack: Mamba-2 layers whose recurrent state
lives in state pools beside the KV pages, layers that are ONE part (a
mixer or the experts) under one norm, attention with no positional term,
un-gated ``relu²`` experts — the program against the benchmark's plain
float32 reference (``benchmark/reference/nemotron_h_decoder.py``: the
per-token recurrence) on seeded random weights at a small size, with
controls that are wrong in one way each and must not pass.

Tolerances (largest |logit difference|, logits of magnitude ~1):
``F32_TOL`` 5e-6 — both sides float32 at ``highest`` precision, readings
1.5e-7 to 2.1e-7 (the chunk form against the recurrence; chunks, pages, the
merge); every control reads above 50x that (a state rounded to bf16 on
its way to the pool: 4.8e-4). ``BF16_TOL`` 0.05 — bf16
weights, stream inputs, cache and convolution tails (the STATE stays
float32) against the float32 reference."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 5e-6
BF16_TOL = 0.05
CPU = jax.devices("cpu")[0]
VOCAB = 96


def published() -> dict:
    """The source's ``config.json`` (the catalog row, letter for letter)."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "nemotron-3-nano-30b-a3b.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: every kind of layer, 8 query
    heads on 2 KV heads, 8 state-space heads in 2 groups, a router of 16
    with experts 4-11 held."""
    hf = published()
    hf.update(hidden_size=48, num_hidden_layers=6,
              hybrid_override_pattern="MEM*EM", num_attention_heads=8,
              num_key_value_heads=2, head_dim=16, vocab_size=VOCAB,
              mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
              ssm_state_size=16, moe_intermediate_size=32,
              moe_shared_expert_intermediate_size=64, n_routed_experts=8,
              num_experts_per_tok=3,
              expert_share={"router_experts": 16, "first_expert": 4})
    hf.update(over)
    return hf


def randomised(params, seed: int = 5):
    """The vectors that initialise to constants, drawn: a selection bias
    of zeros or a skip of ones would make a test of them vacuous."""
    rng = np.random.default_rng(seed)
    layers = []
    for lp in params["layers"]:
        lp = dict(lp)
        if "ssm" in lp:
            lp["ssm"] = dict(lp["ssm"], D=jnp.asarray(
                rng.uniform(0.5, 1.5, lp["ssm"]["D"].shape), jnp.float32))
        if "moe" in lp:
            lp["moe"] = dict(lp["moe"], router_bias=jnp.asarray(
                rng.normal(0, 0.1, lp["moe"]["router_bias"].shape),
                jnp.float32))
        layers.append(lp)
    return dict(params, layers=layers)


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
            cfg.head_dim, cfg.vocab_size) == (2688, 52, 32, 2, 128, 131072)
    kinds = cfg.layer_kinds
    assert (kinds.count(3), kinds.count(-1), kinds.count(0)) == (23, 23, 6)
    assert all((s == 1) == (k == -1) and s in (1, -1)
               for k, s in zip(kinds, cfg.layer_sparse))
    assert cfg.recurrent and not cfg.full_attn_rope and \
        cfg.kind_rope_theta(0) is None and cfg.rope_theta == 10000.0
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel) == (64, 64, 8, 128, 4)
    assert cfg.ssm_inner == 4096 and cfg.ssm_conv_dim == 6144
    assert (cfg.num_experts, cfg.num_held_experts, cfg.num_experts_per_tok,
            cfg.intermediate_size, cfg.shared_expert_size) == \
        (128, 128, 6, 1856, 3712)
    assert cfg.router_scoring == "sigmoid" and cfg.router_select_bias and \
        cfg.norm_topk_prob and cfg.routed_scale == 2.5 and \
        cfg.router_groups == 1
    assert cfg.activation == "relu2" and cfg.norm == "rmsnorm" and \
        cfg.norm_eps == 1e-5 and not cfg.tie_embeddings


def test_reader_builds_the_cut_file_and_its_share():
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("nemotron3-nano-l26-e16-serve")
    assert "sliding_window" not in conf        # published null: left out
    cfg = model_lib.build_model(conf)
    kinds = cfg.layer_kinds
    assert (kinds.count(3), kinds.count(-1), kinds.count(0)) == (12, 11, 3)
    assert cfg.num_experts == 128 and cfg.experts_held == (0, 16) and \
        cfg.vocab_size == 16384
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    m, e, a = (shapes["layers"][l] for l in (0, 1, 5))
    assert set(m) == {"ln1", "ssm"} and set(a) == {"ln1", "attn"} and \
        set(e) == {"ln1", "moe", "shared"}
    assert m["ssm"]["w_in"].shape == (2688, 10304) and \
        m["ssm"]["conv_w"].shape == (6144, 4) and \
        m["ssm"]["w_out"].shape == (4096, 2688)
    assert set(e["moe"]) == {"router", "router_bias", "wi", "wo"} and \
        e["moe"]["wi"].shape == (16, 2688, 1856) and \
        e["shared"]["wo"].shape == (3712, 2688) and \
        a["attn"]["wk"].shape == (2688, 256)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count - 2_602.7e6) < 1e6         # ISSUE 43's 2,602.7M


@pytest.mark.parametrize("key,value,name", [
    ("hybrid_override_pattern", "MEM-EM", "letter '-'"),
    ("hybrid_override_pattern", "MEM", "hybrid_override_pattern has 3"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("attention_bias", True, "attention_bias"),
    ("n_shared_experts", 2, "n_shared_experts"),
    ("time_step_limit", [0.0, 1.0], "time_step_limit"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
])
def test_reader_refuses_by_name_what_is_not_built(key, value, name):
    with pytest.raises(ValueError, match="nemotron_h.*" + name):
        config_from_hf(small(**{key: value}))


def test_stack_refuses_what_it_does_not_build(tiny):
    cfg = tiny[1]
    with pytest.raises(NotImplementedError, match="nemotron_h"):
        tl.init_typed_params(dataclasses.replace(cfg, activation="gelu"),
                             jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="ssm_heads"):
        dataclasses.replace(cfg, ssm_groups=3)
    with pytest.raises(ValueError, match="is empty"):
        dataclasses.replace(cfg, layer_sparse=(-1,) * 6)


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
    "skip_term_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", D=jnp.zeros_like)),
    "convolution_bias_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "ssm", conv_b=jnp.zeros_like)),
    "selection_bias_dropped": lambda cfg, p: (
        cfg, _in_layers(p, "moe", router_bias=jnp.zeros_like)),
    "one_group_of_b_and_c": lambda cfg, p: (
        dataclasses.replace(cfg, ssm_groups=1), p),
    "routed_scale_one": lambda cfg, p: (
        dataclasses.replace(cfg, routed_scale=1.0), p),
    "bf16_weights": lambda cfg, p: (cfg, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_program_wrong_in_one_way_is_caught(name, tiny):
    _, cfg, params, tokens, want = tiny
    wrong_cfg, wrong_params = CONTROLS[name](cfg, params)
    if name == "one_group_of_b_and_c":      # the widths follow the groups
        with pytest.raises(Exception):
            uncached(wrong_cfg, wrong_params, tokens[:64])
        return
    diff = np.abs(uncached(wrong_cfg, wrong_params, tokens) - want).max()
    assert diff > 50 * F32_TOL, diff


def test_attention_has_no_positional_term():
    """Shift every position by 5: a stack of ``*`` layers cannot tell."""
    cfg = config_from_hf(small(num_hidden_layers=2,
                               hybrid_override_pattern="**"))
    params = tf.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, VOCAB, (1, 20)))
    at = jnp.arange(20)[None]
    a = tl.forward_hidden_typed(cfg, params, tokens, positions=at)
    b = tl.forward_hidden_typed(cfg, params, tokens, positions=at * 3 + 5)
    assert float(jnp.abs(a - b).max()) == 0.0
    rotary = dataclasses.replace(cfg, full_attn_rope=True)
    c = tl.forward_hidden_typed(rotary, params, tokens, positions=at * 3 + 5)
    assert float(jnp.abs(a - c).max()) > 1e-3


def _rows(cfg, m=3, c=24, seed=0):
    rng = np.random.default_rng(seed)
    p = tf.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)["layers"][
        0]["ssm"]
    u = jnp.asarray(rng.normal(0, 0.5, (m, c, cfg.ssm_conv_dim)), jnp.float32)
    dt = jnp.asarray(rng.normal(0, 1, (m, c, cfg.ssm_heads)), jnp.float32)
    state = jnp.asarray(rng.normal(0, 1, (
        m, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size)), jnp.float32)
    return p, u, dt, state


def test_chunk_form_is_the_recurrence_on_the_same_row(tiny):
    """One row of 24 positions from a carried state: the chunk form in one
    call against 24 steps of the recurrence; and the chunk form over 17
    LIVE positions against 17 steps (padded positions advance nothing)."""
    cfg = tiny[1]
    p, u, dt, state = _rows(cfg)
    with jax.default_matmul_precision("highest"):
        counts = jnp.asarray([24, 17, 0], jnp.int32)
        y, s_out = ssm.scan_chunk(cfg, p, u, dt, state, counts)
        s, ys = state, []
        for t in range(24):
            y_t, s_new = ssm.scan_step(cfg, p, u[:, t:t + 1], dt[:, t:t + 1],
                                       s, (t < counts).astype(jnp.int32))
            s = s_new
            ys.append(y_t)
    want = np.concatenate(ys, axis=1)
    for r, n in enumerate((24, 17)):
        assert np.abs(np.asarray(y)[r, :n] - want[r, :n]).max() < 1e-5
    assert np.abs(np.asarray(s_out) - np.asarray(s)).max() < 1e-5
    # the row with no live position carried its state through untouched
    assert np.array_equal(np.asarray(s_out)[2], np.asarray(state)[2])
    assert np.abs(np.asarray(s_out)[1] - np.asarray(state)[1]).max() > 1e-2


def test_convolution_carries_its_tail_past_padding(tiny):
    cfg = tiny[1]
    p, u, _, _ = _rows(cfg, m=2, c=10)
    tail = jnp.asarray(np.random.default_rng(1).normal(
        0, 1, (2, 3, cfg.ssm_conv_dim)), jnp.float32)
    counts = jnp.asarray([10, 4], jnp.int32)
    got, new_tail = ssm.conv_rows(cfg, p, u, tail, counts)
    seq = np.concatenate([np.asarray(tail), np.asarray(u)], axis=1)
    w, b = np.asarray(p["conv_w"]), np.asarray(p["conv_b"])
    for t in range(10):
        acc = b + sum(seq[:, t + i] * w[:, i] for i in range(4))
        assert np.abs(np.asarray(got)[:, t] -
                      np.asarray(jax.nn.silu(acc))).max() < 1e-5
    assert np.array_equal(np.asarray(new_tail)[0], seq[0, 10:13])
    assert np.array_equal(np.asarray(new_tail)[1], seq[1, 4:7])


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
    """The state across chunk edges (127 / 128 / 129) and across launches
    (300: a fresh chunk and two split ones, the last of 44 live positions),
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
        assert eng.arena[f"ssm{i}"].shape == (9, 8, 8, 16) and \
            eng.arena[f"conv{i}"].shape == (9, 3 * 128)
    assert "ssm3" not in eng.arena and "ssm" not in eng.arena
    got = _walk(eng, tokens[:140], 130)
    assert np.abs(got - want[129:140]).max() < BF16_TOL


def test_rows_of_both_forms_in_one_launch(tiny):
    """Four sequences at once, 4-row programs at capacities 64 / 128: a
    prompt of 300 arrives while three sequences decode, a step at a time.
    Its first chunk (125 tokens beside three decode rows) takes the TOP
    instance, every row in the chunk form; the later ones, under a budget
    of 40 tokens a step, ride GROUPED split steps — one row at the chunk's
    width in the chunk form from the state the earlier launches left, three
    rows of one query stepping the recurrence, the state pools carried
    through the capacity switch. The long prompt's last logits and every
    decode row's are the reference's."""
    from deepspeed_tpu.telemetry.registry import registry
    hf, cfg, params, tokens, want = tiny
    w = ref.Widths.from_hf(hf)
    rng = np.random.default_rng(9)
    seqs = {u: rng.integers(0, VOCAB, 40 + 3 * u) for u in range(1, 4)}
    eng = engine(cfg, params, max_sequences=4, max_batch_tokens=128)
    assert eng._token_capacities(4, 128, "split") == (64, 128)
    count = {name: registry.counter("dispatch/" + name) for name in (
        "steps.split", "split_grouped_steps", "ssm_chunk_tokens",
        "state_resets")}
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
    # 300 = 125 (top instance) + 4 x 37 + 27 (grouped)
    assert moved["steps.split"] == 6 and \
        moved["split_grouped_steps"] == 5 and moved["state_resets"] == 4
    assert moved["ssm_chunk_tokens"] == sum(
        len(s) - 8 for s in seqs.values()) + 128 + 175
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
    assert np.abs(got - want[129:140]).max() > 50 * F32_TOL


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


def test_dispatch_counts_the_state_work(tiny):
    from deepspeed_tpu.inference import launch_work
    from deepspeed_tpu.inference.ragged import RaggedBatch
    _, cfg, params, _, _ = tiny
    site = launch_work.Site(cfg, 8, 32, False)
    fed = np.array([128, 1, 44, 1], np.int32)
    starts = np.array([0, 60, 128, 0], np.int32)

    def state(chunk, grouped):
        return tuple(launch_work.state_work(site, launch_work.Launch(
            "split", chunk, grouped, 4 * chunk, 174, starts, fed)).values())
    assert state(128, grouped=True) == (4, 2, 172)
    assert state(128, grouped=False) == (4, 2, 174)
    assert state(1, grouped=False) == (4, 2, 0)
    work = launch_work.launch_work(
        site, "split", launch_work.Form(4, (), 512, 1, 132), 128, starts,
        fed)
    assert (work["state_rows"], work["state_resets"],
            work["ssm_chunk_tokens"]) == (4, 2, 172)
    eng = engine(cfg, params)
    eng._put_validated([0, 1, 2, 3], [[1]] * 4)
    batch = RaggedBatch(
        uids=[0, 1, 2, 3], token_ids=np.zeros((4, 128), np.int32),
        token_counts=fed, start_positions=starts,
        slots=np.asarray([eng.state.seqs[u].slot for u in range(4)],
                         np.int32))
    packed = eng._pack(batch, 8, 128)
    assert packed[-8:].tolist() == batch.slots.tolist() + [8] * 4   # trash
    assert sorted(batch.slots.tolist()) == [0, 1, 2, 3]
    assert len(packed) == eng._packed_len(8, 128)


# -- what cannot carry the state stands aside, by name ------------------------

def test_the_prefix_cache_hands_a_recurrent_stack_no_pages(tiny):
    from deepspeed_tpu.serving import ServingFrontend
    from deepspeed_tpu.serving.frontend import adopt_cached
    from deepspeed_tpu.serving.prefix_cache import PrefixCache
    _, cfg, params, tokens, _ = tiny
    eng = engine(cfg, params)
    fe = ServingFrontend(eng)
    assert fe.cache is None and eng.state.recurrent
    prompt = tokens[:40].tolist()
    first = fe.submit(prompt, max_new_tokens=4)
    while first.finish_reason is None:
        fe.step()
    again = fe.submit(prompt, max_new_tokens=4)
    while again.finish_reason is None:
        fe.step()
    assert again.cached_tokens == 0 and \
        list(again.tokens_out) == list(first.tokens_out)
    fe.close()
    # a cache that DOES hold the prompt's pages is passed over all the same
    cache = PrefixCache(eng.state.allocator)
    blocks = eng.state.allocator.allocate(3)
    cache.insert(prompt, blocks)
    assert cache.match(prompt).matched(16) > 0
    assert adopt_cached(eng, cache, 77, prompt) == 0
    assert eng.state.seqs[77].seen_tokens == 0
    with pytest.raises(ValueError, match="recurrent stack"):
        eng.state.adopt(78, prompt, blocks[:1], 16)


def test_page_handoff_refuses_a_recurrent_stack_by_name(tiny):
    _, cfg, params, _, _ = tiny
    eng = engine(cfg, params)
    for call in (lambda: eng.export_pages([0]),
                 lambda: eng.import_pages({}, [0]),
                 lambda: eng.cow_block(0)):
        with pytest.raises(NotImplementedError, match="recurrent stack"):
            call()
    assert eng.kv_page_nbytes() == 2 * 16 * 2 * 16 * 4     # one * layer's


# -- the share ----------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two of the router's 16 experts each. Their routed
    parts (the program's ``held_experts_moe_layer`` on each share's slice of
    the weights), with the shared expert counted ONCE, add up to the
    reference's uncut ``E`` layer."""
    whole_hf = small(expert_share=None, n_routed_experts=16,
                     num_hidden_layers=1, hybrid_override_pattern="E")
    cfg = config_from_hf(whole_hf)
    w = ref.Widths.from_hf(whole_hf)
    assert w.held_experts == 16 and cfg.experts_held is None
    lp = randomised(tf.init_params(cfg, jax.random.PRNGKey(11),
                                   jnp.float32))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(12), (256, 48), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._layer(x, lp, w, "E")
        hin = ref.dense._rms_norm(x, lp["ln1"]["scale"], w.eps)
        routed = jnp.zeros_like(x)
        for chip in range(8):
            hf_i = small(expert_share={"router_experts": 16,
                                       "first_expert": 2 * chip},
                         n_routed_experts=2, num_hidden_layers=1,
                         hybrid_override_pattern="E")
            mine = slice(2 * chip, 2 * chip + 2)
            p_i = dict(lp["moe"], **{k: lp["moe"][k][mine]
                                     for k in ("wi", "wo")})
            part = moe.held_experts_moe_layer(config_from_hf(hf_i), p_i,
                                              hin[None])[0][0]
            assert float(jnp.abs(part - ref.experts_part(
                hin, p_i, ref.Widths.from_hf(hf_i))).max()) < 1e-5
            routed = routed + part
        shared = ref._relu2_unit(hin, lp["shared"]["wi"], lp["shared"]["wo"])
        # the program's shared expert is the same unit
        assert float(jnp.abs(moe._shared_expert(lp["shared"], hin) -
                             shared).max()) < 1e-5
    assert float(jnp.abs(x + routed + shared - whole).max()) < 1e-4
    assert float(jnp.abs(routed).max()) > 1e-3      # ... and not vacuously


# -- the reference's mixer against the published module -----------------------

def test_reference_mixer_is_transformers_mamba2():
    """The reference's ``M`` mixer against ``transformers``'
    ``Mamba2Mixer.torch_forward`` (its chunked scan, convolution and
    projections) with the gated norm taken in groups as Zamba2's
    ``Zamba2RMSNormGated`` takes it (Mamba2's own norm has one group)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer
    from transformers.models.zamba2.modeling_zamba2 import Zamba2RMSNormGated
    hf = small()
    w = ref.Widths.from_hf(hf)
    conf = transformers.Mamba2Config(
        hidden_size=w.hidden, num_heads=w.ssm_heads, head_dim=w.ssm_head_dim,
        state_size=w.ssm_state, n_groups=w.ssm_groups, expand=w.inner /
        w.hidden, conv_kernel=w.conv_kernel, chunk_size=16, use_bias=False,
        use_conv_bias=True, hidden_act="silu", layer_norm_epsilon=w.eps,
        num_hidden_layers=1, vocab_size=VOCAB)
    torch.manual_seed(0)
    mixer = Mamba2Mixer(conf, layer_idx=0).float()
    mixer.norm = Zamba2RMSNormGated(w.inner, group_size=w.inner //
                                    w.ssm_groups, eps=w.eps)
    with torch.no_grad():
        mixer.D.uniform_(0.5, 1.5)
        mixer.norm.weight.uniform_(0.5, 1.5)
        hin = torch.randn(1, 70, w.hidden)
        want = mixer.torch_forward(hin).numpy()[0]
    get = lambda t: jnp.asarray(t.detach().numpy())
    p = {"w_in": get(mixer.in_proj.weight).T,
         "conv_w": get(mixer.conv1d.weight)[:, 0, :],
         "conv_b": get(mixer.conv1d.bias), "dt_bias": get(mixer.dt_bias),
         "A_log": get(mixer.A_log), "D": get(mixer.D),
         "norm": {"scale": get(mixer.norm.weight)},
         "w_out": get(mixer.out_proj.weight).T}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba_mixer(w, p, get(hin)[0]))
    assert np.abs(got - want).max() < 2e-5
