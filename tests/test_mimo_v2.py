"""MiMo-V2's block (``model_type: mimo_v2``; models/typed_layers.py) against
its plain reference (``benchmark/reference/mimo_v2_decoder.py``) on seeded
random weights at a tiny size: both layer kinds (full: 1 KV head, window:
2 KV heads, a window of 8, a sink that is not zero), K heads 24 wide with
8 rotary dims, V heads 16, a leading dense layer, a sigmoid top-4-of-16
router with a selection bias of which 4 experts are held here.

Everything is float32 on the CPU on both sides, so the tolerances are
float32 round-off over a few hundred accumulated terms (logits are of the
order of 1): 2e-4 absolute. A mechanism left out moves the logits by
1e-2 and more (``test_a_mechanism_left_out_is_seen``), fifty times that."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.hf_loader import config_from_hf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 128

TINY = {
    "model_type": "mimo_v2", "hidden_act": "silu", "hidden_size": 64,
    "num_attention_heads": 4, "swa_num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
    "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "sliding_window": 8, "sliding_window_size": 8,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_value_scale": 0.707,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1], "num_hidden_layers": 4,
    "moe_layer_freq": [0, 1, 1, 1, 1, 1], "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "expert_share": {"router_experts": 16, "first_expert": 4},
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "layernorm_epsilon": 1e-05, "vocab_size": VOCAB,
    "tie_word_embeddings": False, "max_position_embeddings": 4096}


def reference():
    from benchmark.reference import mimo_v2_decoder
    return mimo_v2_decoder


def build(hf, seed=0):
    """(cfg, params): float32, a wide init so that attention is not
    uniform, a router bias that is not zero so that it selects."""
    cfg = dataclasses.replace(config_from_hf(hf), init_std=0.1)
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), cfg.num_layers)
    for lp, key in zip(params["layers"], keys):
        if "moe" in lp:
            lp["moe"]["router_bias"] = 0.3 * jax.random.normal(
                key, lp["moe"]["router_bias"].shape)
    return cfg, params


@pytest.fixture(scope="module")
def tiny():
    cfg, params = build(TINY)
    return cfg, params, reference().Widths.from_hf(TINY)


def program_logits(cfg, params, tokens):
    return np.asarray(transformer.forward(
        cfg, params, jnp.asarray([tokens], jnp.int32))[0])


def test_the_tree_is_typed(tiny):
    cfg, params, w = tiny
    assert cfg.layer_kinds == (0, 1, 1, 0) and cfg.layer_sparse == \
        (0, 1, 1, 1)
    assert cfg.num_experts == 16 and cfg.experts_held == (4, 4)
    shapes = [jax.tree.map(lambda a: a.shape, lp) for lp in params["layers"]]
    assert shapes[0]["attn"]["wk"] == (64, 1 * 24)       # full: 1 KV head
    assert shapes[1]["attn"]["wk"] == (64, 2 * 24)       # window: 2
    assert shapes[1]["attn"]["wv"] == (64, 2 * 16)       # V heads of 16
    assert shapes[1]["attn"]["wo"] == (4 * 16, 64)
    assert shapes[1]["attn"]["sink"] == (4,) and "sink" not in \
        shapes[0]["attn"] and "sink" not in shapes[3]["attn"]
    assert shapes[0]["mlp"]["wg"] == (64, 96) and "moe" not in shapes[0]
    assert shapes[1]["moe"]["router"] == (64, 16)        # router: all 16
    assert shapes[1]["moe"]["wg"] == (4, 64, 32)         # experts: held 4
    assert shapes[1]["moe"]["router_bias"] == (16,)
    assert float(jnp.abs(params["layers"][1]["attn"]["sink"]).min()) > 0
    assert w.kinds == cfg.layer_kinds and w.rope_dim == cfg.rope_dim == 8


def test_forward_matches_the_reference(tiny):
    """(a) the uncached forward, 29 tokens: more than three windows."""
    cfg, params, w = tiny
    toks = np.random.default_rng(0).integers(0, VOCAB, 29).tolist()
    ours = program_logits(cfg, params, toks)
    theirs = reference().logits_of(w, params, toks, jax.devices()[0])
    assert ours.shape == theirs.shape == (29, VOCAB)
    assert np.abs(theirs).max() > 0.3                    # not a null model
    assert np.abs(ours - theirs).max() < TOL


def _engine(cfg, params, **over):
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    conf = dict(dtype="float32", max_sequences=4, num_blocks=32,
                block_size=8, max_seq_len=64, max_batch_tokens=64,
                prefill_chunk=8)
    conf.update(over)
    return RaggedInferenceEngineTPU(cfg, conf, params=params)


def test_prefill_in_chunks_then_decode_through_the_paged_cache(tiny):
    """(b) two sequences, prompts of 21 and 11 tokens (longer than the
    window of 8 and than the chunk of 8, so fresh, split and decode
    programs all run and the window bites in each), then 12 decode steps,
    the second sequence joining later: every step's logits against the
    reference's full forward of the same tokens."""
    cfg, params, w = tiny
    ref, dev = reference(), jax.devices()[0]
    rng = np.random.default_rng(1)
    eng = _engine(cfg, params)
    from deepspeed_tpu.inference.engine_v2 import _pools
    assert set(_pools(eng.arena)) == {"k", "v", "k_win", "v_win"}
    # token-major pools: [layers of the kind x (blocks + 1), bs, kvh * d]
    assert eng.arena["k"].shape == (2 * 33, 8, 1 * 24)
    assert eng.arena["v_win"].shape == (2 * 33, 8, 2 * 16)
    seqs = {0: rng.integers(0, VOCAB, 21).tolist(),
            1: rng.integers(0, VOCAB, 11).tolist()}

    def check(out):
        for uid, logits in out.items():
            want = ref.logits_of(w, params, seqs[uid], dev)[-1]
            assert np.abs(np.asarray(logits) - want).max() < TOL, uid

    out = eng.put([0], [seqs[0]])
    check(out)
    for step in range(12):
        feed = {uid: int(np.argmax(out[uid])) for uid in out}
        for uid, tok in feed.items():
            seqs[uid].append(tok)
        uids = list(feed)
        toks = [[feed[u]] for u in uids]
        if step == 3:                    # a prompt joins the decode rows
            uids.append(1)
            toks.append(seqs[1])
        out = eng.put(uids, toks)
        assert set(out) == set(uids)
        check(out)
    assert len(seqs[0]) == 33 and len(seqs[1]) == 11 + 8
    from deepspeed_tpu.telemetry.registry import registry
    live = registry.get("dispatch/kv_window_live_tokens").value
    held = registry.get("dispatch/kv_window_held_tokens").value
    assert 0 < live < held              # rows outgrew the window of 8


MUTATIONS = {
    # what the PROGRAM is made to leave out -> the config it runs with
    "window_ignored": lambda c: dataclasses.replace(c, sliding_window=4096),
    "value_scale_ignored": lambda c: dataclasses.replace(c, value_scale=1.0),
    "sink_ignored": None,                # the parameters lose their sinks
    "rope_base_of_the_full_kind_on_window_layers":
        lambda c: dataclasses.replace(c, window_rope_theta=c.rope_theta),
    "bias_ignored_in_selection": "router_bias",
}


@pytest.mark.parametrize("left_out", sorted(MUTATIONS))
def test_a_mechanism_left_out_is_seen(tiny, left_out):
    """(d) each mechanism, left out of the program, moves the logits by
    far more than ``TOL``: the comparisons above fail without it."""
    cfg, params, w = tiny
    toks = np.random.default_rng(0).integers(0, VOCAB, 29).tolist()
    theirs = reference().logits_of(w, params, toks, jax.devices()[0])
    how = MUTATIONS[left_out]
    if how is None:
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            lp["attn"].pop("sink", None)
    elif how == "router_bias":
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            if "moe" in lp:
                lp["moe"].pop("router_bias")
    else:
        cfg = how(cfg)
    assert np.abs(program_logits(cfg, params, toks) - theirs).max() > \
        50 * TOL


def test_the_bias_selects_and_does_not_weigh():
    """(d) ``noaux_tc``: an expert lifted by the bias is selected, and its
    weight is its unbiased score over the unbiased scores selected."""
    from deepspeed_tpu.parallel.moe import route_tokens
    cfg = config_from_hf(TINY)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)) * 0.1, jnp.float32)
    bias = np.zeros(16, np.float32)
    bias[11] = 10.0                      # expert 11 wins every selection
    topw, topi = route_tokens(cfg, {"router": router,
                                    "router_bias": jnp.asarray(bias)}, x)
    z = np.asarray(jax.nn.sigmoid(x @ router))
    assert (np.asarray(topi)[:, 0] == 11).all()
    picked = np.take_along_axis(z, np.asarray(topi), axis=1)
    np.testing.assert_allclose(
        np.asarray(topw), picked / picked.sum(1, keepdims=True), atol=1e-6)
    # had the bias weighed, expert 11 would hold nearly all the weight
    assert float(np.asarray(topw)[:, 0].max()) < 0.5
    assert np.asarray(topw).sum(1) == pytest.approx(1.0, abs=1e-6)


def test_the_router_runs_in_float32_on_bf16_activations():
    """(d) the engine's activations and weights are bf16; the router's
    input, matmul and sigmoid are float32 of those values: equal to the
    float32 computation to round-off, where a bf16 matmul is 1e-3 off."""
    from deepspeed_tpu.parallel.moe import route_tokens
    cfg = dataclasses.replace(config_from_hf(TINY), norm_topk_prob=False)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(64, 16)) * 0.3, jnp.bfloat16)
    topw, topi = route_tokens(cfg, {"router": router}, x)
    exact = jax.nn.sigmoid(jnp.einsum(
        "sd,de->se", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    want, want_i = jax.lax.top_k(exact, 4)
    assert (np.asarray(topi) == np.asarray(want_i)).all()
    assert np.abs(np.asarray(topw) - np.asarray(want)).max() < 1e-6
    rough = jax.nn.sigmoid((x @ router).astype(jnp.float32))
    assert np.abs(np.asarray(jax.lax.top_k(rough, 4)[0]) -
                  np.asarray(want)).max() > 1e-4


def test_the_shares_add_up_to_the_uncut_layer():
    """(c) the four shares of one sparse layer (experts 0-3, 4-7, 8-11,
    12-15), each computed by the PROGRAM's expert layer told which experts
    it holds, sum to the uncut REFERENCE's output for the layer: all 16
    experts held, nothing left out."""
    from deepspeed_tpu.parallel.moe import held_experts_moe_layer
    ref = reference()
    uncut = dict(TINY, n_routed_experts=16)
    del uncut["expert_share"]
    cfg, params = build(uncut)
    moe = params["layers"][1]["moe"]
    assert moe["wg"].shape[0] == 16 and cfg.experts_held is None
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 9, 64)),
                    jnp.float32)
    whole = np.asarray(ref.experts_part(
        x.reshape(18, 64), moe, ref.Widths.from_hf(uncut)))
    assert np.abs(whole).max() > 0.05
    total = np.zeros_like(whole)
    for first in (0, 4, 8, 12):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 4))
        share = dict(moe, **{n: moe[n][first:first + 4]
                             for n in ("wg", "wi", "wo")})
        part, _aux = held_experts_moe_layer(share_cfg, share, x)
        part = np.asarray(part).reshape(18, 64)
        assert np.abs(part).max() > 1e-3          # every share gives some
        # ... and is what the reference gives for the same share
        share_w = ref.Widths.from_hf(dict(
            uncut, n_routed_experts=4,
            expert_share={"router_experts": 16, "first_expert": first}))
        np.testing.assert_allclose(
            part, np.asarray(ref.experts_part(x.reshape(18, 64), share,
                                              share_w)), atol=2e-6)
        total += part
    np.testing.assert_allclose(total, whole, atol=5e-6)


@pytest.mark.parametrize("skewed", [False, True])
def test_many_tokens_are_served_in_rounds_and_none_is_dropped(skewed):
    """Beyond ``HELD_ROUND_ROWS`` tokens a held assignment's place in its
    expert's rows is the number of earlier tokens that picked the expert,
    and the experts are served 128 rows each a round. 300 tokens of which
    the last 40 are padding (``valid``); ``skewed``: the bias sends every
    token to held expert 5 as well, so that expert needs three rounds.
    Against the reference on the valid tokens; padding rows come out 0."""
    from deepspeed_tpu.parallel import moe as moe_mod
    ref = reference()
    cfg, params = build(TINY)
    moe = dict(params["layers"][1]["moe"])
    if skewed:
        moe["router_bias"] = moe["router_bias"].at[5].set(10.0)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, 100, 64)),
                    jnp.float32)
    valid = (jnp.arange(300) < 260).reshape(3, 100)
    assert 300 > moe_mod.HELD_ROUND_ROWS
    out, _aux = moe_mod.held_experts_moe_layer(cfg, moe, x, valid=valid)
    out = np.asarray(out).reshape(300, 64)
    want = np.asarray(ref.experts_part(x.reshape(300, 64), moe,
                                       ref.Widths.from_hf(TINY)))
    np.testing.assert_allclose(out[:260], want[:260], atol=5e-6)
    assert np.abs(want[:260]).max() > 0.05 and not out[260:].any()
    # the few-token path (every held expert on every token) agrees
    few, _aux = moe_mod.held_experts_moe_layer(cfg, moe, x[:1])
    np.testing.assert_allclose(np.asarray(few).reshape(100, 64),
                               want[:100], atol=5e-6)


def _many_tokens(case):
    """(cfg, the layer's parameters, x [3, 100, 64], valid [3, 100]) of a
    many-token step: 300 token slots, experts 4-7 of 16 held."""
    cfg, params = build(TINY)
    moe = dict(params["layers"][1]["moe"])
    x = jnp.asarray(np.random.default_rng(8).normal(size=(3, 100, 64)),
                    jnp.float32)
    slots = np.arange(300)
    if case in ("exactly_128", "one_over_129"):
        # every valid token picks held expert 5: 128 fill round 0 to its
        # last row, the 129th opens round 1
        moe["router_bias"] = moe["router_bias"].at[5].set(10.0)
        valid = slots < (128 if case == "exactly_128" else 129)
    elif case == "none_held":
        # (a score is under 1: a bias of -10 takes an expert out)
        moe["router_bias"] = moe["router_bias"].at[4:8].set(-10.0)
        valid = slots < 260
    else:
        # padding in the MIDDLE of the step, and a run of it
        valid = (slots % 3 != 1) & ~((slots > 130) & (slots < 170))
    return cfg, moe, x, jnp.asarray(valid).reshape(3, 100)


@pytest.mark.parametrize("case", ["exactly_128", "one_over_129",
                                  "none_held", "holes"])
def test_a_place_is_the_count_of_earlier_tokens_of_the_expert(case):
    """The many-token path's placement at its edges: an expert with
    exactly ``HELD_ROUND_ROWS`` assignments (one round, its last row
    used) and with one more (a second round for one row); a step in which
    NO token picks a held expert (zeros, no NaN); padding between valid
    tokens. Against the reference on the valid tokens; padding rows 0."""
    from deepspeed_tpu.parallel import moe as moe_mod
    ref = reference()
    cfg, moe, x, valid = _many_tokens(case)
    _w, topi = moe_mod.route_tokens(cfg, moe, x.reshape(300, 64))
    picked = np.asarray(topi)[np.asarray(valid).reshape(-1)]
    most = max(int((picked == e).sum()) for e in range(4, 8))
    assert {"exactly_128": most == 128, "one_over_129": most == 129,
            "none_held": most == 0, "holes": 0 < most < 128}[case]
    out, _aux = moe_mod.held_experts_moe_layer(cfg, moe, x, valid=valid)
    out = np.asarray(out).reshape(300, 64)
    want = np.asarray(ref.experts_part(x.reshape(300, 64), moe,
                                       ref.Widths.from_hf(TINY)))
    live = np.asarray(valid).reshape(-1)
    assert np.isfinite(out).all() and not out[~live].any()
    np.testing.assert_allclose(out[live], want[live], atol=5e-6)
    if case == "none_held":
        assert not out.any() and not want[live].any()
    else:
        assert np.abs(want[live]).max() > 0.05


def test_two_calls_on_one_input_are_the_same_bits():
    """No scatter-add: a token's rows are summed in a fixed order."""
    from deepspeed_tpu.parallel import moe as moe_mod
    cfg, moe, x, valid = _many_tokens("one_over_129")
    moe = jax.tree.map(lambda a: a.astype(jnp.bfloat16), moe)
    layer = jax.jit(lambda p, x, v: moe_mod.held_experts_moe_layer(
        cfg, p, x, valid=v)[0])
    first, again = np.asarray(layer(moe, x, valid)), \
        np.asarray(layer(moe, x, valid))
    assert first.any() and (first == again).all()
    eager = np.asarray(moe_mod.held_experts_moe_layer(cfg, moe, x,
                                                      valid=valid)[0])
    np.testing.assert_allclose(eager, first, atol=2e-2)


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(inner)


@pytest.mark.parametrize("slots", [100, 300])
def test_the_many_token_path_neither_sorts_nor_scatter_adds(slots):
    """Structure: the many-token path (300 slots) places by a prefix sum
    and moves rows by gathers — no ``sort``, no scatter of any kind (the
    ``bincount`` was one), its rounds after the first one ``while`` —; the
    few-token path (100 slots) holds none of them (the router gathers its
    weights in both)."""
    from deepspeed_tpu.parallel import moe as moe_mod
    cfg, moe, x, valid = _many_tokens("holes")
    x, valid = x.reshape(1, 300, 64)[:, :slots], \
        valid.reshape(1, 300)[:, :slots]
    found = set(_primitives(jax.make_jaxpr(
        lambda p, x, v: moe_mod.held_experts_moe_layer(cfg, p, x, valid=v))(
            moe, x, valid).jaxpr))
    assert not {n for n in found if "sort" in n or "scatter" in n}, found
    assert "gather" in found and ("cumsum" in found, "while" in found) == \
        (slots > moe_mod.HELD_ROUND_ROWS,) * 2, found


def test_layers_of_one_shape_trace_their_rounds_once():
    """Set-up: the many-token rounds are a jit of their own, so a stack's
    sparse layers (here three, each its own weights) hold ONE traced body
    between them — the program's trace and lowering do not grow by a
    round's operations a layer."""
    from deepspeed_tpu.parallel import moe as moe_mod
    cfg, moe, x, valid = _many_tokens("holes")
    layers = [jax.tree.map(lambda a, i=i: a * (1.0 + i), moe)
              for i in range(3)]

    def stack(layers, x):
        for p in layers:
            x = x + moe_mod.held_experts_moe_layer(cfg, p, x, valid=valid)[0]
        return x
    calls = [eqn for eqn in jax.make_jaxpr(stack)(layers, x).jaxpr.eqns
             if eqn.primitive.name in ("pjit", "jit")
             and eqn.params["name"] == "_held_rounds"]
    assert len(calls) == 3
    assert len({id(eqn.params["jaxpr"]) for eqn in calls}) == 1


@pytest.mark.parametrize("window", [None, 8])
def test_the_paged_kernel_with_unequal_widths_and_a_window(window):
    """``paged_attn_lse`` (interpret mode) over pools whose K is 256
    lanes wide (heads of 24 zero-padded) and V 128, history-only, with and
    without the window, against the XLA form at the true width's scale."""
    from deepspeed_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(7)
    kvh, bs, nb, dk, dv, h, c = 2, 8, 12, 256, 128, 4, 8
    ak = jnp.asarray(rng.normal(size=(nb + 1, bs, kvh, dk)), jnp.float32)
    ak = ak.at[..., 24:].set(0.0).reshape(nb + 1, bs, kvh * dk)
    av = jnp.asarray(rng.normal(size=(nb + 1, bs, kvh * dv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, c, h, dk)), jnp.float32)
    q = q.at[..., 24:].set(0.0)
    pt = jnp.asarray(rng.permutation(nb)[:9].reshape(3, 3), jnp.int32)
    starts = jnp.asarray([0, 5, 19], jnp.int32)
    scale = 24 ** -0.5
    want, want_lse = pa.paged_attention_hist_xla(
        q, ak, av, pt, starts, window=window, scale=scale)
    got, got_lse = pa.paged_attention_with_lse(
        q, ak, av, pt, starts, jnp.zeros_like(starts), interpret=True,
        window=window, scale=scale)
    seen = np.asarray(want_lse) > -1e29          # rows with some history
    assert seen.any() and not seen.all()
    np.testing.assert_allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_lse)[seen],
                               np.asarray(want_lse)[seen], atol=2e-5)
    assert (np.asarray(got_lse)[~seen] < -1e29).all()


def test_a_padded_k_pool_gives_the_same_logits(tiny, monkeypatch):
    """On the chip the K pools are padded to the kernel's lanes (192 →
    256) and q, k are zero-padded to them; here 24 → 32 through the XLA
    forms: same logits as the reference."""
    cfg, params, w = tiny
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.ops import paged_attention as pa
    real = pa.init_arena_typed
    monkeypatch.setattr(
        engine_v2.pa, "init_arena_typed",
        lambda kinds, kvh, nb, bs, k_width, v_width, dtype:
        real(kinds, kvh, nb, bs, k_width + 8, v_width, dtype))
    eng = _engine(cfg, params)
    assert eng.arena["k_win"].shape[-1] == 2 * 32
    toks = np.random.default_rng(8).integers(0, VOCAB, 19).tolist()
    out = eng.put([7], [toks])
    want = reference().logits_of(w, params, toks, jax.devices()[0])[-1]
    assert np.abs(np.asarray(out[7]) - want).max() < TOL


def test_copy_on_write_copies_a_page_in_every_pool(tiny):
    """The prefix cache's copy-on-write duplicate covers both kinds'
    pools, every layer of each."""
    cfg, params, _w = tiny
    eng = _engine(cfg, params)
    toks = np.random.default_rng(9).integers(0, VOCAB, 8).tolist()
    eng.put([1], [toks])
    src = eng.state.seqs[1].blocks[0]
    dst = eng.cow_block(src)
    assert dst != src
    from deepspeed_tpu.inference.engine_v2 import _pools
    for name, pool in _pools(eng.arena).items():
        for layer in range(2):                   # two layers of each kind
            a = np.asarray(pool[layer * 33 + src])
            assert np.abs(a).max() > 0, name
            np.testing.assert_array_equal(
                a, np.asarray(pool[layer * 33 + dst]))
    assert eng.kv_page_nbytes() == 2 * 8 * (1 * (24 + 16) + 2 * (24 + 16)) * 4


def test_what_is_not_built_refuses_by_name(tiny):
    """(f) training, the v1 cache, page export and quantised weights say
    what they are."""
    import deepspeed_tpu as ds
    cfg, params, _w = tiny
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        ds.initialize(model=cfg, config={"train_batch_size": 1})
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        transformer.partition_specs(cfg)
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        transformer.forward_with_cache(cfg, params, jnp.zeros((1, 1),
                                                              jnp.int32),
                                       {}, jnp.int32(0))
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        eng.export_pages([0])
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        _engine(cfg, params, weight_quant="int8")


def test_generate_on_a_typed_stack_runs_ahead(tiny):
    """generate() waits for no program on a typed stack either: every
    launch but the first is made ahead of the one before it, through the
    three step programs, and the tokens are the reference's argmax."""
    from deepspeed_tpu.telemetry.registry import registry
    cfg, params, w = tiny
    eng = _engine(cfg, params)
    ahead = registry.counter("dispatch/launches_ahead")
    before = ahead.value
    prompt = np.random.default_rng(2).integers(0, VOCAB, 11).tolist()
    (out,) = eng.generate([prompt], max_new_tokens=5)
    assert ahead.value - before == 2 + 4 - 1    # chunks + decode steps
    assert {fn.__name__ for fn in eng._step_fns.values()} == {
        "serve_fresh_r1_c8", "serve_split_r1_c8", "serve_decode_r1"}
    assert out[:11].tolist() == prompt and len(out) == 16
    ref, dev = reference(), jax.devices()[0]
    for i in range(11, 16):
        logits = ref.logits_of(w, params, out[:i].tolist(), dev)[-1]
        assert logits[out[i]] > logits.max() - TOL
    assert not eng.state.seqs and eng.state.allocator.free_blocks == 32


def test_generate_is_the_stepwise_greedy_run_with_an_eos_inside(tiny):
    from tests.test_paged import generate_against_stepwise_with_an_eos
    cfg, params, _w = tiny
    rng = np.random.default_rng(12)
    generate_against_stepwise_with_an_eos(
        lambda: _engine(cfg, params),
        [rng.integers(0, VOCAB, n).tolist() for n in (11, 3, 19)], 9)


def test_config_from_hf_reads_the_cells_file_and_the_published_file():
    """(e) the benchmark's configuration file (7 layers, 16 of 256 experts
    held, an eighth of the vocabulary) and the source's config as
    published (48 layers, all 256 experts) both build."""
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("mimo-v2.5-l7-e16-serve")
    cfg = model_lib.build_model(conf)
    assert cfg.layer_kinds == (0, 1, 1, 1, 1, 0, 1)
    assert cfg.layer_sparse == (0, 1, 1, 1, 1, 1, 1)
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.v_dim) == \
        (4096, 64, 192, 128)
    assert (cfg.kv_heads, cfg.window_kv_heads) == (4, 8)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.rope_dim) == \
        (1e7, 1e4, 64)
    assert cfg.sliding_window == 128 and cfg.window_sink
    assert cfg.value_scale == 0.707 and cfg.norm_eps == 1e-5
    assert (cfg.intermediate_size, cfg.dense_intermediate_size) == \
        (2048, 16384)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) \
        == (256, (0, 16), 8)
    assert cfg.vocab_size == 19072 and not cfg.tie_embeddings
    assert cfg.router_scoring == "sigmoid" and cfg.router_select_bias
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "mimo-v2.5.json")) as fh:
        published = json.load(fh)
    full = config_from_hf({k: v for k, v in published.items()
                           if k != "source"})
    assert full.num_layers == 48 and len(full.layer_kinds) == 48
    assert sum(full.layer_kinds) == 39 and full.layer_sparse[0] == 0
    assert full.num_experts == 256 and full.experts_held is None
    assert full.vocab_size == 152576
    # the reference's useful work: the held share of a token's eight
    ref = reference()
    w = ref.Widths.from_hf(model_lib.published_keys(conf))
    attn = [4096 * 64 * 192 + 4096 * kv * 320 + 64 * 128 * 4096
            for kv in (4, 8)]
    sparse = 4096 * 256 + 8 * 16 // 256 * 3 * 4096 * 2048 + \
        (8 * 16 % 256) * 3 * 4096 * 2048 // 256
    assert ref.matmul_params_per_token(w) == \
        2 * attn[0] + 5 * attn[1] + 3 * 4096 * 16384 + 6 * sparse + \
        4096 * 19072
    with pytest.raises(ValueError, match="not built"):
        config_from_hf(dict(TINY, n_shared_experts=1))


# --- what the reference's check judges: tokens whose routing is decided ---

def _router_case(logit_rows, bias=None):
    """A router whose logits are written down: hin = one-hot rows, so the
    router's rows ARE the logits. 8 experts, 2 a token, experts 2-3 held."""
    w = dataclasses.replace(
        reference().Widths.from_hf(TINY), hidden=len(logit_rows),
        router_experts=8, per_token=2, first_expert=2, held_experts=2)
    m = {"router": jnp.asarray(logit_rows, jnp.float32)}
    if bias is not None:
        m["router_bias"] = jnp.asarray(bias, jnp.float32)
    return jnp.eye(len(logit_rows), dtype=jnp.float32), m, w


def test_the_routing_margin_of_held_experts_by_hand():
    """``held_margin``: the least move of a HELD expert's logit that
    changes its membership; experts held elsewhere do not count."""
    rows = [
        # held 2 is in (second), the best outsider 0.03 under it
        [3.0, -1.0, 1.00, -2.0, 0.97, -1.5, -3.0, -2.5],
        # held 3 is out, 0.25 under the last one in; held 2 far below
        [2.0, 1.0, -4.0, 0.75, -1.0, -2.0, -3.0, -2.5],
        # the near tie (0.001) is between experts held ELSEWHERE
        [2.0, 1.000, -1.0, -2.0, 0.999, -3.0, -2.5, -4.0],
    ]
    hin, m, w = _router_case(rows)
    got = np.asarray(reference().held_margin(hin, m, w))
    np.testing.assert_allclose(got, [0.03, 0.25, 2.0], atol=1e-5)
    # a selection bias moves the boundary and not the score: expert 4,
    # lifted by 0.2, now stands ahead of held 2, whose pick must rise to
    # sigmoid(0.97) + 0.2 to get back in
    hin, m, w = _router_case(rows[:1], bias=[0, 0, 0, 0, 0.2, 0, 0, 0])
    z = 1 / (1 + np.exp(-0.97)) + 0.2
    want = np.log(z / (1 - z)) - 1.0
    np.testing.assert_allclose(
        np.asarray(reference().held_margin(hin, m, w)), [want], atol=1e-5)
    # ... and where the bias lifts both selected picks over 1, which no
    # score reaches, no move of a held logit changes the selection
    hin, m, w = _router_case(rows[:1], bias=[0.9, 0, 0, 0, 0.9, 0, 0, 0])
    assert np.isinf(np.asarray(reference().held_margin(hin, m, w))).all()


def test_the_check_judges_the_tokens_whose_routing_is_decided(
        tiny, monkeypatch):
    """``argmax_gaps`` returns a gap for every generated token whose least
    held margin, over the sparse layers, reaches the module's limit, and
    for no other: at 0 all of them, at the limit those this walk counts,
    past every margin none."""
    cfg, params, w = tiny
    ref = reference()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (5, 11)]
    outs = [rng.integers(0, VOCAB, n).tolist() for n in (9, 6)]
    dev = jax.devices()[0]
    from benchmark.reference import dense_decoder as dense
    rows = [dense._padded(p + o) for p, o in zip(prompts, outs)]
    _, margins = ref.hidden_and_margins(w, params, rows, dev)
    mine = np.concatenate([np.asarray(m)[len(p) - 1:len(p) - 1 + len(o)]
                           for m, p, o in zip(margins, prompts, outs)])
    assert np.isfinite(mine).all() and mine.min() >= 0
    monkeypatch.setattr(ref, "UNDECIDED_LOGIT_MARGIN", 0.0)
    every = ref.argmax_gaps(w, params, prompts, outs, dev)
    assert len(every) == 15 and (every > 0).any()   # random tokens: gaps
    limit = float(np.median(mine))
    monkeypatch.setattr(ref, "UNDECIDED_LOGIT_MARGIN", limit)
    some = ref.argmax_gaps(w, params, prompts, outs, dev)
    np.testing.assert_array_equal(some, every[mine >= limit])
    assert 0 < len(some) < 15
    monkeypatch.setattr(ref, "UNDECIDED_LOGIT_MARGIN", float(mine.max()) * 2)
    assert len(ref.argmax_gaps(w, params, prompts, outs, dev)) == 0
