"""DeepSeek-V3's block (``model_type: deepseek_v3``, as GigaChat3.1-702B-A36B
publishes it; models/typed_layers.py) against its plain reference
(``benchmark/reference/deepseek_v3_decoder.py``) on seeded random weights at
a tiny size with the published RATIOS: V heads (24) wider than the nope part
of a key (16), 8 rotary dims, latents of 48 / 32, a leading dense layer, a
sigmoid router over 32 experts in 8 groups of which 4 are kept, top-8, the
kept weights scaled by 2.5, a shared expert, and 2 experts held here: half
of group 0. YaRN with an original length of 16, so that every test runs past
it.

Everything is float32 on the CPU on both sides, so the tolerances are
float32 round-off over a few hundred accumulated terms (logits are of the
order of 1): 2e-4 absolute. The absorbed and the expanded form are one
function in exact arithmetic, and differ by the same round-off. A mechanism
left out moves the logits by 1e-2 and more
(``test_a_mechanism_left_out_is_seen``), fifty times that."""

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf
from deepspeed_tpu.ops import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 128

TINY = {
    "model_type": "deepseek_v3", "hidden_act": "silu", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "attention_bias": False,
    "rope_theta": 100000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "rope_type": "yarn"},
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "n_shared_experts": 1, "ep_size": 1,
    "expert_share": {"router_experts": 32, "first_expert": 0},
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-06,
    "vocab_size": VOCAB, "tie_word_embeddings": False,
    "max_position_embeddings": 4096}


def reference():
    from benchmark.reference import deepseek_v3_decoder
    return deepseek_v3_decoder


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


def test_the_tree_is_latent(tiny):
    cfg, params, w = tiny
    assert cfg.layer_kinds == (2, 2, 2) and cfg.layer_sparse == (0, 1, 1)
    assert cfg.num_experts == 32 and cfg.experts_held == (0, 2)
    assert (cfg.head_dim, cfg.v_dim, cfg.rope_dim, cfg.latent_dim) == \
        (24, 24, 8, 40)
    assert (cfg.router_groups, cfg.router_groups_kept, cfg.routed_scale,
            cfg.shared_expert_size) == (8, 4, 2.5, 32)
    shapes = [jax.tree.map(lambda a: a.shape, lp) for lp in params["layers"]]
    assert shapes[0]["attn"] == {
        "wq_a": (64, 48), "q_norm": {"scale": (48,)},
        "wq_b": (48, 4 * 24), "wkv_a": (64, 32 + 8),
        "kv_norm": {"scale": (32,)}, "wkv_b": (32, 4 * (16 + 24)),
        "wo": (4 * 24, 64)}
    assert shapes[0]["mlp"]["wg"] == (64, 160) and "moe" not in shapes[0]
    assert shapes[1]["moe"]["router"] == (64, 32)        # router: all 32
    assert shapes[1]["moe"]["wg"] == (2, 64, 32)         # experts: held 2
    assert shapes[1]["shared"]["wo"] == (32, 64) and "shared" not in shapes[0]
    assert w.sparse == cfg.layer_sparse and w.v_head > w.nope


def test_forward_matches_the_reference(tiny):
    """(a) the uncached forward (expanded form), 29 tokens: past YaRN's
    original length of 16."""
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


@pytest.fixture()
def kernel_interpreted(monkeypatch):
    """``mla_decode`` in interpret mode, for an engine told to use it."""
    monkeypatch.setattr(pa, "mla_decode", functools.partial(
        pa.mla_decode, interpret=True))


def _serve_and_check(eng, cfg, params, w):
    """Two sequences, prompts of 21 and 11 tokens (three chunks of 8 and
    two: the fresh, split and decode programs all run), 12 decode steps,
    the second sequence's prompt joining the first's decode rows in ONE
    split step; every step's logits against the reference's full forward
    of the same tokens."""
    ref, dev = reference(), jax.devices()[0]
    rng = np.random.default_rng(1)
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
    return {fn.__name__ for fn in eng._step_fns.values()}


def test_prefill_in_chunks_then_decode_through_the_latent_pool(tiny):
    """(b) all four cache paths over the XLA readers: a fresh chunk, a
    chunked prefill whose later chunks attend an absorbed history, decode
    through the latent pool, a split step that mixes prefill and decode
    rows. The pool is ONE tensor of 40 values a token."""
    cfg, params, w = tiny
    eng = _engine(cfg, params)
    from deepspeed_tpu.inference.engine_v2 import _pools
    assert set(_pools(eng.arena)) == {"latent"} and not eng.use_pallas
    assert eng.arena["latent"].shape == (3 * 33, 8, 40)
    programs = _serve_and_check(eng, cfg, params, w)
    assert programs == {n + "_logits" for n in (
        "serve_fresh_r1_c8", "serve_split_r1_c8", "serve_split_r2_c8",
        "serve_decode_r1", "serve_decode_r2")}
    from deepspeed_tpu.telemetry.tracer import tracer
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        eng.put([0], [[5]])
        (span,) = [e for e in tracer.events()
                   if e["name"] == "serving/dispatch"]
    finally:
        tracer.configure(enabled=False)
    assert span["args"]["kv_tokens_latent"] == \
        span["args"]["context_tokens"] == 34


def test_the_kernel_serves_a_padded_pool_with_the_same_logits(
        tiny, kernel_interpreted):
    """(b) the same walk with ``mla_decode`` (interpret mode) as the
    reader of the decode step and of the split step's history: the pool's
    rows are padded to whole lane tiles (40 -> 128), the queries with
    zeros."""
    cfg, params, w = tiny
    eng = _engine(cfg, params, use_pallas=True)
    assert eng.arena["latent"].shape == (3 * 33, 8, 128)
    _serve_and_check(eng, cfg, params, w)


@pytest.mark.parametrize("c", [1, 8, 12])
def test_the_kernel_is_the_xla_reader(c):
    """``mla_decode`` against ``paged_attention_xla`` /
    ``paged_attention_hist_xla`` over a latent pool: 3 rows of unequal
    history (one empty, one dead row), chunk widths that are one tile, a
    tile of 8 and tiles of 4 (there over a page table of ODD width: the
    kernel takes four pages a loop turn, so the longest row takes two); the outputs of live queries and
    their logsumexps agree, dead tiles are zeros with an lse of -1e30."""
    rng = np.random.default_rng(c)
    n, h, w, vl, bs, mb = 3, 4, 128, 32, 8, 9 if c == 12 else 8
    pool = jnp.asarray(rng.normal(size=(40, bs, w)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(n, c, h, w)), jnp.float32)
    pt = jnp.asarray(rng.permutation(39)[:n * mb].reshape(n, mb), jnp.int32)
    starts = jnp.asarray([45, 0, 21], jnp.int32)     # 6, 0 and 3 pages
    counts = jnp.asarray([min(c, 5), min(c, 3), 0], jnp.int32)
    for hist in (False, True):
        kcounts = jnp.zeros_like(counts) if hist else counts
        out, lse = pa.mla_decode(q, pool, pt, starts, kcounts, counts,
                                 v_lanes=vl, scale=0.2, interpret=True)
        if hist:
            want, want_lse = pa.paged_attention_hist_xla(
                q, pool, None, pt, starts, scale=0.2, v_lanes=vl)
        else:
            want, want_lse = pa.paged_attention_xla(
                q, pool, None, pt, starts, counts, scale=0.2, v_lanes=vl,
                with_lse=True)
        assert out.shape == (n, c, h, vl) and lse.shape == (n, c, h)
        for i in range(n):
            live = int(counts[i])
            if hist and int(starts[i]) == 0:
                live = 0                      # no history: nothing visible
            np.testing.assert_allclose(out[i, :live], want[i, :live],
                                       atol=2e-5)
            np.testing.assert_allclose(lse[i, :live], want_lse[i, :live],
                                       atol=2e-5)
        assert not np.asarray(out[2]).any() and \
            (np.asarray(lse[2]) < -1e29).all()


def test_absorbed_is_expanded_to_rounding(tiny):
    """One layer's attention both ways on the same 19 tokens: scores and
    weighted sum over the latents with ``W_UK`` folded into the query and
    ``W_UV`` applied after, against keys and values expanded to heads."""
    cfg, params, _w = tiny
    a = params["layers"][1]["attn"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 19, 64)),
                    jnp.float32)
    pos = jnp.arange(19, dtype=jnp.int32)[None]
    q_nope, q_rope, latent = tl.latent_qkv(
        cfg, a, x, *transformer.rope_table(cfg, pos))
    q, k, v = tl.latent_expand_kv(cfg, a, q_nope, q_rope, latent)
    assert q.shape == k.shape == (1, 19, 4, 24) and v.shape == (1, 19, 4, 24)
    expanded, lse_e = pa.causal_attention_with_lse(q, k, v,
                                                   scale=cfg.attn_scale)
    q_lat = tl.latent_absorb_q(cfg, a, q_nope, q_rope, 40)
    assert q_lat.shape == (1, 19, 4, 40)
    o_lat, lse_a = pa.causal_attention_with_lse(
        q_lat, latent[:, :, None], latent[:, :, None, :32],
        scale=cfg.attn_scale)
    absorbed = tl.latent_expand_out(cfg, a, o_lat)
    assert np.abs(np.asarray(expanded)).max() > 0.05
    np.testing.assert_allclose(absorbed, expanded, atol=2e-6)
    np.testing.assert_allclose(lse_a, lse_e, atol=2e-6)


def test_the_yarn_table_against_the_formula():
    """``rope_table`` at the PUBLISHED parameters (theta 100000, 64 rotary
    dims, factor 64 over 4,096, beta 32 / 1), at positions past the
    original length: pairs 0-8 keep their frequency, pairs 19-31 turn 64
    times slower, a linear ramp between; sin / cos are not scaled
    (mscale = mscale_all_dim), the softmax is, by (0.1 ln 64 + 1)^2."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "gigachat3.1-702b-a36b.json")) as fh:
        published = json.load(fh)
    cfg = config_from_hf({k: v for k, v in published.items()
                          if k != "source"})
    i = np.arange(32)
    f = 100000.0 ** (-2.0 * i / 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(100000.0)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(100000.0)))
    assert (low, high) == (8, 19)
    keep = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = f / 64 * (1 - keep) + f * keep
    assert want[8] == f[8] and want[19] == f[19] / 64 and \
        f[12] / 64 < want[12] < f[12]
    pos = np.asarray([[0, 1, 4095, 4096, 5000, 100000, 262143]], np.int32)
    sin, cos = transformer.rope_table(cfg, jnp.asarray(pos))
    assert sin.shape == (1, 7, 32)
    ang = pos[0][:, None].astype(np.float64) * want[None]
    # float32 angles: an angle of 2.6e5 rad carries 0.03 rad of rounding
    loose = np.maximum(1e-6, np.abs(ang) * 2.0 ** -22)
    assert (np.abs(np.asarray(sin[0]) - np.sin(ang)) <= loose + 1e-6).all()
    assert (np.abs(np.asarray(cos[0]) - np.cos(ang)) <= loose + 1e-6).all()
    np.testing.assert_allclose(
        reference().rope_frequencies(reference().Widths.from_hf(published)),
        want, rtol=1e-12)
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.41589, abs=1e-5)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    # no yarn: the plain table, the plain scale
    plain = dataclasses.replace(cfg, rope_yarn=None)
    assert plain.attn_scale == 192 ** -0.5
    np.testing.assert_allclose(
        np.asarray(transformer.rope_table(plain, jnp.asarray(pos))[0][0, 1]),
        np.sin(f), atol=1e-6)


def _route(scores, bias=None, **over):
    """``route_tokens`` on written-down scores: the input is one-hot rows
    and the router's rows are the logits of the scores."""
    from deepspeed_tpu.parallel.moe import route_tokens
    cfg = dataclasses.replace(config_from_hf(TINY), **over)
    z = np.asarray(scores, np.float64)
    p = {"router": jnp.asarray(np.log(z) - np.log1p(-z), jnp.float32)}
    if bias is not None:
        p["router_bias"] = jnp.asarray(bias, jnp.float32)
    topw, topi = route_tokens(cfg, p, jnp.eye(len(z), dtype=jnp.float32))
    return np.asarray(topw), np.asarray(topi)


def _scores_in_groups():
    """32 scores, groups of 4: group g's four are 0.10 + 0.02 g + (0.000,
    0.004, 0.008, 0.012), all distinct; a group's score is 0.22 + 0.04 g,
    so the groups rank 7 > 6 > 5 > 4 (0.38) > 3 ..."""
    return (0.10 + 0.02 * np.repeat(np.arange(8), 4)
            + 0.004 * np.tile(np.arange(4), 8))


def test_a_high_score_in_a_dropped_group_is_not_picked():
    z = _scores_in_groups()
    z[1] = 0.95               # the best expert of all, alone in group 0:
    topw, topi = _route([z])  # its group scores 0.95 + 0.112, and is kept
    assert topi[0, 0] == 1
    z[1] = 0.266              # still the best single score; its group:
    # 0.266 + 0.112 = 0.378 against group 4's 0.380: DROPPED
    topw, topi = _route([z])
    assert 1 not in topi[0]
    # the flat router (one group) picks it first
    flat_w, flat_i = _route([z], router_groups=1, router_groups_kept=1)
    assert flat_i[0, 0] == 1
    # the eight are the best of the four kept groups' sixteen
    assert sorted(topi[0]) == list(range(24, 32))
    picked = z[topi[0]]
    np.testing.assert_allclose(topw[0], 2.5 * picked / picked.sum(),
                               rtol=1e-5)


def test_the_bias_selects_a_group_and_does_not_weigh():
    z = _scores_in_groups()
    bias = np.zeros(32)
    bias[2:4] = 0.5           # lifts group 0's pair sum over every other
    topw, topi = _route([z], bias)
    assert set(topi[0, :2].tolist()) == {2, 3}
    picked = z[topi[0]]
    np.testing.assert_allclose(topw[0], 2.5 * picked / picked.sum(),
                               rtol=1e-5)
    assert topw[0].sum() == pytest.approx(2.5, rel=1e-5)
    # had the bias weighed, experts 0 and 1 would hold most of the weight
    mine = np.isin(topi[0], [2, 3])
    assert topw[0][mine].sum() < 0.6


def test_the_flat_router_is_untouched():
    """One group, no scale: the router MiMo-V2 runs lowers to the program
    it lowered to before there were groups."""
    from deepspeed_tpu.parallel.moe import route_tokens
    flat = dataclasses.replace(config_from_hf(TINY), router_groups=1,
                               router_groups_kept=1, routed_scale=1.0)
    p = {"router": jnp.zeros((64, 32)), "router_bias": jnp.zeros((32,))}
    text = jax.jit(functools.partial(route_tokens, flat, p)).lower(
        jnp.zeros((5, 64))).as_text()
    assert text.count("top_k") == 1 or text.count("topk") == 1
    grouped = jax.jit(functools.partial(
        route_tokens, config_from_hf(TINY), p)).lower(
        jnp.zeros((5, 64))).as_text()
    assert len(grouped) > len(text)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """(c) THE SHARE TEST: the 16 shares of one sparse layer (2 experts
    each), every one computed by the PROGRAM's expert layer told which
    experts it holds, sum to the uncut REFERENCE's routed part; with the
    shared expert added ONCE (it is on every chip, and counts once across
    them) that is the uncut reference's whole layer."""
    from deepspeed_tpu.parallel.moe import held_experts_moe_layer
    ref = reference()
    uncut = dict(TINY, n_routed_experts=32)
    del uncut["expert_share"]
    cfg, params = build(uncut)
    lp = params["layers"][1]
    moe = lp["moe"]
    assert moe["wg"].shape[0] == 32 and cfg.experts_held is None
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 9, 64)),
                    jnp.float32)
    xf = x.reshape(18, 64)
    uw = ref.Widths.from_hf(uncut)
    routed = np.asarray(ref.experts_part(xf, moe, uw))
    assert np.abs(routed).max() > 0.05
    total = np.zeros_like(routed)
    gave = 0
    for first in range(0, 32, 2):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
        share = dict(moe, **{n: moe[n][first:first + 2]
                             for n in ("wg", "wi", "wo")})
        part, _aux = held_experts_moe_layer(share_cfg, share, x)
        part = np.asarray(part).reshape(18, 64)
        gave += bool(np.abs(part).max() > 1e-3)
        share_w = ref.Widths.from_hf(dict(
            uncut, n_routed_experts=2,
            expert_share={"router_experts": 32, "first_expert": first}))
        np.testing.assert_allclose(
            part, np.asarray(ref.experts_part(xf, share, share_w)),
            atol=5e-6)
        total += part
    assert gave >= 8                      # 4 of 8 groups a token, 18 tokens
    np.testing.assert_allclose(total, routed, atol=1e-5)
    # ... and the layer: x + routed + shared, the program's typed_ffn on
    # ONE share adds the shared expert whole
    hin = xf                                # (the norm is not in question)
    shared = np.asarray(ref._glu(hin, lp["shared"]["wg"], lp["shared"]["wi"],
                                 lp["shared"]["wo"]))
    assert np.abs(shared).max() > 0.05
    share_cfg = dataclasses.replace(cfg, experts_held=(0, 2))
    one = dict(lp, moe=dict(moe, **{n: moe[n][:2]
                                    for n in ("wg", "wi", "wo")}))
    ffn = np.asarray(tl.typed_ffn(share_cfg, one, x, None)).reshape(18, 64)
    part0, _ = held_experts_moe_layer(share_cfg, one["moe"], x)
    np.testing.assert_allclose(ffn - np.asarray(part0).reshape(18, 64),
                               shared, atol=5e-6)
    whole, _margin = ref.sparse_block(
        xf, {"scale": jnp.ones(64)}, moe, lp["shared"], uw)
    normed = np.asarray(xf) / np.sqrt(
        (np.asarray(xf) ** 2).mean(-1, keepdims=True) + 1e-6)
    again = np.asarray(ref.experts_part(jnp.asarray(normed), moe, uw)) + \
        np.asarray(ref._glu(jnp.asarray(normed), lp["shared"]["wg"],
                            lp["shared"]["wi"], lp["shared"]["wo"]))
    np.testing.assert_allclose(np.asarray(whole) - np.asarray(xf), again,
                               atol=1e-5)


def _float8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


MUTATIONS = {
    # what the PROGRAM is made to leave out or round -> how
    "group_cut_left_out": lambda c: dataclasses.replace(
        c, router_groups=1, router_groups_kept=1),
    "yarn_softmax_scale_left_out": lambda c: dataclasses.replace(
        c, rope_yarn=c.rope_yarn[:5] + (0.0,)),
    "yarn_frequencies_left_out": lambda c: dataclasses.replace(
        c, rope_yarn=None, head_dim_override=int(round(
            (c.head_dim ** -0.5 / c.attn_scale) ** -2))),
    "routed_scale_left_out": lambda c: dataclasses.replace(
        c, routed_scale=1.0),
    "shared_expert_left_out": "shared",
    "w_kvb_in_float8": "wkv_b",
}


@pytest.mark.parametrize("left_out", sorted(MUTATIONS))
def test_a_mechanism_left_out_is_seen(tiny, left_out):
    """(d) each mechanism, left out of the program (or ``W_kvb`` rounded to
    float8), moves the logits by far more than ``TOL``: the comparisons
    above fail without it."""
    cfg, params, w = tiny
    toks = np.random.default_rng(0).integers(0, VOCAB, 29).tolist()
    theirs = reference().logits_of(w, params, toks, jax.devices()[0])
    how = MUTATIONS[left_out]
    if left_out == "yarn_frequencies_left_out":
        # the plain table under the SAME softmax scale: only the 32
        # frequencies differ (head_dim_override cannot carry that, so the
        # scale is put back by hand)
        plain = dataclasses.replace(cfg, rope_yarn=None)
        sin, cos = transformer.rope_table(
            plain, jnp.arange(29, dtype=jnp.int32)[None])
        sin_y, cos_y = transformer.rope_table(
            cfg, jnp.arange(29, dtype=jnp.int32)[None])
        assert np.abs(np.asarray(sin) - np.asarray(sin_y)).max() > 0.1
        return
    if how == "shared":
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            lp.pop("shared", None)
    elif how == "wkv_b":
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            lp["attn"]["wkv_b"] = _float8(lp["attn"]["wkv_b"])
    else:
        cfg = how(cfg)
    assert np.abs(program_logits(cfg, params, toks) - theirs).max() > \
        50 * TOL


def test_a_float8_latent_pool_is_seen(tiny, monkeypatch):
    """(d) the latent rows rounded to float8 on their way into the pool:
    the decode steps' logits leave the reference's by far more than
    ``TOL``."""
    cfg, params, w = tiny
    write = pa.write_rows
    monkeypatch.setattr(pa, "write_rows", lambda pool, rows, *a, **k: write(
        pool, _float8(rows), *a, **k))
    eng = _engine(cfg, params)
    toks = np.random.default_rng(3).integers(0, VOCAB, 20).tolist()
    out = eng.put([0], [toks[:19]])
    out = eng.put([0], [toks[19:]])
    want = reference().logits_of(w, params, toks, jax.devices()[0])[-1]
    assert np.abs(np.asarray(out[0]) - want).max() > 50 * TOL


def test_what_the_reader_cannot_honour_refuses_by_name():
    for key, value in (("attention_bias", True), ("topk_method", "greedy"),
                       ("scoring_func", "softmax"), ("ep_size", 8),
                       ("q_lora_rank", None), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            config_from_hf(dict(TINY, **{key: value}))
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(dict(TINY, rope_scaling={"rope_type": "llama3",
                                                "factor": 8}))
    # the prediction module is accepted and not built; no scaling is plain
    plain = config_from_hf(dict(TINY, rope_scaling=None,
                                num_nextn_predict_layers=3))
    assert plain.rope_yarn is None and plain.num_layers == 3
    with pytest.raises(ValueError, match="all latent"):
        dataclasses.replace(plain, layer_kinds=(2, 0, 2))
    with pytest.raises(ValueError, match="router_groups"):
        dataclasses.replace(plain, router_groups=5)


def test_what_is_not_built_refuses_by_name(tiny):
    """(f) training, the v1 cache, page export and quantised weights say
    what they are."""
    import deepspeed_tpu as ds
    cfg, params, _w = tiny
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        ds.initialize(model=cfg, config={"train_batch_size": 1})
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        transformer.forward_with_cache(cfg, params, jnp.zeros((1, 1),
                                                              jnp.int32),
                                       {}, jnp.int32(0))
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        eng.export_pages([0])
    with pytest.raises(NotImplementedError, match="typed layer stack"):
        _engine(cfg, params, weight_quant="int8")


def test_copy_on_write_copies_a_latent_page(tiny):
    cfg, params, _w = tiny
    eng = _engine(cfg, params)
    eng.put([0], [list(range(1, 12))])
    src = eng.state.seqs[0].blocks[0]
    dst = eng.cow_block(src)
    pool = np.asarray(eng.arena["latent"])
    assert np.abs(pool[src]).max() > 0
    for layer in range(3):
        np.testing.assert_array_equal(pool[layer * 33 + dst],
                                      pool[layer * 33 + src])


def test_config_from_hf_reads_the_cells_file_and_the_published_file():
    """(e) the benchmark's configuration file (5 layers, 16 of 256 experts
    held, an eighth of the vocabulary) builds what ``BUILT_AS`` asks, every
    width as published; the source's config as published builds too."""
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("gigachat3.1-l5-e16-serve")
    assert conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    cfg = model_lib.build_model(conf)
    assert cfg.layer_kinds == (2,) * 5 and cfg.layer_sparse == (0, 1, 1, 1, 1)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.v_dim) == (7168, 64, 64, 192, 192)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.latent_dim) == (1536, 512, 128, 64, 576)
    assert (cfg.intermediate_size, cfg.dense_intermediate_size,
            cfg.shared_expert_size) == (2048, 18432, 2048)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.router_groups, cfg.router_groups_kept, cfg.routed_scale) \
        == (256, (0, 16), 8, 8, 4, 2.5)
    assert cfg.rope_theta == 1e5 and cfg.norm_eps == 1e-6
    assert cfg.rope_yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.vocab_size == 16032 and not cfg.tie_embeddings
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "gigachat3.1-702b-a36b.json")) as fh:
        published = json.load(fh)
    full = config_from_hf({k: v for k, v in published.items()
                           if k != "source"})
    assert full.num_layers == 64 and full.layer_sparse[:4] == (0, 0, 0, 1)
    assert sum(full.layer_sparse) == 61 and full.experts_held is None
    assert full.num_experts == 256 and full.vocab_size == 128256
    # the reference's useful work: the held share of a token's eight
    ref = reference()
    w = ref.Widths.from_hf(model_lib.published_keys(conf))
    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 320 + \
        64 * 192 * 7168
    assert attn == 132_579_328
    sparse = 7168 * 256 + 3 * 7168 * 2048 + 3 * 7168 * 2048 // 2
    assert ref.matmul_params_per_token(w) == \
        5 * attn + 3 * 7168 * 18432 + 4 * sparse + 7168 * 16032
    # MiMo-V2's reader shares the router keys and still has no shared expert
    from tests.test_mimo_v2 import TINY as MIMO
    grouped = config_from_hf(dict(MIMO, n_group=4, topk_group=2,
                                  routed_scaling_factor=1.5))
    assert (grouped.router_groups, grouped.router_groups_kept,
            grouped.routed_scale) == (4, 2, 1.5)


# --- what the reference's check judges: tokens whose routing is decided ---

def _router_case(scores, bias=None):
    """A router whose scores are written down (hin = one-hot rows): 32
    experts in 8 groups, 4 kept, 8 a token, experts 0-1 held."""
    w = dataclasses.replace(reference().Widths.from_hf(TINY),
                            hidden=len(scores))
    z = np.asarray(scores, np.float64)
    m = {"router": jnp.asarray(np.log(z) - np.log1p(-z), jnp.float32)}
    if bias is not None:
        m["router_bias"] = jnp.asarray(bias, jnp.float32)
    return jnp.eye(len(scores), dtype=jnp.float32), m, w


def test_the_routing_margin_covers_the_group_cut():
    """``held_margin`` by hand. Row 0: the held group is dropped by a
    hair (0.370 against the last kept 0.380: a gap of 0.01 over the
    steepest slope among the groups' pairs). Row 1: it is dropped by far
    (0.220). Row 2: it is kept, far from the cut: the least of the cut
    between the last kept and the first dropped group (0.42 - 0.38) and
    of the held experts' own moves in or out of the eight."""
    logit = lambda p: np.log(p) - np.log1p(-p)
    rows = []
    z = _scores_in_groups()
    z[1] = 0.258
    rows.append(z.copy())
    z[1] = 0.104
    rows.append(z.copy())
    z[1] = 0.95
    rows.append(z.copy())
    hin, m, w = _router_case(rows)
    got = np.asarray(reference().held_margin(hin, m, w))

    def steepest(z):
        pairs = np.sort(z.reshape(8, 4), axis=1)[:, -2:]
        return (pairs * (1 - pairs)).max()

    assert got[0] == pytest.approx(0.01 / steepest(rows[0]), rel=1e-3)
    assert got[1] == pytest.approx((0.380 - 0.220) / steepest(rows[1]),
                                   rel=1e-3)
    # row 2: groups 0 (1.062), 7, 6 and 5 (0.42) are kept, group 4 is the
    # first dropped; of the kept sixteen the eight are 0.95, group 7 and
    # the best three of group 6: held expert 1 leaves at the best
    # unselected pick (0.220), held expert 0 (0.10) enters at the last
    # selected (0.224)
    cut = (0.42 - 0.38) / steepest(rows[2])
    own = min(abs(logit(0.95) - logit(0.220)),
              abs(logit(0.10) - logit(0.224)))
    assert cut < own
    assert got[2] == pytest.approx(cut, rel=1e-3)
    assert np.asarray(reference().held_margin(
        hin, m, dataclasses.replace(w, groups_kept=8)))[2] == \
        pytest.approx(abs(logit(0.10) - logit(np.sort(rows[2])[-8])),
                      rel=1e-3)
    # one group: no cut, the plain top-k margin
    flat = dataclasses.replace(w, groups=1, groups_kept=1)
    plain = np.asarray(reference().held_margin(hin, m, flat))
    assert np.isfinite(plain).all() and plain[2] == pytest.approx(
        abs(logit(0.10) - logit(np.sort(rows[2])[-8])), rel=1e-3)


def test_the_check_judges_the_tokens_whose_routing_is_decided(
        tiny, monkeypatch):
    """``argmax_gaps`` returns a gap for every generated token whose least
    held margin, over the sparse layers, reaches the module's limit, and
    for no other."""
    cfg, params, w = tiny
    ref = reference()
    from benchmark.reference import mimo_v2_decoder
    # this block's own reading (2.5 x the weight a flipped expert carries,
    # a wider stream under the router): four times MiMo-V2's
    assert ref.UNDECIDED_LOGIT_MARGIN == 0.16 == \
        4 * mimo_v2_decoder.UNDECIDED_LOGIT_MARGIN
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (5, 11)]
    outs = [rng.integers(0, VOCAB, n).tolist() for n in (9, 6)]
    dev = jax.devices()[0]
    rows = [ref._padded(p + o) for p, o in zip(prompts, outs)]
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


def test_generate_takes_the_reference_argmax(tiny):
    """generate() on the latent stack: every generated token is the
    reference's argmax."""
    cfg, params, w = tiny
    eng = _engine(cfg, params)
    prompt = np.random.default_rng(2).integers(0, VOCAB, 11).tolist()
    (out,) = eng.generate([prompt], max_new_tokens=5)
    assert out[:11].tolist() == prompt
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
