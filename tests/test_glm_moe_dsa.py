"""GLM-5.2's block (``model_type: glm_moe_dsa``; models/typed_layers.py:
DeepSeek-V3's latent block with DeepSeek-V3.2's sparse-attention indexer in
front of the softmax) against its plain reference (``benchmark/reference/
glm_moe_dsa_decoder.py``) on seeded random weights at a tiny size with the
cell's LAYOUT: five layers ``full shared shared shared full``, one leading
dense layer, a flat sigmoid router over 32 experts of which 2 are held, a
shared expert, 4 index heads of 16 (the first 8 dims rotated, as the heads'
rotary part), and ``index_topk`` 12 — far under the contexts of 30 to 60
tokens, so most queries pick a quarter of their keys, on both sides of the
8-token pages and chunks.

Everything is float32 on the CPU on both sides, so the tolerances are
float32 round-off over a few hundred accumulated terms (logits are of the
order of 1): 2e-4 absolute — PROVIDED both sides pick the same keys, which
they do wherever the k-th and (k+1)-th score are further apart than that
round-off (``PICK_MARGIN``; the seeds below have no closer pair, and the
test of the picks says so set for set). A mechanism of the indexer left out
moves the logits by 1e-2 and more (``test_a_control_is_seen``)."""

import dataclasses
import functools
import json
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
#: the reference's k-th / (k+1)-th score gap over which the float32
#: program's picks must be the reference's, set for set: a thousand times
#: float32's round-off on scores of the order of 0.1
PICK_MARGIN = 1e-4
VOCAB = 128
TOPK = 12

TINY = {
    "model_type": "glm_moe_dsa", "hidden_act": "silu", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "head_dim": 16,
    "qk_head_dim": 24, "attention_bias": False,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "rope_interleave": True, "indexer_rope_interleave": True,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": TOPK,
    "index_topk_freq": 4, "index_skip_topk_offset": 3,
    "index_topk_pattern": None, "index_share_for_mtp_iteration": True,
    "indexer_types": ["full", "shared", "shared", "shared", "full"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "n_shared_experts": 1, "ep_size": 1,
    "expert_share": {"router_experts": 32, "first_expert": 0},
    "num_experts_per_tok": 8, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-05,
    "vocab_size": VOCAB, "tie_word_embeddings": False,
    "max_position_embeddings": 4096}


def reference():
    from benchmark.reference import glm_moe_dsa_decoder
    return glm_moe_dsa_decoder


def build(hf, seed=0):
    """(cfg, params): float32, a wide init so that neither the attention
    nor the index scores are uniform, a router bias and an index key norm
    (scale and bias) that are not their initial values."""
    cfg = dataclasses.replace(config_from_hf(hf), init_std=0.1)
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), cfg.num_layers)
    for lp, key in zip(params["layers"], keys):
        if "moe" in lp:
            lp["moe"]["router_bias"] = 0.3 * jax.random.normal(
                key, lp["moe"]["router_bias"].shape)
        if "indexer" in lp:
            norm = lp["indexer"]["k_norm"]
            a, b = jax.random.split(key)
            norm["scale"] = 1 + 0.2 * jax.random.normal(a, norm["scale"].shape)
            norm["bias"] = 0.2 * jax.random.normal(b, norm["bias"].shape)
    return cfg, params


@pytest.fixture(scope="module")
def tiny():
    cfg, params = build(TINY)
    return cfg, params, reference().Widths.from_hf(TINY)


def program_logits(cfg, params, tokens):
    return np.asarray(transformer.forward(
        cfg, params, jnp.asarray([tokens], jnp.int32))[0])


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


# -- the reader ---------------------------------------------------------------

def test_the_tree_holds_an_indexer_where_the_layer_owns_one(tiny):
    cfg, params, w = tiny
    assert cfg.layer_kinds == (2,) * 5 and cfg.layer_sparse == (0, 1, 1, 1, 1)
    assert cfg.layer_indexer == (1, 0, 0, 0, 1) == w.owners
    assert cfg.picks_keys and cfg.indexer_layers == 2
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == \
        (4, 16, TOPK)
    # the file's head_dim is the NOPE width: a head is nope + rope
    assert (cfg.head_dim, cfg.v_dim, cfg.rope_dim, cfg.latent_dim) == \
        (24, 24, 8, 40)
    assert cfg.attn_scale == 24 ** -0.5 and cfg.rope_theta == 8e6 and \
        cfg.rope_yarn is None
    assert (cfg.router_groups, cfg.routed_scale, cfg.shared_expert_size,
            cfg.experts_held) == (1, 2.5, 32, (0, 2))
    shapes = [jax.tree.map(lambda a: a.shape, lp) for lp in params["layers"]]
    assert [("indexer" in s) for s in shapes] == [True, False, False, False,
                                                  True]
    assert shapes[0]["indexer"] == {
        "wq": (48, 4 * 16), "wk": (64, 16),
        "k_norm": {"scale": (16,), "bias": (16,)}, "ww": (64, 4)}
    assert shapes[0]["attn"]["wq_b"] == (48, 4 * 24)


def test_config_from_hf_reads_the_cells_file_and_the_published_file():
    """The cell's file builds 3,881M parameters (the issue's count, ± 1M),
    and the published file its 78 layers with the published lists."""
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("glm-5.2-l5-e16-serve")
    cfg = model_lib.build_model(conf)
    tree = jax.eval_shape(lambda r: transformer.init_params(cfg, r),
                          jax.random.PRNGKey(0))
    total = sum(a.size for a in jax.tree.leaves(tree))
    assert abs(total - 3881e6) < 1e6, total
    assert cfg.layer_indexer == (1, 0, 0, 0, 1) and cfg.index_topk == 2048
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_dim, cfg.index_heads, cfg.index_head_dim) == \
        (6144, 64, 2048, 512, 192, 64, 256, 32, 128)
    assert cfg.num_experts == 256 and cfg.experts_held == (0, 16)
    published = model_lib.load_published(conf)
    whole = config_from_hf({k: v for k, v in published.items()
                            if k != "source"})
    assert whole.num_layers == 78 and whole.indexer_layers == 21
    assert whole.layer_indexer[:8] == (1, 1, 1, 0, 0, 0, 1, 0)
    assert whole.layer_sparse[:4] == (0, 0, 0, 1) and \
        whole.experts_held is None
    # without the list, the frequency and the offset make the same one
    made = config_from_hf({k: v for k, v in published.items()
                           if k not in ("source", "indexer_types")})
    assert made.layer_indexer == whole.layer_indexer
    assert reference().Widths.from_hf(
        {k: v for k, v in published.items() if k != "indexer_types"}
    ).owners == whole.layer_indexer


REFUSED = {
    "index_topk_pattern": ([1, 2], "index_topk_pattern"),
    "rope_parameters": ({"rope_type": "yarn", "rope_theta": 1e4, "factor": 4},
                        "rope_parameters"),
    "index_n_heads": (0, "index_n_heads"),
    "index_topk": (None, "index_topk"),
    "mlp_layer_types": (["sparse"] * 5, "mlp_layer_types"),
    "indexer_types": (["shared", "full", "full", "full", "full"],
                      "indexer_types"),
    "attention_bias": (True, "attention_bias"),
    "scoring_func": ("softmax", "scoring_func"),
    "q_lora_rank": (None, "q_lora_rank"),
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_what_the_reader_cannot_honour_refuses_by_name(key):
    value, named = REFUSED[key]
    with pytest.raises(ValueError, match=named):
        config_from_hf(dict(TINY, **{key: value}))


def test_the_config_refuses_a_stack_that_cannot_pick(tiny):
    cfg = tiny[0]
    with pytest.raises(ValueError, match="layer_indexer"):
        dataclasses.replace(cfg, layer_indexer=(0, 1, 0, 0, 1))
    with pytest.raises(ValueError, match="layer_indexer"):
        dataclasses.replace(cfg, index_topk=0)
    with pytest.raises(ValueError, match="layer_indexer has 3 entries"):
        dataclasses.replace(cfg, layer_indexer=(1, 0, 1))
    # the prediction module is accepted and not built
    assert config_from_hf(dict(TINY, num_nextn_predict_layers=3)
                          ).num_layers == 5


# -- the selection -------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 3, 7, 12, 40])
def test_topk_mask_is_lax_top_k_with_ties_to_the_lower_position(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(3, 5, 33)).astype(np.float32)
    scores[0, 0, :] = 0.5                     # all tied
    scores[0, 1, 5:25] = -0.25                # a tie across the boundary
    scores[1, 2, ::2] = 0.0
    scores[1, 3, ::3] = -0.0                  # -0.0 sorts under +0.0
    scores[2, 1, 20:] = -np.inf               # fewer visible than k
    scores[2, 2, :] = -np.inf                 # none visible
    got = np.asarray(pa.topk_mask(jnp.asarray(scores), k))
    idx = np.asarray(jax.lax.top_k(jnp.asarray(scores), min(k, 33))[1])
    want = np.zeros_like(got)
    np.put_along_axis(want, idx, True, axis=-1)
    want &= scores > -np.inf
    if k <= 33:
        # (lax.top_k keeps -0.0 and 0.0 apart by position only; the mask
        # orders them by value, so leave that row to the count)
        rows = np.ones(got.shape[:2], bool)
        rows[1, 3] = False
        assert (got[rows] == want[rows]).all()
    assert (got.sum(-1) == np.minimum((scores > -np.inf).sum(-1), k)).all()
    picks, live = pa.topk_picks(jnp.asarray(scores[2]), k)
    assert picks.shape == live.shape == (5, min(k, 33))
    assert (np.asarray(live).sum(-1) ==
            np.minimum((scores[2] > -np.inf).sum(-1), k)).all()


def test_index_scores_in_turns_are_the_scores_at_once():
    """A chunk's queries walk rows and heads four at a time; one query a
    row scores its heads at once: one function."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(3, 5, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 40, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 5, 8)), jnp.float32)
    want = np.einsum("ncjs,ncj->ncs", np.maximum(
        np.einsum("ncjd,nsd->ncjs", q, k), 0.0), w)
    np.testing.assert_allclose(pa.index_scores(q, k, w), want, atol=1e-5)
    np.testing.assert_allclose(pa.index_scores(q[:, :1], k, w[:, :1]),
                               want[:, :1], atol=1e-5)
    # an indexer whose heads the turn does not divide scores them at once
    np.testing.assert_allclose(pa.index_scores(q[:, :, :6], k, w[:, :, :6]),
                               np.einsum("ncjs,ncj->ncs", np.maximum(
                                   np.einsum("ncjd,nsd->ncjs", q[:, :, :6],
                                             k), 0.0), w[:, :, :6]),
                               atol=1e-5)


# -- against the reference -----------------------------------------------------

@pytest.mark.parametrize("n", [9, 29, 57])
def test_forward_matches_the_reference(tiny, n):
    """(a) the uncached forward (the picks as a mask on the expanded
    form): under ``index_topk`` (every key picked), and well past it."""
    cfg, params, w = tiny
    toks = _tokens(n, n)
    ours = program_logits(cfg, params, toks)
    theirs = reference().logits_of(w, params, toks, jax.devices()[0])
    assert ours.shape == theirs.shape == (n, VOCAB)
    assert np.abs(theirs).max() > 0.3                    # not a null model
    assert np.abs(ours - theirs).max() < TOL


def test_the_float32_picks_are_the_references_set_for_set(tiny, monkeypatch):
    """Wherever the reference's k-th and (k+1)-th score stand further apart
    than ``PICK_MARGIN``, the program picks the same SET; both owners."""
    cfg, params, w = tiny
    toks = _tokens(7, 57)
    seen = []
    mask = pa.topk_mask
    monkeypatch.setattr(pa, "topk_mask", lambda s, k: seen.append(
        mask(s, k)) or seen[-1])
    tl.forward_hidden_typed(cfg, params, jnp.asarray([toks], jnp.int32))
    theirs = reference().picks_of(w, params, toks, jax.devices()[0])
    assert len(seen) == len(theirs) == 2
    for ours, (want, gap) in zip(seen, theirs):
        ours = np.asarray(ours)[0]
        decided = gap > PICK_MARGIN
        assert decided.sum() >= 40, decided.sum()
        assert (ours[decided] == want[decided]).all()
        assert (want.sum(-1) == np.minimum(np.arange(57) + 1, TOPK)).all()
        # the two owners do not pick alike (else a borrower's source could
        # not be told)
    assert (np.asarray(seen[0]) != np.asarray(seen[1])).mean() > 0.05


def test_borrowers_read_the_owners_picks(tiny, monkeypatch):
    """Layers 1-3 attend under layer 0's picks — the SAME array — and
    layer 4 under its own; perturbing a borrower's input (its first norm)
    changes its output and not its picks."""
    cfg, params, _w = tiny
    toks = jnp.asarray([_tokens(2, 40)], jnp.int32)
    attend = pa.causal_attention_with_lse

    def run(p):
        used = []
        monkeypatch.setattr(pa, "causal_attention_with_lse",
                            lambda *a, picked=None, **k: used.append(picked)
                            or attend(*a, picked=picked, **k))
        out = tl.forward_hidden_typed(cfg, p, toks)
        monkeypatch.setattr(pa, "causal_attention_with_lse", attend)
        return used, np.asarray(out)

    used, out = run(params)
    assert len(used) == 5 and all(u is used[0] for u in used[1:4])
    assert used[4] is not used[0]
    bent = jax.tree.map(lambda a: a, params)
    bent["layers"][2]["ln1"]["scale"] = \
        bent["layers"][2]["ln1"]["scale"] * 1.5
    used2, out2 = run(bent)
    assert np.abs(out2 - out).max() > 1e-3
    assert (np.asarray(used2[2]) == np.asarray(used[0])).all()


def _engine(cfg, params, **over):
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    conf = dict(dtype="float32", max_sequences=4, num_blocks=40,
                block_size=8, max_seq_len=80, max_batch_tokens=64,
                prefill_chunk=8)
    conf.update(over)
    return RaggedInferenceEngineTPU(cfg, conf, params=params)


@pytest.fixture()
def kernel_interpreted(monkeypatch):
    """``mla_decode`` in interpret mode, for an engine told to use it."""
    monkeypatch.setattr(pa, "mla_decode", functools.partial(
        pa.mla_decode, interpret=True))


def _serve_and_check(eng, params, w, tol=TOL):
    """Three sequences through one engine: a 29-token prompt (four chunks
    of 8: from the second on, queries pick 12 of up to 29 keys, on both
    sides of the chunk's edge and across pages) and 14 decode steps; a
    19-token prompt that joins the first's decode row in ONE split step
    (a chunk row and a row of one query riding along); a 9-token prompt
    (under ``index_topk``: every key picked) that joins later, and, after
    the first sequence is flushed, a 21-token one that takes its SLOT and
    pages over. Every launch's logits against the reference's full
    forward of the same tokens."""
    ref, dev = reference(), jax.devices()[0]
    seqs = {0: _tokens(11, 29), 1: _tokens(12, 19), 2: _tokens(13, 9),
            3: _tokens(14, 21)}
    checked = 0

    def check(out):
        nonlocal checked
        for uid, logits in out.items():
            want = ref.logits_of(w, params, seqs[uid], dev)[-1]
            assert np.abs(np.asarray(logits) - want).max() < tol, \
                (uid, len(seqs[uid]))
            checked += 1

    out = eng.put([0], [seqs[0]])
    check(out)
    joins = {3: 1, 6: 2}
    for step in range(18):
        if step == 14:                   # uid 0 ends; its slot is reused
            eng.flush(0)
            out.pop(0)
            joins[step] = 3
        feed = {uid: int(np.argmax(out[uid])) for uid in out}
        for uid, tok in feed.items():
            seqs[uid].append(tok)
        uids = list(feed)
        toks = [[feed[u]] for u in uids]
        if step in joins:
            uids.append(joins[step])
            toks.append(seqs[joins[step]])
        out = eng.put(uids, toks)
        assert set(out) == set(uids)
        check(out)
    assert len(seqs[0]) == 29 + 14 and checked > 40
    return {fn.__name__ for fn in eng._step_fns.values()}


def test_prefill_in_chunks_then_decode_through_both_pools(tiny):
    """(b) every cache path over the XLA readers: a fresh chunk, chunks
    whose picks fall in the absorbed history AND in the chunk itself, the
    by-index read of decode rows, split steps that mix both, a reused
    slot. The index keys live in a pool of their own, a region an owner."""
    cfg, params, w = tiny
    eng = _engine(cfg, params)
    from deepspeed_tpu.inference.engine_v2 import _pools
    assert set(_pools(eng.arena)) == {"latent", "index"} and \
        not eng.use_pallas
    assert eng.arena["latent"].shape == (5 * 41, 8, 40)
    assert eng.arena["index"].shape == (2 * 41, 8, 16)
    programs = _serve_and_check(eng, params, w)
    assert {"serve_fresh_r1_c8_logits", "serve_split_r2_c8_logits",
            "serve_split_r4_c8_logits", "serve_decode_r1_logits",
            "serve_decode_r2_logits"} <= programs


def test_the_kernel_reads_the_history_under_the_picks(tiny,
                                                      kernel_interpreted):
    """(b) the same walk with ``mla_decode`` (interpret mode) reading a
    chunk's history under the picks' mask over a pool padded to 128
    lanes."""
    cfg, params, w = tiny
    eng = _engine(cfg, params, use_pallas=True, block_size=8)
    assert eng.use_pallas and eng.arena["latent"].shape[-1] == 128
    _serve_and_check(eng, params, w)


def test_the_split_ladder_serves_the_same_logits(tiny):
    """A 4-sequence engine whose full-row split program holds a ladder
    (chunk group + rows of one query below the top rung): both row-group
    forms of the picks in one program."""
    cfg, params, w = tiny
    eng = _engine(cfg, params, max_batch_tokens=32, prefill_chunk=8,
                  max_sequences=4)
    programs = _serve_and_check(eng, params, w)
    caps = eng._token_capacities(4, 8, "split")
    print("ladder", caps, sorted(programs))


@pytest.mark.parametrize("c", [1, 8])
def test_the_picked_kernel_is_the_xla_reader(c):
    """``mla_decode(picked=)`` in interpret mode against
    ``paged_attention_hist_xla(picked=)``: outputs and logsumexps, rows of
    differing history, a row with no pick at all."""
    rng = np.random.default_rng(c)
    n, h, w_, bs, mb, vl = 3, 4, 128, 8, 6, 32
    pool = jnp.asarray(rng.normal(size=(mb * n + 1, bs, w_)) * 0.3,
                       jnp.float32)
    pt = jnp.asarray(rng.permutation(mb * n).reshape(n, mb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(n, c, h, w_)) * 0.3, jnp.float32)
    starts = jnp.asarray([37, 9, 20], jnp.int32)
    picked = rng.random(size=(n, c, mb * bs)) < 0.3
    picked[1, 0] = False
    qcounts = jnp.full((n,), c, jnp.int32)
    want, want_lse = pa.paged_attention_hist_xla(
        q, pool, None, pt, starts, scale=0.2, v_lanes=vl,
        picked=jnp.asarray(picked))
    got, got_lse = pa.mla_decode(q, pool, pt, starts, jnp.zeros_like(starts),
                                 qcounts, v_lanes=vl, scale=0.2,
                                 interpret=True, picked=jnp.asarray(picked))
    some = np.asarray(picked & (np.arange(mb * bs) <
                                np.asarray(starts)[:, None, None])).any(-1)
    assert not some[1, 0] and some.sum() >= n * c - 2
    np.testing.assert_allclose(np.asarray(got)[some], np.asarray(want)[some],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_lse)[some],
                               np.asarray(want_lse)[some], atol=2e-5)
    assert (np.asarray(got_lse)[~some] < -1e29).all()


def test_picked_attention_reads_the_rows_by_token_index():
    """The by-index read against the dense reader under the same picks as
    a mask: the same softmax, from ``K`` gathered rows."""
    rng = np.random.default_rng(3)
    n, h, w_, bs, mb, vl, K = 3, 4, 40, 8, 6, 32, 10
    pool = jnp.asarray(rng.normal(size=(mb * n + 1, bs, w_)) * 0.3,
                       jnp.float32)
    pt = jnp.asarray(rng.permutation(mb * n).reshape(n, mb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(n, 1, h, w_)) * 0.3, jnp.float32)
    starts = jnp.asarray([37, 9, 20], jnp.int32)
    scores = jnp.asarray(rng.normal(size=(n, mb * bs)), jnp.float32)
    scores = jnp.where(jnp.arange(mb * bs)[None] < starts[:, None], scores,
                       -jnp.inf)
    picks, live = pa.topk_picks(scores, K)
    assert (np.asarray(live).sum(-1) == [10, 9, 10]).all()
    got, got_lse = pa.picked_attention(q, pool, pt, picks, live, v_lanes=vl,
                                       scale=0.2)
    want, want_lse = pa.paged_attention_hist_xla(
        q, pool, None, pt, starts, scale=0.2, v_lanes=vl,
        picked=pa.topk_mask(scores, K)[:, None])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_lse, want_lse, atol=2e-5)


def test_under_index_topk_the_stack_is_the_plain_latent_stack(tiny):
    """With every context at most ``index_topk`` the stack equals the same
    weights run as a plain latent stack (``layer_indexer`` None: cell 5's
    code): the uncached forward and the engine's three programs."""
    cfg, params, _w = tiny
    wide = dataclasses.replace(cfg, index_topk=64)
    plain = dataclasses.replace(cfg, layer_indexer=None, index_topk=0,
                                index_heads=0, index_head_dim=0)
    toks = _tokens(5, 37)
    np.testing.assert_allclose(program_logits(wide, params, toks),
                               program_logits(plain, params, toks),
                               atol=TOL)
    # ... and past it they differ
    assert np.abs(program_logits(cfg, params, toks) -
                  program_logits(plain, params, toks)).max() > 50 * TOL
    outs = []
    for model in (wide, plain):
        eng = _engine(model, params)
        out = eng.put([0], [toks[:30]])
        rows = [np.asarray(out[0])]
        for t in toks[30:]:
            rows.append(np.asarray(eng.put([0], [[t]])[0]))
        outs.append(np.stack(rows))
    np.testing.assert_allclose(outs[0], outs[1], atol=TOL)


# -- the controls: each mechanism of the indexer, wrong in one way -------------

def _latest(scores):
    pos = jnp.arange(scores.shape[-1], dtype=jnp.float32)
    return jnp.where(scores > -jnp.inf, pos, -jnp.inf)


def _control(name, cfg, params, monkeypatch):
    """(cfg, params) of the program made wrong in the way ``name`` says,
    the module functions patched where the way is one of theirs."""
    scores, qkw = pa.index_scores, tl.index_qkw
    picks, mask = pa.topk_picks, pa.topk_mask
    if name == "last_keys_for_the_top":
        monkeypatch.setattr(pa, "topk_picks",
                            lambda s, k: picks(_latest(s), k))
        monkeypatch.setattr(pa, "topk_mask",
                            lambda s, k: mask(_latest(s), k))
    elif name == "scores_without_relu":
        monkeypatch.setattr(pa, "index_scores", lambda q, k, w: jnp.einsum(
            "ncjs,ncj->ncs", jnp.einsum("ncjd,nsd->ncjs", q, k), w))
    elif name == "scores_without_head_weights":
        monkeypatch.setattr(pa, "index_scores", lambda q, k, w: scores(
            q, k, jnp.ones_like(w)))
    elif name == "index_keys_without_rope":
        def unrotated(c, p, x, c_q, sin, cos):
            q, _, w = qkw(c, p, x, c_q, sin, cos)
            return q, qkw(c, p, x, c_q, jnp.zeros_like(sin),
                          jnp.ones_like(cos))[1], w
        monkeypatch.setattr(tl, "index_qkw", unrotated)
    elif name == "index_key_norm_left_out":
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            if "indexer" in lp:
                lp["indexer"]["k_norm"] = {
                    "scale": jnp.ones(16), "bias": jnp.zeros(16)}
    elif name == "borrower_scores_for_itself":
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            lp.setdefault("indexer", params["layers"][0]["indexer"])
        cfg = dataclasses.replace(cfg, layer_indexer=(1,) * 5)
    elif name == "owner_borrows_from_the_owner_below":
        cfg = dataclasses.replace(cfg, layer_indexer=(1, 0, 0, 0, 0))
    elif name == "whole_history_read":
        cfg = dataclasses.replace(cfg, layer_indexer=None)
    elif name == "weights_in_float8":
        params = jax.tree.map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, params)
    else:
        raise KeyError(name)
    return cfg, params


CONTROLS = ("last_keys_for_the_top", "scores_without_relu",
            "scores_without_head_weights", "index_keys_without_rope",
            "index_key_norm_left_out", "borrower_scores_for_itself",
            "owner_borrows_from_the_owner_below", "whole_history_read",
            "weights_in_float8")


@pytest.mark.parametrize("name", CONTROLS)
def test_a_control_is_seen_by_the_uncached_forward(tiny, name, monkeypatch):
    """(d) each control moves the uncached forward's logits by far more
    than ``TOL``: the comparisons above fail without the mechanism."""
    cfg, params, w = tiny
    toks = _tokens(21, 57)
    theirs = reference().logits_of(w, params, toks, jax.devices()[0])
    wrong_cfg, wrong_params = _control(name, cfg, params, monkeypatch)
    assert np.abs(program_logits(wrong_cfg, wrong_params, toks) -
                  theirs).max() > 50 * TOL


@pytest.mark.parametrize("name", CONTROLS)
def test_a_control_is_seen_by_the_engine(tiny, name, monkeypatch):
    """(d) the same through the engine: a 41-token prompt in chunks (the
    split program's picks) and eight decode steps (the by-index read)."""
    cfg, params, w = tiny
    toks = _tokens(22, 49)
    theirs = reference().logits_of(w, params, toks, jax.devices()[0])
    wrong_cfg, wrong_params = _control(name, cfg, params, monkeypatch)
    eng = _engine(wrong_cfg, wrong_params)
    out = eng.put([0], [toks[:41]])
    split = np.abs(np.asarray(out[0]) - theirs[40]).max()
    decode = 0.0
    for i in range(41, 49):
        out = eng.put([0], [[toks[i]]])
        decode = max(decode, np.abs(np.asarray(out[0]) - theirs[i]).max())
    assert split > 50 * TOL and decode > 50 * TOL, (split, decode)


# -- the share -----------------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """(c) THE SHARE TEST at this block's router (one group): the 16 shares
    of one sparse layer (2 experts each), every one computed by the
    PROGRAM's expert layer told which experts it holds, sum to the uncut
    REFERENCE's routed part; with the shared expert added ONCE that is the
    uncut reference's whole layer."""
    from deepspeed_tpu.parallel.moe import held_experts_moe_layer
    ref = reference().latent
    uncut = dict(TINY, n_routed_experts=32)
    del uncut["expert_share"]
    cfg, params = build(uncut)
    lp = params["layers"][1]
    moe = lp["moe"]
    assert moe["wg"].shape[0] == 32 and cfg.experts_held is None
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 9, 64)),
                    jnp.float32)
    xf = x.reshape(18, 64)
    uw = reference().Widths.from_hf(uncut)
    routed = np.asarray(ref.experts_part(xf, moe, uw))
    assert np.abs(routed).max() > 0.05
    total = np.zeros_like(routed)
    for first in range(0, 32, 2):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
        share = dict(moe, **{n: moe[n][first:first + 2]
                             for n in ("wg", "wi", "wo")})
        part, _aux = held_experts_moe_layer(share_cfg, share, x)
        part = np.asarray(part).reshape(18, 64)
        share_w = reference().Widths.from_hf(dict(
            uncut, n_routed_experts=2,
            expert_share={"router_experts": 32, "first_expert": first}))
        np.testing.assert_allclose(
            part, np.asarray(ref.experts_part(xf, share, share_w)),
            atol=5e-6)
        total += part
    np.testing.assert_allclose(total, routed, atol=1e-5)
    whole, _margin = ref.sparse_block(
        xf, {"scale": jnp.ones(64)}, moe, lp["shared"], uw)
    normed = np.asarray(xf) / np.sqrt(
        (np.asarray(xf) ** 2).mean(-1, keepdims=True) + 1e-5)
    again = np.asarray(ref.experts_part(jnp.asarray(normed), moe, uw)) + \
        np.asarray(ref._glu(jnp.asarray(normed), lp["shared"]["wg"],
                            lp["shared"]["wi"], lp["shared"]["wo"]))
    np.testing.assert_allclose(np.asarray(whole) - np.asarray(xf), again,
                               atol=1e-5)


# -- spans, counters, what is not built ----------------------------------------

def test_the_dispatch_span_counts_what_was_scored_and_selected(tiny):
    from deepspeed_tpu.telemetry.registry import registry
    from deepspeed_tpu.telemetry.tracer import tracer
    cfg, params, _w = tiny
    eng = _engine(cfg, params)
    before = {n: registry.counter("dispatch/" + n).value
              for n in ("index_tokens_scored", "kv_tokens_selected")}
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        eng.put([0], [_tokens(1, 29)])          # 8 + 8 + 8 + 5
        eng.put([0], [[5]])
        spans = [e["args"] for e in tracer.events()
                 if e["name"] == "serving/dispatch"]
    finally:
        tracer.configure(enabled=False)
    assert [s["program"] for s in spans] == ["fresh", "split", "split",
                                             "split", "decode"]
    # scored: every fed token x the keys it sees, x 2 owners
    pairs = [36, 8 * 8 + 36, 8 * 16 + 36, 5 * 24 + 15, 30]
    assert [s["index_tokens_scored"] for s in spans] == \
        [2 * p for p in pairs]
    # selected: min(context, 12) a row x 5 layers; held: the context
    assert [s["kv_tokens_selected"] for s in spans] == [5 * 8] + [5 * 12] * 4
    assert [s["kv_tokens_latent"] for s in spans] == [8, 16, 24, 29, 30]
    # picked pairs: a token at position p picks min(p + 1, 12)
    assert [s["attn_pairs_selected"] for s in spans] == \
        [36, 9 + 10 + 11 + 12 * 5, 12 * 8, 12 * 5, 12]
    for name, was in before.items():
        assert registry.counter("dispatch/" + name).value - was == \
            sum(s[name] for s in spans)
    # a stack that picks nothing has neither
    plain = dataclasses.replace(cfg, layer_indexer=None)
    from deepspeed_tpu.inference import launch_work
    picks = lambda c: launch_work.picked_work in [
        t.work for t in launch_work.Site(c, 8, 16, False).terms]
    assert picks(cfg) and not picks(plain)


def test_the_new_scopes_are_in_the_programs(tiny):
    """``attn_index`` and ``attn_select`` are vocabulary words and stand in
    the decode and split programs' HLO."""
    from deepspeed_tpu.telemetry import explain
    assert {"attn_index", "attn_select"} <= set(explain.SCOPE_VOCABULARY)
    cfg, params, _w = tiny
    eng = _engine(cfg, params)
    eng.put([0], [_tokens(1, 20)])
    eng.put([0], [[5]])
    for key, fn in eng._step_fns.items():
        if "fresh" in fn.__name__:
            continue        # 8-token chunks under index_topk 12: no scores
        nb, cb = key[0], key[1]
        packed = jnp.zeros((eng._packed_len(nb, cb),), jnp.int32)
        text = fn.lower(eng.params, eng.arena, packed,
                        eng._rng_dev).as_text(debug_info=True)
        assert "attn_index" in text and "attn_select" in text, fn.__name__


def test_copy_on_write_copies_both_pools_pages(tiny):
    cfg, params, _w = tiny
    eng = _engine(cfg, params)
    eng.put([0], [_tokens(4, 8)])
    src = int(eng.state.get_sequence(0).blocks[0]) \
        if hasattr(eng.state, "get_sequence") else 0
    dst = eng.cow_block(src)
    for name, layers in (("latent", 5), ("index", 2)):
        pool = np.asarray(eng.arena[name])
        for l in range(layers):
            assert np.abs(pool[l * 41 + src]).max() > 0
            np.testing.assert_array_equal(pool[l * 41 + src],
                                          pool[l * 41 + dst])

