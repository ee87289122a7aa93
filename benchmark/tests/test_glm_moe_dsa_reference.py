"""The GLM-5.2 reference (``reference/glm_moe_dsa_decoder.py``) on its own:
the indexer and its picks by hand in numpy float64, the walk in blocks
against the same walk in one piece, and a rehearsal of the cell. The
program against this reference is ``tests/test_glm_moe_dsa.py``; the static
pieces (contract, work counts, the cell's entries) are
``test_sparse_latent_work.py``."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_moe_dsa_decoder as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm-5.2-l5-e16-serve-longdoc-closed16"
CPU = jax.devices("cpu")[0]

HF = {
    "model_type": "glm_moe_dsa", "hidden_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 12,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "index_n_heads": 3, "index_head_dim": 8, "index_topk": 5,
    "indexer_types": ["full", "shared", "full"],
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "rms_norm_eps": 1e-5, "vocab_size": 64}


def _params(seed=0):
    w = ref.Widths.from_hf(HF)
    rng = np.random.default_rng(seed)

    def m(*shape, std=0.3):
        return jnp.asarray(rng.normal(size=shape) * std, jnp.float32)

    def norm(n):
        return {"scale": jnp.asarray(1 + 0.1 * rng.normal(size=n),
                                     jnp.float32)}

    layers = []
    for l in range(3):
        lp = {"ln1": norm(32), "ln2": norm(32), "attn": {
            "wq_a": m(32, 24), "q_norm": norm(24), "wq_b": m(24, 4 * 12),
            "wkv_a": m(32, 20), "kv_norm": norm(16),
            "wkv_b": m(16, 4 * 20), "wo": m(4 * 12, 32)}}
        if w.owners[l]:
            lp["indexer"] = {"wq": m(24, 3 * 8), "wk": m(32, 8),
                             "k_norm": {**norm(8), "bias": m(8, std=0.2)},
                             "ww": m(32, 3)}
        if l == 0:
            lp["mlp"] = {"wg": m(32, 48), "wi": m(32, 48), "wo": m(48, 32)}
        else:
            lp["moe"] = {"router": m(32, 4), "router_bias": m(4, std=0.1),
                         "wg": m(4, 32, 16), "wi": m(4, 32, 16),
                         "wo": m(4, 16, 32)}
            lp["shared"] = {"wg": m(32, 16), "wi": m(32, 16),
                            "wo": m(16, 32)}
        layers.append(lp)
    return w, {"embed": {"tokens": m(64, 32, std=1.0)}, "layers": layers,
               "final_norm": norm(32), "lm_head": m(32, 64)}


def _rope64(x, pos, rope, theta):
    """Rotate-half over the leading ``rope`` dims of x [T, d], float64."""
    half = rope // 2
    ang = pos[:, None] * theta ** (-np.arange(half) / half)
    x1, x2 = x[:, :half], x[:, half:rope]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang),
                           x[:, rope:]], axis=1)


def test_the_indexer_and_its_picks_by_hand():
    """``index_vectors`` + ``picks_mask`` against the equations written out
    in numpy float64, query by query."""
    w, params = _params()
    rng = np.random.default_rng(1)
    t = 23
    hin = rng.normal(size=(t, 32))
    c_q = rng.normal(size=(t, 24))
    ix = jax.tree.map(lambda a: np.asarray(a, np.float64),
                      params["layers"][0]["indexer"])
    pos = np.arange(t, dtype=np.float64)
    q = (c_q @ ix["wq"]).reshape(t, 3, 8)
    q = np.stack([_rope64(q[:, j], pos, 4, 1e4) for j in range(3)], axis=1)
    k = hin @ ix["wk"]
    k = (k - k.mean(-1, keepdims=True)) / np.sqrt(
        k.var(-1, keepdims=True) + 1e-6) * ix["k_norm"]["scale"] \
        + ix["k_norm"]["bias"]
    k = _rope64(k, pos, 4, 1e4)
    weight = (hin @ ix["ww"]) * 3 ** -0.5
    want = np.zeros((t, t), bool)
    for i in range(t):
        score = 8 ** -0.5 * sum(
            weight[i, j] * np.maximum(q[i, j] @ k[:i + 1].T, 0.0)
            for j in range(3))
        want[i, np.argsort(-score, kind="stable")[:min(5, i + 1)]] = True
    with jax.default_matmul_precision("highest"):
        got_q, got_k, got_w = ref.index_vectors(
            jnp.asarray(hin, jnp.float32), jnp.asarray(c_q, jnp.float32),
            params["layers"][0]["indexer"], w)
        np.testing.assert_allclose(got_q, q, atol=2e-5)
        np.testing.assert_allclose(got_k, k, atol=2e-5)
        np.testing.assert_allclose(got_w, weight * 8 ** -0.5, atol=2e-5)
        mask = np.asarray(ref.picks_mask(got_q, got_k, got_w, w))
    assert (mask.sum(-1) == np.minimum(np.arange(t) + 1, 5)).all()
    assert (mask == want).all()
    assert not mask[np.triu_indices(t, 1)].any()


def test_the_walk_in_blocks_is_the_walk_in_one_piece(monkeypatch):
    """Heads two at a time, per-token parts and the indexer 16 positions at
    a time: the same logits as everything at once, and a borrower under its
    owner's picks (layer 1 has no indexer of its own)."""
    w, params = _params()
    toks = np.random.default_rng(2).integers(0, 64, 40).tolist()
    monkeypatch.setattr(ref.latent, "PAD_TO", 48)
    whole = ref.logits_of(w, params, toks, CPU)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 2)
    monkeypatch.setattr(ref, "INDEX_BLOCK", 16)
    monkeypatch.setattr(ref.latent, "PAD_TO", 16)
    monkeypatch.setattr(ref.latent, "QUERY_BLOCK", 16)
    for fn in (ref.picks_mask, ref.attention_heads):
        fn.clear_cache()        # the block sizes are read at trace time
    blocks = ref.logits_of(w, params, toks, CPU)
    for fn in (ref.picks_mask, ref.attention_heads):
        fn.clear_cache()
    assert whole.shape == (40, 64) and np.abs(whole).max() > 0.5
    np.testing.assert_allclose(blocks, whole, atol=2e-4)
    picks = ref.picks_of(w, params, toks, CPU)
    assert len(picks) == 2 and all(
        (m.sum(-1) == np.minimum(np.arange(40) + 1, 5)).all()
        for m, _gap in picks)
    # dropping the picks (every causal key read) is another function
    dense = ref.Widths.from_hf(dict(HF, index_topk=64))
    assert np.abs(ref.logits_of(dense, params, toks, CPU) - whole
                  ).max() > 0.05


def test_argmax_gaps_judges_its_own_greedy_tokens_as_exact():
    w, params = _params()
    prompt = np.random.default_rng(3).integers(0, 64, 17).tolist()
    out = []
    for _ in range(6):
        out.append(int(ref.logits_of(w, params, prompt + out, CPU)[-1]
                       .argmax()))
    gaps = ref.argmax_gaps(w, params, [prompt], [out], CPU)
    assert len(gaps) <= 6 and (gaps == 0.0).all()
    wrong = [(t + 1) % 64 for t in out]
    seen = ref.teacher_forced(w, params, [prompt], [wrong], CPU)
    assert seen["picks"].all() and (seen["gap"] > 0.0).all() and \
        (seen["lead"] > 0.0).all() and len(seen["routing"]) == 6
    # what is judged: routing decided AND (no picks, or the argmax decided)
    margins = ref.UNDECIDED_LOGIT_MARGIN, ref.UNDECIDED_ARGMAX_MARGIN
    try:
        ref.UNDECIDED_LOGIT_MARGIN = 0.0
        ref.UNDECIDED_ARGMAX_MARGIN = float(np.sort(seen["lead"])[3])
        kept = ref.argmax_gaps(w, params, [prompt], [wrong], CPU)
        assert len(kept) == 3 and (kept > 0.0).all()
        ref.UNDECIDED_ARGMAX_MARGIN = 0.0
        assert len(ref.argmax_gaps(w, params, [prompt], [wrong], CPU)) == 6
        # a query under index_topk picks nothing: judged whatever the lead
        ref.UNDECIDED_ARGMAX_MARGIN = 1e9
        dense = ref.Widths.from_hf(dict(HF, index_topk=64))
        assert len(ref.argmax_gaps(dense, params, [prompt], [wrong],
                                   CPU)) == 6
        assert len(ref.argmax_gaps(w, params, [prompt], [wrong], CPU)) == 0
    finally:
        ref.UNDECIDED_LOGIT_MARGIN, ref.UNDECIDED_ARGMAX_MARGIN = margins
    assert abs(ref.loss(w, params, np.asarray([prompt]), CPU)
               - np.log(64)) < 3.0


def test_rehearsal_of_the_glm_cell():
    """The cell end to end at rehearsal widths on the CPU (control flow
    only): a result line whose counter-read metric is there."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--trace", "1", "--seconds", "6",
         "--seed", "3800000011"], capture_output=True, text=True,
        timeout=1500, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    result = lines[-1]
    assert result["device"]["platform"] == "cpu"
    share = result["metrics"]["kv_selected_share"]["value"]
    assert 0.0 < share < 100.0
    checks = next(l for l in lines if l.get("phase") == "checks")
    # (a few seconds at tiny widths judge few tokens or none: the margins
    # are the chip's)
    assert checks["no_failed_request"] and \
        checks["every_request_full_length"] and \
        checks["no_compile_in_window"]
