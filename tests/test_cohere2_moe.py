"""Cohere2-MoE's block (Command A+; ``hf_loader``: ``cohere2_moe``) on the
typed stack: a parallel attention / experts block under ONE LayerNorm,
window layers with interleaved rotary beside position-free full layers at
16 queries a KV head, four averaged shared experts, a tied head over a
vocabulary slice — the program against the benchmark's plain float32
reference (``benchmark/reference/cohere2_moe_decoder.py``) on seeded random
weights at a small size, with controls that are wrong in one way each and
must not pass.

Tolerances (largest |logit difference|, logits of magnitude ~2):
``F32_TOL`` 2e-5 — both sides float32 at ``highest`` precision, readings
3e-7 to 1e-6 (the order of the sums differs: chunks, pages, the merge);
every control reads above 1e-3, a float8 cache and bf16 weights among
them. ``BF16_TOL`` 0.05 — bf16 weights, stream inputs and cache against
the float32 reference, readings 0.002-0.01 at these widths (no control is
held to it: at tiny widths a float8 cache reads 0.006, inside it; the
chip-side check ``tools/chip_check_command_a.py`` holds the controls to
the bf16 program at the published widths)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cohere2_moe_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 2e-5
BF16_TOL = 0.05
CPU = jax.devices("cpu")[0]


def published() -> dict:
    """The source's ``config.json`` (the catalog row, letter for letter)."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "command-a-plus-05-2026.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: one period (window, window,
    window, full), 16 query heads on ONE KV head, a router of 16 with
    experts 4-11 held, window 24."""
    hf = published()
    hf.update(hidden_size=64, num_attention_heads=16, num_key_value_heads=1,
              head_dim=16, intermediate_size=32, num_hidden_layers=4,
              vocab_size=96, num_experts=16, num_experts_per_tok=4,
              sliding_window=24,
              expert_share={"router_experts": 16, "first_expert": 4,
                            "held_experts": 8})
    del hf["rms_norm_eps"]
    hf.update(over)
    return hf


@pytest.fixture(scope="module")
def tiny():
    hf = small()
    cfg = config_from_hf(hf)
    params = tf.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    tokens = np.random.default_rng(3).integers(0, 96, 70)
    want = ref.logits_of(ref.Widths.from_hf(hf), params, tokens, CPU)
    return hf, cfg, params, tokens, want


def uncached(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(tf.forward(cfg, params, jnp.asarray(tokens)[None])
                          [0], np.float32)


# -- the reader ---------------------------------------------------------------

def test_reader_builds_the_published_config():
    cfg = config_from_hf(published())
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim, cfg.vocab_size) == (4096, 32, 128, 8, 128, 262144)
    assert cfg.layer_kinds == (1, 1, 1, 0) * 8 and cfg.sliding_window == 4096
    assert cfg.norm == "layernorm" and not cfg.ln_bias and \
        cfg.norm_eps == 1e-5 and cfg.parallel_block and not cfg.has_ln2
    assert cfg.rope_interleaved and not cfg.full_attn_rope and \
        cfg.rope_theta == 50000.0 and cfg.rope_dim == 128
    assert cfg.kind_rope_theta(1) == 50000.0 and \
        cfg.kind_rope_theta(0) is None
    assert (cfg.num_experts, cfg.num_held_experts, cfg.num_experts_per_tok,
            cfg.intermediate_size) == (128, 128, 8, 4096)
    assert cfg.router_scoring == "sigmoid" and not cfg.router_select_bias \
        and cfg.norm_topk_prob and cfg.routed_scale == 1.0
    assert cfg.shared_expert_size == 4 * 4096 and \
        cfg.shared_experts_averaged == 4 and cfg.tie_embeddings


def test_reader_builds_the_cut_file_and_its_share():
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("command-a-plus-l4-e16-serve")
    assert "rms_norm_eps" not in conf          # published null: left out
    cfg = model_lib.build_model(conf)
    assert cfg.norm_eps == 1e-5 and cfg.layer_kinds == (1, 1, 1, 0)
    # the router keeps its published width; the share is expert_share's
    assert cfg.num_experts == 128 and cfg.experts_held == (0, 16) and \
        cfg.num_held_experts == 16 and cfg.vocab_size == 32768
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    lp = shapes["layers"][3]
    assert set(lp) == {"ln1", "attn", "moe", "shared"} and \
        "lm_head" not in shapes and set(lp["ln1"]) == {"scale"}
    assert lp["attn"]["wq"].shape == (4096, 16384) and \
        lp["attn"]["wk"].shape == (4096, 1024) and \
        lp["moe"]["router"].shape == (4096, 128) and \
        lp["moe"]["wg"].shape == (16, 4096, 4096) and \
        lp["shared"]["wo"].shape == (16384, 4096)
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count - 4_733e6) < 1e6          # ISSUE 37's 4,733M


@pytest.mark.parametrize("key,value", [
    ("use_qk_norm", True), ("attention_bias", True),
    ("first_k_dense_replace", 1), ("use_parallel_block", False),
    ("position_embedding_type", "rope_neox"),
    ("shared_expert_combination_strategy", "sum"),
    ("expert_selection_fn", "softmax"), ("tie_word_embeddings", False),
    ("rope_parameters", {"rope_theta": 50000, "rope_type": "yarn"}),
    ("layer_types", ["chunked_attention"] * 4),
    ("expert_share", {"router_experts": 64, "first_expert": 0,
                      "held_experts": 8}),
])
def test_reader_refuses_by_name_what_is_not_built(key, value):
    name = {"rope_parameters": "rope_type", "layer_types": "layer type",
            "expert_share": "expert_share.router_experts"}.get(key, key)
    with pytest.raises(ValueError, match="cohere2_moe.*" + name):
        config_from_hf(small(**{key: value}))


def test_logit_scale_is_read_as_the_heads_divisor():
    """``logit_scale`` multiplies the logits: the head divides by its
    inverse (``logits_scaling``, the factor Granite's reader sets too); the
    published 1 adds no operation."""
    assert config_from_hf(small()).logits_scaling == 1.0
    cfg = config_from_hf(small(logit_scale=0.25))
    assert cfg.logits_scaling == 4.0
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, cfg.hidden_size))
    base = tf.lm_logits(dataclasses.replace(cfg, logits_scaling=1.0),
                        params, x)
    np.testing.assert_allclose(tf.lm_logits(cfg, params, x), base * 0.25,
                               rtol=1e-6)


def test_stack_refuses_what_it_does_not_build(tiny):
    cfg = tiny[1]
    for bad in (dict(parallel_block_norms=2), dict(norm_bias=True),
                dict(pos_emb="learned")):
        with pytest.raises(NotImplementedError, match="cohere2_moe"):
            tl.init_typed_params(dataclasses.replace(cfg, **bad),
                                 jax.random.PRNGKey(0))


# -- the equations ------------------------------------------------------------

def test_uncached_forward_is_the_reference(tiny):
    _, cfg, params, tokens, want = tiny
    assert np.abs(uncached(cfg, params, tokens) - want).max() < F32_TOL


def _sequential(cfg, params):
    """A sequential block on the same weights: a second norm (scale 1, as
    the first) re-normalises the stream before the experts."""
    layers = [dict(lp, ln2=lp["ln1"]) for lp in params["layers"]]
    return dataclasses.replace(cfg, parallel_block=False), \
        dict(params, layers=layers)


CONTROLS = {
    "sequential_block": _sequential,
    "rotary_on_the_full_kind": lambda cfg, p: (
        dataclasses.replace(cfg, full_attn_rope=True), p),
    "rotate_half_rotary": lambda cfg, p: (
        dataclasses.replace(cfg, rope_interleaved=False), p),
    "shared_experts_summed": lambda cfg, p: (
        dataclasses.replace(cfg, shared_experts_averaged=1), p),
    "window_one_short": lambda cfg, p: (
        dataclasses.replace(cfg, sliding_window=cfg.sliding_window - 1), p),
    "bf16_weights": lambda cfg, p: (cfg, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_program_wrong_in_one_way_is_caught(name, tiny):
    _, cfg, params, tokens, want = tiny
    wrong_cfg, wrong_params = CONTROLS[name](cfg, params)
    diff = np.abs(uncached(wrong_cfg, wrong_params, tokens) - want).max()
    assert diff > 50 * F32_TOL, diff


def _walk(cfg, params, dtype, tokens, prompt_len, **engine):
    """Prefill ``tokens[:prompt_len]`` in chunks of 16, then feed the rest
    a token a step through the paged cache: the logits that predicted
    each position from the prompt's last on."""
    conf = dict(dtype=dtype, max_sequences=2, num_blocks=32, block_size=8,
                max_seq_len=128, max_batch_tokens=32, prefill_chunk=16)
    conf.update(engine)
    eng = RaggedInferenceEngineTPU(cfg, conf, params=params)
    out = eng.put([0], [list(tokens[:prompt_len])])
    rows = [np.asarray(out[0], np.float32)]
    for t in tokens[prompt_len:]:
        rows.append(np.asarray(eng.put([0], [[int(t)]])[0], np.float32))
    return np.stack(rows), eng


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_chunked_prefill_and_decode_through_the_cache(dtype, tol, tiny):
    """60 prompt tokens in four chunks (the later chunks' live queries see
    chunk + history across the window's edge, 24), then ten decode steps
    at contexts of 60-69."""
    _, cfg, params, tokens, want = tiny
    with jax.default_matmul_precision("highest"):
        got, eng = _walk(cfg, params, dtype, tokens, 60)
    names = {fn.__name__.split("_")[1] for fn in eng._step_fns.values()}
    assert names == {"fresh", "split", "decode"}
    assert np.abs(got - want[59:]).max() < tol


def test_float8_cache_is_caught(tiny, monkeypatch):
    _, cfg, params, tokens, want = tiny
    write_kv = pa.write_kv

    def float8(ak, av, k, v, *a, **kw):
        k, v = (t.astype(jnp.float8_e4m3fn).astype(t.dtype) for t in (k, v))
        return write_kv(ak, av, k, v, *a, **kw)

    monkeypatch.setattr(pa, "write_kv", float8)
    with jax.default_matmul_precision("highest"):
        got, _ = _walk(cfg, params, "float32", tokens, 60)
    assert np.abs(got - want[59:]).max() > 50 * F32_TOL


def test_generate_serves_it(tiny):
    _, cfg, params, tokens, _ = tiny
    eng = RaggedInferenceEngineTPU(cfg, dict(
        dtype="float32", max_sequences=2, num_blocks=32, block_size=8,
        max_seq_len=128, max_batch_tokens=32, prefill_chunk=16),
        params=params)
    (out,) = eng.generate([tokens[:40].tolist()], max_new_tokens=6)
    assert len(out) == 46
    logits = uncached(cfg, params, out[:-1])
    assert out[40:].tolist() == logits[39:].argmax(-1).tolist()


def test_interleaved_rotary_pairs_neighbours():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)[None] + 3
    cfg = tf.DecoderConfig(hidden_size=16, num_heads=2, pos_emb="rope",
                           rope_theta=50000.0)
    sin, cos = tf.rope_table(cfg, pos)
    got = tf.apply_rope(x, sin, cos, interleaved=True)
    ang = np.asarray(pos, np.float32)[0][:, None] * \
        50000.0 ** (-np.arange(0, 8, 2) / 8)
    want = np.array(x)
    for i in range(4):
        a, b = np.asarray(x[..., 2 * i]), np.asarray(x[..., 2 * i + 1])
        c, s = np.cos(ang[:, i])[None, :, None], np.sin(ang[:, i])[None, :,
                                                                   None]
        want[..., 2 * i], want[..., 2 * i + 1] = a * c - b * s, b * c + a * s
    assert np.abs(np.asarray(got) - want).max() < 1e-6
    # the same rotation under another pairing is another function
    assert np.abs(np.asarray(tf.apply_rope(x, sin, cos)) - want).max() > 0.1


@pytest.mark.parametrize("types,moves", [
    (["full_attention"] * 2, False), (["sliding_attention"] * 2, True)])
def test_only_window_layers_know_positions(types, moves):
    """Shift every position by 5: a stack of full layers (no positional
    term) cannot tell, a stack of window layers can."""
    hf = small(num_hidden_layers=2, layer_types=types, sliding_window=64)
    cfg = config_from_hf(hf)
    params = tf.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 96, (1, 20)))
    at = jnp.arange(20)[None]
    a = tl.forward_hidden_typed(cfg, params, tokens, positions=at)
    b = tl.forward_hidden_typed(cfg, params, tokens, positions=at * 3 + 5)
    assert (float(jnp.abs(a - b).max()) > 1e-3) == moves


def test_the_norm_is_mean_centred_and_the_head_is_tied(tiny):
    _, cfg, params, tokens, want = tiny
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 64)) + 4.0
    h = tf._norm(cfg, {"scale": jnp.ones(64)}, x)
    assert float(jnp.abs(h.mean(-1)).max()) < 1e-5 and \
        abs(float((h ** 2).mean()) - 1.0) < 1e-3
    assert "lm_head" not in params and want.shape == (70, 96)
    with jax.default_matmul_precision("highest"):
        hidden = tf.forward_hidden(cfg, params, jnp.asarray(tokens)[None])[0]
        tied = hidden[0] @ params["embed"]["tokens"].T
    assert np.abs(np.asarray(tied) - want).max() < F32_TOL


# -- the share ----------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two of the router's 16 experts each. Their routed
    parts (the program's ``held_experts_moe_layer`` on each share's slice of
    the weights), with attention and the averaged shared experts counted
    ONCE, add up to the reference's uncut layer."""
    hf = small(expert_share=None, num_hidden_layers=1)
    cfg = config_from_hf(hf)
    w = ref.Widths.from_hf(hf)
    assert w.held_experts == 16 and cfg.experts_held is None
    lp = tf.init_params(cfg, jax.random.PRNGKey(11), jnp.float32)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(12), (512, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._layer(x, lp, w, 1)
        hin = ref._layer_norm(x, lp["ln1"]["scale"], w.eps)
        routed = jnp.zeros_like(x)
        for chip in range(8):
            share = {"router_experts": 16, "first_expert": 2 * chip,
                     "held_experts": 2}
            cfg_i = config_from_hf(small(expert_share=share,
                                         num_hidden_layers=1))
            mine = slice(2 * chip, 2 * chip + 2)
            p_i = dict(lp["moe"], **{k: lp["moe"][k][mine]
                                     for k in ("wg", "wi", "wo")})
            part = moe.held_experts_moe_layer(cfg_i, p_i, hin[None])[0][0]
            w_i = ref.Widths.from_hf(small(expert_share=share,
                                           num_hidden_layers=1))
            assert float(jnp.abs(
                part - ref.experts_part(hin, p_i, w_i)).max()) < 1e-5
            routed = routed + part
        # one chip's layer less its routed part: x + attention + shared
        first = {"router_experts": 16, "first_expert": 0, "held_experts": 2}
        w_0 = ref.Widths.from_hf(small(expert_share=first,
                                       num_hidden_layers=1))
        lp_0 = dict(lp, moe=dict(lp["moe"], **{
            k: lp["moe"][k][:2] for k in ("wg", "wi", "wo")}))
        once = ref._layer(x, lp_0, w_0, 1)[0] - \
            ref.experts_part(hin, lp_0["moe"], w_0)
    assert float(jnp.abs(once + routed - whole).max()) < 1e-4
    assert float(jnp.abs(routed).max()) > 1e-3      # ... and not vacuously


# -- the kernel, the counters -------------------------------------------------

def test_paged_kernel_at_16_queries_a_head_across_the_window_edge():
    """``paged_attn_lse(window=)`` in interpret mode: 32 query heads on 2
    KV heads (16 queries a KV head), chunks of 8 LIVE queries whose
    windows (24) straddle chunk and history, over pages of 8: against the
    XLA history reader."""
    n, c, h, kvh, d, bs, mb, window = 4, 8, 32, 2, 128, 8, 8, 24
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((n, c, h, d)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((n * mb + 1, bs, kvh * d)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((n * mb + 1, bs, kvh * d)),
                         jnp.float32)
    pt = jnp.asarray(rng.permutation(n * mb).reshape(n, mb), jnp.int32)
    starts = jnp.asarray([40, 20, 0, 23], jnp.int32)
    got, lse = pa.paged_attention_with_lse(
        q, pool_k, pool_v, pt, starts, jnp.zeros_like(starts),
        interpret=True, window=window)
    want, want_lse = pa.paged_attention_hist_xla(
        q, pool_k, pool_v, pt, starts, window=window)
    live = np.asarray(want_lse) > -1e29
    assert live[0].all() and live[1].all() and not live[2].any()
    # (row 0's first query sees history 17-39, its last only 24-39)
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < 2e-5
    assert np.abs(np.asarray(lse) - np.asarray(want_lse))[live].max() < 2e-5
    assert (np.asarray(lse)[~live] < -1e29).all()
    unwindowed, _ = pa.paged_attention_with_lse(
        q, pool_k, pool_v, pt, starts, jnp.zeros_like(starts),
        interpret=True)
    assert np.abs(np.asarray(unwindowed) - np.asarray(want))[0].max() > 1e-2


def test_dispatch_counts_the_live_pairs(tiny):
    """``attn_pairs_*`` of the ``serving/dispatch`` span against a count
    of the mask itself."""
    from deepspeed_tpu.inference import launch_work
    _, cfg, _, _, _ = tiny
    site = launch_work.Site(cfg, 8, 16, False)
    starts, fed = np.array([40, 0, 20, 63]), np.array([16, 16, 7, 1])
    launch = launch_work.Launch("split", 16, False, 64, int(fed.sum()),
                                starts.astype(np.int32),
                                fed.astype(np.int32))
    want = dict.fromkeys(("attn_pairs_full", "attn_pairs_window",
                          "attn_pairs_own_full", "attn_pairs_own_window"), 0)
    for s, n in zip(starts, fed):
        for qpos in range(s, s + n):
            for kpos in range(qpos + 1):
                own = kpos >= s
                want["attn_pairs_full"] += 1
                want["attn_pairs_own_full"] += own
                if qpos - kpos < cfg.sliding_window:
                    want["attn_pairs_window"] += 1
                    want["attn_pairs_own_window"] += own
    assert launch_work.attn_pairs(site, launch) == want
    work = launch_work.launch_work(site, "split",
                                   launch_work.Form(4, (), 64, 4, 64), 16,
                                   launch.start, launch.fed)
    assert {k: work[k] for k in want} == want
    assert (work["kv_tokens_window_held"], work["kv_tokens_full"]) == \
        (int((starts + fed).sum()),) * 2
    # a stack without a window kind counts none
    dense = launch_work.Site(
        tf.DecoderConfig(vocab_size=64, hidden_size=32, num_layers=1,
                         num_heads=2, pos_emb="rope", use_bias=False),
        8, 4, False)
    assert not dense.terms and not set(want) & set(launch_work.launch_work(
        dense, "split", launch_work.Form(4, (), 64, 4, 64), 16,
        launch.start, launch.fed))
