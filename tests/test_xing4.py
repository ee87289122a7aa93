"""Xing4.0's stack (XingChen-AGI Xing4.0-29B-A4B; ``hf_loader``: ``xing4_0``)
on the typed stack: DeepSeek-V3's latent attention and sigmoid-routed experts
on a residual stream FOUR hidden states wide, every sublayer reading a
learned, token-dependent mix of them and writing back through a doubly
stochastic 4 x 4 matrix (manifold-constrained hyper-connections: 20
Sinkhorn rounds a map). The program against the benchmark's plain float32
reference (``benchmark/reference/xing4_decoder.py``) on seeded random
weights at a small size (``hc_mult`` 4 and 20 rounds as published), with
programs that are wrong in one way each and must not pass.

Tolerances (largest |logit difference|; the logits spread by 0.2 at this
size). ``F32_TOL`` 1e-5 — both sides float32 at ``highest`` precision; the
two differ in the ORDER of float32 sums alone (the program's maps take the
``phi`` product a stream and scale it after, its rounds run on sixteen
arrays with the tokens on the lanes, its experts are dispatched), readings
3e-7 (uncached) to 1e-6 (through the cache). Every wrong program reads
above 100x that (the least: 2 rounds for 20).
``BF16_TOL`` 0.06 — bf16 weights, sublayer inputs and latent cache against
the float32 reference (the STREAM and the maps stay float32 in both):
rounding alone reads 0.002-0.005 on most rows, and a row past a position
where bf16 flipped one of the top-2-of-8 experts 0.01-0.03; what tells the
precisions apart is ``F32_TOL``'s bf16-weights control, this is a sanity
bound."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4_decoder as ref
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.models.hf_loader import config_from_hf, config_to_hf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
BF16_TOL = 0.06
CPU = jax.devices("cpu")[0]
VOCAB = 96


def published() -> dict:
    """The source's ``config.json`` (the catalog row, key for key)."""
    with open(os.path.join(REPO, "benchmark", "configs", "published",
                           "xing4.0-29b-a4b.json")) as fh:
        hf = json.load(fh)
    hf.pop("source")
    return hf


def small(**over) -> dict:
    """The published keys at a small size: hidden 128, four heads of 32
    nope + 16 rope, one dense layer and two of 8 experts, 2 a token; the
    residual path AS PUBLISHED (4 hidden states, 20 rounds, the clamp)."""
    hf = published()
    hf.update(hidden_size=128, num_hidden_layers=3, first_k_dense_replace=1,
              num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
              kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
              v_head_dim=32, intermediate_size=192, moe_intermediate_size=48,
              n_routed_experts=8, num_experts_per_tok=2, vocab_size=VOCAB)
    hf.update(over)
    return hf


def randomised(params, seed: int = 5):
    """What the init makes vacuous, made to count: the three ``α`` of every
    map (ones at init) and the pre / post biases (zero at init) drawn."""
    rng = np.random.default_rng(seed)

    def maps(hc):
        n2 = hc["base"].shape[0]
        base = np.asarray(hc["base"]).copy()
        n = int(round((1 + n2) ** 0.5)) - 1
        base[:2 * n] = rng.normal(0, 0.5, 2 * n)
        return dict(hc, base=jnp.asarray(base, jnp.float32),
                    scale=jnp.asarray(rng.uniform(0.6, 1.6, 3), jnp.float32))

    return dict(params, layers=[
        dict(lp, hc_attn=maps(lp["hc_attn"]), hc_ffn=maps(lp["hc_ffn"]))
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
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.head_dim,
            cfg.v_dim, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.vocab_size,
            cfg.intermediate_size, cfg.dense_intermediate_size) == \
        (3584, 40, 32, 192, 128, 768, 512, 131072, 1024, 9216)
    assert cfg.layer_kinds == (2,) * 40 and \
        cfg.layer_sparse == (0, 0) + (1,) * 38
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.router_scoring,
            cfg.router_select_bias, cfg.router_groups,
            cfg.router_groups_kept, cfg.routed_scale,
            cfg.shared_expert_size, cfg.experts_held) == \
        (64, 4, "sigmoid", True, 1, 1, 2.0, 1024, None)
    # ``rope_scaling.type`` (not ``rope_type``) names YaRN here
    assert cfg.rope_yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0) and \
        cfg.rope_theta == 10000.0 and not cfg.tie_embeddings
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    shapes = jax.eval_shape(lambda r: tf.init_params(cfg, r),
                            jax.random.PRNGKey(0))
    sparse = shapes["layers"][2]
    assert sparse["hc_attn"]["phi"].shape == \
        sparse["hc_ffn"]["phi"].shape == (4 * 3584, 24)
    assert sparse["hc_ffn"]["base"].shape == (24,) and \
        sparse["hc_ffn"]["scale"].shape == (3,)
    assert sparse["moe"]["wi"].shape == (64, 3584, 1024)
    # the whole model by the published widths: the name's 29B-A4B
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 29.4e9 < total < 29.6e9


def test_reader_builds_the_file_whole():
    """The benchmark's configuration through the harness's own path: every
    width as published, six layers, ALL 64 experts and the whole
    vocabulary."""
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("xing4.0-29b-a4b-l6-serve")
    cfg = model_lib.build_model(conf)
    assert cfg.layer_sparse == (0, 1, 1, 1, 1, 1) and cfg.hc_mult == 4 and \
        cfg.num_held_experts == 64 and cfg.vocab_size == 131072
    assert "expert_share" not in conf and \
        conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    tiny_cfg = model_lib.build_model(conf, rehearse=True)
    assert (tiny_cfg.hc_mult, tiny_cfg.hc_sinkhorn_iters) == (4, 20)


@pytest.mark.parametrize("over,named", [
    (dict(model_type="deepseek_v3"), "deepseek_v3.*hc_mult=4"),
    (dict(hc_mult=0), "xing4_0.*hc_mult=0"),
    (dict(hc_mult=2.5), "xing4_0.*hc_mult=2.5"),
    (dict(hc_sinkhorn_iters=None), "xing4_0.*hc_sinkhorn_iters=None"),
    (dict(hc_sinkhorn_iters=0), "xing4_0.*hc_sinkhorn_iters=0"),
    (dict(ep_size=8), "ep_size"),
    (dict(rope_scaling={"type": "linear", "factor": 4}), "rope_scaling")])
def test_reader_refuses_by_name_what_is_not_built(over, named):
    hf = small(**over)
    if over.get("hc_sinkhorn_iters", 1) is None:
        del hf["hc_sinkhorn_iters"]         # the key missing, not null
    with pytest.raises(ValueError, match=named):
        config_from_hf(hf)


def test_a_wide_stream_needs_sequential_two_part_layers():
    cfg = config_from_hf(small())
    with pytest.raises(ValueError, match="hc_mult"):
        dataclasses.replace(cfg, parallel_block=True,
                            parallel_block_norms=1)
    with pytest.raises(ValueError, match="hc_mult"):
        dataclasses.replace(cfg, hc_sinkhorn_iters=0)
    with pytest.raises(NotImplementedError, match="xing4_0"):
        config_to_hf(cfg)


# -- one hidden state a token: today's stack, untouched ---------------------------

def test_one_hidden_state_is_the_parent_familys_stack_bit_for_bit():
    """``hc_mult`` 1 under ``xing4_0`` builds ``deepseek_v3``'s
    configuration and tree, and the four stream functions ARE ``x``, ``x``,
    ``x + y`` and ``x``: the jaxpr of the forward is, equation for
    equation, the one with those literals written in their place — no
    operation is traced for a stream of one hidden state — and the logits
    are the same bits."""
    hf = small(hc_mult=1)
    parent = {k: v for k, v in small(model_type="deepseek_v3").items()
              if not k.startswith(("hc_", "mhc_"))}
    cfg, cfg_parent = config_from_hf(hf), config_from_hf(parent)
    assert cfg == cfg_parent and cfg.hc_mult == 1
    params = tf.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    assert not any(k.startswith("hc_") for lp in params["layers"]
                   for k in lp)
    x, y = jnp.ones((3, 8)), jnp.ones((3, 8))
    assert tl.stream_open(cfg, x) is x and tl.stream_close(cfg, x) is x
    assert tl.stream_read(cfg, None, x) == (x, None)
    assert str(jax.make_jaxpr(lambda a, b: tl.stream_write(cfg, None, a, b))
               (x, y)) == str(jax.make_jaxpr(lambda a, b: a + b)(x, y))
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, 48))[
        None]
    forward = lambda p: tf.forward(cfg, p, tokens)
    now = jax.make_jaxpr(forward)(params)
    got = np.asarray(forward(params))
    literal = {"stream_open": lambda c, a: a, "stream_close": lambda c, a: a,
               "stream_read": lambda c, hc, a: (a, None),
               "stream_write": lambda c, m, a, b: a + b}
    saved = {name: getattr(tl, name) for name in literal}
    try:
        for name, fn in literal.items():
            setattr(tl, name, fn)
        before = jax.make_jaxpr(forward)(params)
        want = np.asarray(forward(params))
    finally:
        for name, fn in saved.items():
            setattr(tl, name, fn)
    assert str(now) == str(before)
    assert np.array_equal(got, want)


# -- the equations --------------------------------------------------------------

def test_uncached_forward_is_the_reference(tiny):
    _, cfg, params, tokens, want = tiny
    assert np.abs(uncached(cfg, params, tokens) - want).max() < F32_TOL


def _stream(cfg, seed=0, tokens=40):
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.hc_mult)
    return tuple(0.05 * jax.random.normal(k, (tokens, cfg.hidden_size),
                                          jnp.float32) for k in keys)


@pytest.mark.parametrize("rounds,doubly_stochastic", [(20, True), (2, False)])
def test_h_res_is_doubly_stochastic_after_the_rounds(tiny, rounds,
                                                      doubly_stochastic):
    """``H_res``'s rows AND columns sum to 1 within 1e-4 after the
    published 20 rounds, and NOT after 2 (the rows do: a round ends on
    them; the columns are what the rounds are for)."""
    _, cfg, params, _, _ = tiny
    cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=rounds)
    pre, post, res = tl.hc_maps(cfg, params["layers"][1]["hc_ffn"],
                                _stream(cfg))
    res = np.asarray(jnp.concatenate(
        [jnp.concatenate(row, axis=-1)[:, None] for row in res], axis=1))
    assert res.shape == (40, 4, 4) and (res > 0).all()
    off = max(np.abs(res.sum(axis=1) - 1).max(),
              np.abs(res.sum(axis=2) - 1).max())
    assert (off < 1e-4) == doubly_stochastic, off
    assert np.abs(res.sum(axis=2) - 1).max() < 1e-4
    pre, post = (np.asarray(jnp.concatenate(t, axis=-1))
                 for t in (pre, post))
    assert ((0 < pre) & (pre < 1)).all() and ((0 < post) & (post < 2)).all()


def test_the_maps_are_the_references(tiny):
    """The program's maps (sixteen arrays, the tokens on the lanes) against
    the reference's ``[T, 4, 4]`` writing, a map at a time."""
    hf, cfg, params, _, _ = tiny
    w = ref.Widths.from_hf(hf)
    x = _stream(cfg, seed=2)
    hc = params["layers"][2]["hc_attn"]
    pre, post, res = tl.hc_maps(cfg, hc, x)
    want = ref.maps(jnp.stack(x, axis=1), hc, w)
    got = (jnp.concatenate(pre, -1), jnp.concatenate(post, -1),
           jnp.stack([jnp.concatenate(row, -1) for row in res], axis=1))
    for g, t in zip(got, want):
        assert np.abs(np.asarray(g) - np.asarray(t)).max() < 2e-6


def _in_maps(params, **leaves):
    return dict(params, layers=[
        dict(lp, **{part: dict(lp[part], **{k: f(lp[part][k])
                                            for k, f in leaves.items()})
                    for part in ("hc_attn", "hc_ffn")})
        for lp in params["layers"]])


#: name → (cfg, params, monkeypatch) → (cfg, params): programs that are
#: wrong in ONE way each
CONTROLS = {
    "dynamic_term_zeroed": lambda cfg, p, mp: (
        cfg, _in_maps(p, scale=jnp.zeros_like)),
    "two_rounds_for_twenty": lambda cfg, p, mp: (
        dataclasses.replace(cfg, hc_sinkhorn_iters=2), p),
    "norm_over_the_stream_left_out": lambda cfg, p, mp: (
        mp.setattr(tl, "_hc_rms_factor", lambda c, x: jnp.ones(
            x[0].shape[:-1] + (1,), jnp.float32)), (cfg, p))[1],
    "h_post_without_its_factor_2": lambda cfg, p, mp: (
        mp.setattr(tl, "HC_POST_GAIN", 1.0), (cfg, p))[1],
    "rows_before_columns": lambda cfg, p, mp: (
        cfg, _in_maps(p, phi=_res_transposed, base=_res_transposed)),
    "bf16_weights": lambda cfg, p, mp: (cfg, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)),
}


def _res_transposed(a):
    """``m_res`` read column-major: ``H_res`` transposed (the rounds then
    normalise rows first, and the write-back mixes the other way)."""
    n = int(round((1 + a.shape[-1]) ** 0.5)) - 1
    res = a[..., 2 * n:].reshape(a.shape[:-1] + (n, n))
    return jnp.concatenate([a[..., :2 * n], jnp.swapaxes(res, -1, -2).reshape(
        a.shape[:-1] + (n * n,))], axis=-1)


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_program_wrong_in_one_way_is_caught(name, tiny, monkeypatch):
    _, cfg, params, tokens, want = tiny
    wrong_cfg, wrong_params = CONTROLS[name](cfg, params, monkeypatch)
    diff = np.abs(uncached(wrong_cfg, wrong_params, tokens) - want).max()
    assert diff > 100 * F32_TOL, diff


def test_the_phi_products_count_as_matmul_parameters():
    w = ref.Widths.from_hf(published())
    n, c = 4, 3584
    assert ref.matmul_params_per_token(w) - \
        ref.v3.matmul_params_per_token(w.block) == 40 * 2 * n * c * 24
    # 3.93B active a token: the name's A4B
    assert 3.92e9 < ref.matmul_params_per_token(w) < 3.94e9


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


@pytest.mark.parametrize("prompt_len", [1, 127, 129, 300])
def test_prefill_then_decode_is_the_reference(prompt_len, tiny):
    """The four-wide stream through the fresh, the split and the decode
    programs: 300 tokens are a fresh chunk and two split ones — the
    EXPANDED chunk joins an ABSORBED history of the row's own through
    ``merge_attention`` — then six decode steps over the latent pool.
    LOGITS, not tokens."""
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params)
    with jax.default_matmul_precision("highest"):
        got = _walk(eng, tokens[:prompt_len + 6], prompt_len)
    assert np.abs(got - want[prompt_len - 1:prompt_len + 6]).max() < F32_TOL


def test_rows_of_both_forms_in_one_launch(tiny):
    """A chunk row and decoding rows packed into ONE split launch: every
    token slot solves its own maps, whatever its row's width."""
    hf, cfg, params, tokens, want = tiny
    other = np.random.default_rng(11).integers(0, VOCAB, 60)
    full = ref.logits_of(ref.Widths.from_hf(hf), params, other, CPU)
    eng = engine(cfg, params)
    with jax.default_matmul_precision("highest"):
        _walk(eng, other[:41], 40, uid=1)           # a decoding row
        eng.put([0], [list(tokens[:128])])
        out = eng.put([0, 1], [list(tokens[128:200]), [int(other[41])]])
    assert np.abs(np.asarray(out[0]) - want[199]).max() < F32_TOL
    assert np.abs(np.asarray(out[1]) - full[41]).max() < F32_TOL


def test_bf16_serving(tiny):
    _, cfg, params, tokens, want = tiny
    eng = engine(cfg, params, dtype="bfloat16")
    got = _walk(eng, tokens[:140], 130)
    assert np.abs(got - want[129:140]).max() < BF16_TOL


def test_the_new_scopes_are_in_the_programs(tiny):
    """``hc_maps`` and ``hc_mix`` are vocabulary words, and
    ``compile_monitor.scopes`` finds them in the split and the decode
    programs beside the block's own."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import explain
    assert {"hc_maps", "hc_mix"} <= set(explain.SCOPE_VOCABULARY)
    _, cfg, params, _, _ = tiny
    eng = engine(cfg, params, max_sequences=4)
    for cb, fresh in ((128, "split"), (1, False)):
        name = eng._step_fn(4, cb, None, fresh).__name__
        found = {e["scope"] for e in
                 telemetry.compile_monitor.scopes(name).values()}
        assert {"hc_maps", "hc_mix", "moe", "mlp", "attn_latent",
                "attn_qkv"} <= found, (name, found)


# -- the counter ------------------------------------------------------------------

@pytest.mark.parametrize("launch,solves", [
    (("decode", 2, 4, 1, None), 4 * 12),
    (("split", 3, 4, 128, 512), 512 * 12),
    (("split", 3, 8, 128, None), 8 * 128 * 12),    # the row form
    (("fresh", 2, 4, 128, 256), 256 * 12)])
def test_dispatch_counts_the_maps_a_launch_solves(launch, solves):
    """``dispatch/hc_maps`` and the span's ``hc_maps``: token slots x 2
    sublayers x 6 layers."""
    from deepspeed_tpu.telemetry.registry import registry
    from tests.test_granitemoehybrid import _launched
    counter = registry.counter("dispatch/hc_maps")
    before = counter.value
    work = _launched(config_from_hf(small(num_hidden_layers=6)), *launch)
    assert work["hc_maps"] == solves == counter.value - before


def test_a_stream_of_one_hidden_state_counts_no_maps():
    from deepspeed_tpu.telemetry.registry import registry
    from tests.test_granitemoehybrid import _launched
    counter = registry.counter("dispatch/hc_maps")
    before = counter.value
    work = _launched(config_from_hf(small(hc_mult=1)), "split", 3, 8, 128,
                     512)
    assert "hc_maps" not in work and counter.value == before

