"""The Xing4.0 reference (``reference/xing4_decoder.py``) on its own: the
contract, the residual path against a second writing of its equations — a
loop over tokens in float64, the 4 x 4 matrices by index —, what
``argmax_gaps`` judges, the two new readers on a recorded run, and the
cell's entries. After ``test_lfm2_moe_reference.py``; the
program against this reference is ``tests/test_xing4.py``."""

import importlib.util
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops, model, stats
from benchmark.trace import reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "xing4.0-29b-a4b-l6-serve"
CELL = "xing4.0-29b-a4b-l6-serve-chat-closed64"
CPU = jax.devices("cpu")[0]
NEW_READERS = ("hc_ms_per_step", "hc_maps_ms_per_step")


def _ref_and_widths():
    conf = model.load_config(CONFIG)
    ref = model.load_reference(conf)
    return ref, ref.Widths.from_hf(model.published_keys(conf))


def _reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_xing4_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("xing4_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert (w.streams, w.rounds, w.hc_eps, w.clamp) == \
        (4, 20, 1e-6, (-30.0, 30.0))
    b = w.block
    assert (b.layers, b.sparse, b.hidden, b.heads, b.nope, b.rope, b.v_head,
            b.held_experts, b.router_experts, b.first_expert, b.per_token,
            b.groups, b.routed_scale, b.vocab) == \
        (6, (0, 1, 1, 1, 1, 1), 3584, 32, 128, 64, 128, 64, 64, 0, 4, 1,
         2.0, 131072)
    # ``rope_scaling.type`` names YaRN; theta 10000: the range [10, 23]
    assert b.yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    f = ref.v3.rope_frequencies(b)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(f[:11], plain[:11]) and \
        np.allclose(f[23:], plain[23:] / 64) and \
        (f[11:23] < plain[11:23]).all() and (f[11:23] > plain[11:23] / 64
                                              ).all()
    assert abs(ref.v3.score_scale(b) - 0.14468) < 1e-5
    # the phi products count, the 4 x 4 ones do not
    assert ref.matmul_params_per_token(w) == \
        ref.v3.matmul_params_per_token(b) + 6 * 2 * 4 * 3584 * 24
    import inspect
    assert not [line for line in inspect.getsource(ref).splitlines()
                if line.startswith(("import deepspeed_tpu",
                                    "from deepspeed_tpu"))]


def _tiny(ref, **over):
    hf = model.published_keys(model.load_config(CONFIG))
    hf.update(hidden_size=32, num_hidden_layers=2, first_k_dense_replace=1,
              num_attention_heads=2, num_key_value_heads=2, q_lora_rank=12,
              kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
              v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
              n_routed_experts=4, num_experts_per_tok=2, vocab_size=40)
    hf.update(over)
    return ref.Widths.from_hf(hf)


def _tree(w, seed=0):
    rng = np.random.default_rng(seed)
    b, n = w.block, w.streams
    d, H = b.hidden, b.heads

    def mat(*shape, std=0.3):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    def norm(k):
        return {"scale": jnp.asarray(rng.uniform(0.5, 1.5, k), jnp.float32)}

    def maps():
        return {"phi": mat(n * d, n * n + 2 * n, std=0.03),
                "base": mat(n * n + 2 * n, std=0.5),
                "scale": jnp.asarray(rng.uniform(0.6, 1.6, 3), jnp.float32)}

    layers = []
    for sparse in b.sparse:
        lp = {"ln1": norm(d), "ln2": norm(d), "hc_attn": maps(),
              "hc_ffn": maps(),
              "attn": {"wq_a": mat(d, b.q_lora), "q_norm": norm(b.q_lora),
                       "wq_b": mat(b.q_lora, H * (b.nope + b.rope)),
                       "wkv_a": mat(d, b.kv_lora + b.rope),
                       "kv_norm": norm(b.kv_lora),
                       "wkv_b": mat(b.kv_lora, H * (b.nope + b.v_head)),
                       "wo": mat(H * b.v_head, d)}}
        if sparse:
            E, f = b.held_experts, b.expert_ffn
            lp["moe"] = {"router": mat(d, E), "router_bias": mat(E, std=0.1),
                         "wg": mat(E, d, f), "wi": mat(E, d, f),
                         "wo": mat(E, f, d)}
            lp["shared"] = {"wg": mat(d, b.shared_ffn),
                            "wi": mat(d, b.shared_ffn),
                            "wo": mat(b.shared_ffn, d)}
        else:
            lp["mlp"] = {"wg": mat(d, b.dense_ffn), "wi": mat(d, b.dense_ffn),
                         "wo": mat(b.dense_ffn, d)}
        layers.append(lp)
    return {"embed": {"tokens": mat(b.vocab, d, std=0.5)}, "layers": layers,
            "final_norm": norm(d), "lm_head": mat(d, b.vocab)}


def _maps_by_hand(X, hc, w):
    """One token's maps in float64, the matrices by index: X [n, C] →
    (H_pre [n], H_post [n], H_res [n, n])."""
    n = w.streams
    phi, base = np.asarray(hc["phi"], np.float64), \
        np.asarray(hc["base"], np.float64)
    a_pre, a_post, a_res = np.asarray(hc["scale"], np.float64)
    flat = X.reshape(-1)
    m = (flat / np.sqrt(np.mean(flat ** 2) + w.block.eps)) @ phi
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    pre = np.array([sig(a_pre * m[i] + base[i]) for i in range(n)])
    post = np.array([2.0 * sig(a_post * m[n + i] + base[n + i])
                     for i in range(n)])
    M = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            k = 2 * n + n * i + j
            M[i, j] = np.exp(np.clip(a_res * m[k] + base[k], *w.clamp))
    for _ in range(w.rounds):
        for j in range(n):
            M[:, j] = M[:, j] / (sum(M[i, j] for i in range(n)) + w.hc_eps)
        for i in range(n):
            M[i, :] = M[i, :] / (sum(M[i, j] for j in range(n)) + w.hc_eps)
    return pre, post, M


def test_the_residual_path_by_a_loop_over_tokens():
    """The whole stack: the reference's readout against a second writing in
    which every token's maps, its sublayer input and its write-back are
    taken one token at a time in float64 (the branches themselves — what
    ``deepseek_v3_decoder`` supplies — are called on the sequence of those
    inputs: attention needs its neighbours)."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    params = _tree(w)
    tokens = np.random.default_rng(1).integers(0, w.block.vocab, 24)
    (got,), _ = ref.hidden_and_margins(w, params, [tokens], CPU)
    n = w.streams
    emb = np.asarray(params["embed"]["tokens"], np.float64)
    X = np.stack([np.stack([emb[t]] * n) for t in tokens])     # [T, n, C]
    with jax.default_matmul_precision("highest"):
        for lp in params["layers"]:
            for part, branch in (
                    ("hc_attn", lambda u, lp=lp: ref.attention_branch(
                        u, lp["ln1"], lp["attn"], w.block)),
                    ("hc_ffn", lambda u, lp=lp: ref.ffn_branch(
                        u, lp["ln2"], {k: lp[k] for k in ("mlp", "moe",
                                                           "shared")
                                       if k in lp}, w.block)[0])):
                H = [_maps_by_hand(X[t], lp[part], w)
                     for t in range(len(tokens))]
                u = np.stack([sum(H[t][0][i] * X[t, i] for i in range(n))
                              for t in range(len(tokens))])
                y = np.asarray(branch(jnp.asarray(u, jnp.float32)),
                               np.float64)
                X = np.stack([np.stack([
                    sum(H[t][2][i, j] * X[t, j] for j in range(n)) +
                    H[t][1][i] * y[t] for i in range(n)])
                    for t in range(len(tokens))])
                res = np.stack([h[2] for h in H])
                # doubly stochastic: the stream's MEAN is kept by the mixing
                assert np.abs(res.sum(1) - 1).max() < 1e-4 and \
                    np.abs(res.sum(2) - 1).max() < 1e-4
    want = X.sum(axis=1)
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()
    # ... and a writing with the rows normalised FIRST is another function
    swapped = np.asarray(ref.maps(jnp.asarray(X, jnp.float32),
                                  params["layers"][0]["hc_attn"], w)[2])
    one = _maps_by_hand(X[0], params["layers"][0]["hc_attn"], w)[2]
    assert np.abs(swapped[0] - one).max() < 1e-5
    assert np.abs(swapped[0] - one.T).max() > 1e-3


def test_what_argmax_gaps_judges(monkeypatch):
    """Every generated token, flattened; a token that IS the reference's
    argmax reads 0.0; the margin leaves out the undecided ones and an
    infinite margin (none decided) leaves an empty array, not an error."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    params = _tree(w, seed=3)
    prompt = list(np.random.default_rng(2).integers(0, w.block.vocab, 9))
    out = []
    for _ in range(5):      # the reference's own greedy continuation
        out.append(int(ref.logits_of(w, params, prompt + out, CPU)[-1]
                       .argmax()))
    monkeypatch.setattr(ref, "UNDECIDED_LOGIT_MARGIN", 0.0)
    gaps = ref.argmax_gaps(w, params, [prompt], [out], CPU)
    assert gaps.shape == (5,) and (gaps == 0.0).all()
    wrong = [(t + 1) % w.block.vocab for t in out]
    assert (ref.argmax_gaps(w, params, [prompt], [wrong], CPU) > 0).all()
    seen = ref.teacher_forced(w, params, [prompt], [out], CPU)
    assert seen["margin"].shape == (5,) and (seen["margin"] >= 0).all()
    monkeypatch.setattr(ref, "UNDECIDED_LOGIT_MARGIN", np.inf)
    assert ref.argmax_gaps(w, params, [prompt], [out], CPU).shape == (0,)
    # the loss over the same logits
    batch = np.asarray([prompt + out])
    logits = ref.logits_of(w, params, batch[0], CPU)[:-1]
    nll = jax.nn.logsumexp(jnp.asarray(logits), -1) - \
        logits[np.arange(len(logits)), batch[0, 1:]]
    assert abs(ref.loss(w, params, batch, CPU) - float(nll.mean())) < 1e-5


def _recorded_run(model_cfg, launches, rows):
    """A run as the harness hands it to a reader, from recorded facts:
    three server steps of which the last two are traced, each with one
    launch (``launches``: the ``serving/dispatch`` arguments), and a device
    attribution ``rows`` {(program, scope, kind): ns}."""
    steps = [{"name": "serving/engine_step", "ph": "X", "ts": 10.0 * i,
              "dur": 9.0, "tid": 1, "args": {"program": a["program"]}}
             for i, a in enumerate(launches)]
    events = list(steps) + [
        {"name": "serving/dispatch", "ph": "X", "ts": 10.0 * i + 1,
         "dur": 2.0, "tid": 1, "args": dict(a)}
        for i, a in enumerate(launches)]
    run = SimpleNamespace(
        facts={"traced_step_range": (1, 3), "model": model_cfg,
               "steps": [None] * 3, "spans": events},
        trace=None, peaks={"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9},
        span_name="benchmark/serve_step", flops=flops, stats=stats,
        reduce=reduce,
        program_spans=lambda name: [e for e in events if e["name"] == name])
    run._scopes_analysis = {
        "device": {"ops": {}, "rows": rows, "scoped_ns": 1, "sum_ns": 1},
        "steps": 2, "events": events}
    return run


def test_the_readers_on_a_recorded_run():
    cfg = SimpleNamespace(hc_mult=4, hidden_size=3584, num_layers=6)
    split = {"program": "split", "tokens": 410, "slots": 512, "rows": 64,
             "hc_maps": 512 * 12}
    decode = {"program": "decode", "tokens": 64, "slots": 64, "rows": 64,
              "hc_maps": 64 * 12}
    rows = {("serve_split_r64_c128", "hc_maps", "forward"): 1.0e6,
            ("serve_split_r64_c128", "hc_mix", "forward"): 2.4e6,
            ("serve_decode_r64", "hc_maps", "forward"): 0.6e6,
            ("serve_decode_r64", "hc_mix", "forward"): 0.2e6,
            ("serve_split_r64_c128", "moe", "forward"): 30.0e6}
    # the untraced first step is not counted: one split launch of 512
    # slots and one decode launch of 64
    run = _recorded_run(cfg, [split, split, decode], rows)
    assert _reader("hc_ms_per_step").read(run) == 2.1
    assert _reader("hc_maps_ms_per_step").read(run) == 0.8
    # a program without the scopes (the parent's): nothing, and no raise
    no_scope = {("serve_split_r64_c128", "moe", "forward"): 30.0e6}
    for name in NEW_READERS:
        assert _reader(name).read(
            _recorded_run(cfg, [split] * 3, no_scope)) is None, name


def test_the_new_readers_read_nothing_from_an_empty_run():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better, layer in (
            ("hc_ms_per_step", "ms", "lower", "step programs"),
            ("hc_maps_ms_per_step", "ms", "lower", "step programs")):
        # (later cells may join the list: this cell stays in it)
        assert {k: v for k, v in entries[name].items()
                if k != "workloads"} == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "serve_tokens_per_s"} and \
            CELL in entries[name]["workloads"]
        reader = _reader(name)
        assert (reader.LAYER, reader.MOVES) == (layer, "serve_tokens_per_s")
        assert reader.read(run) is None


def test_the_cell_is_the_issues_and_its_mix_is_cell_2s():
    from benchmark.lib import traffic
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chat-closed64", 1)
    assert [w["traffic"] for w in bench["workloads"]].count(
        "chat-closed64") >= 4           # four stacks under one mix
    mix = traffic.load_mix("chat-closed64")
    assert mix["arrival"] == {"process": "closed", "clients": 64} and \
        mix["max_total_tokens"] == 4096
    conf = model.load_config(CONFIG)
    engine = conf["engine"]
    assert engine == {"dtype": "bfloat16", "max_sequences": 64,
                      "num_blocks": 2048, "block_size": 128,
                      "max_seq_len": 4096, "max_batch_tokens": 2048,
                      "prefill_chunk": 128} and conf["frontend"] == {}
    # no request can fail: 64 x 4,096 tokens fit the arena
    assert 64 * mix["max_total_tokens"] <= \
        engine["num_blocks"] * engine["block_size"]
    # depth alone is cut: ALL 64 experts, the whole vocabulary, every width
    assert conf["reduced"] == ["num_hidden_layers",
                               "first_k_dense_replace"] and \
        "expert_share" not in conf
    published = model.load_published(conf)
    assert published["source"] == conf["source"] and all(
        conf[k] == v for k, v in published.items()
        if k not in conf["reduced"])
    assert {k: conf["changed"][k] for k in conf["reduced"]} == {
        "num_hidden_layers": {"published": 40, "run": 6},
        "first_k_dense_replace": {"published": 2, "run": 1}}
    assert conf["n_routed_experts"] == 64 and conf["vocab_size"] == 131072
    assert conf["rehearsal"]["hc_mult"] == 4 and \
        conf["rehearsal"]["hc_sinkhorn_iters"] == 20
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine >= set(NEW_READERS) | {
        "device_idle_share.serve", "ttft_p90_closed_ms", "rows_per_step",
        "token_slot_utilization", "decode_program_step_share",
        "prefill_tokens_per_step", "moe_ms_per_step",
        "moe_router_ms_per_step", "moe_shared_ms_per_step",
        "attn_latent_ms_per_step", "attn_chunk_ms_per_step",
        "idle_ms_per_step.fanout", "idle_ms_per_step.frontend",
        "idle_ms_per_step.caller", "idle_ms_per_step.launch_and_fetch",
        "idle_attributed_share.serve", "setup_import_s",
        "setup_engine_init_s", "setup_program_trace_s",
        "setup_program_lower_s", "setup_program_load_s",
        "setup_program_compile_s", "setup_programs_built"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
