"""The Qwen3-Next reference (``reference/qwen3_next_decoder.py``) on its own:
the contract, a layer by hand, its margins, the new readers and the cell's
entries. After ``test_granitemoehybrid_reference.py``; the program against
this reference is ``tests/test_qwen3_next.py``."""

import importlib.util
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import delta_rule_work, flops, model, stats
from benchmark.trace import reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "qwen3-next-80b-a3b-l12-e64-serve"
CELL = "qwen3-next-80b-a3b-l12-e64-serve-rag-closed64"
CPU = jax.devices("cpu")[0]
NEW_READERS = ("delta_rule_ms_per_step", "delta_rule_roofline",
               "attn_gate_ms_per_step")


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


def test_the_qwen3_next_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("qwen3_next_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert w.layers == 12 and [w.is_full(l) for l in range(12)] == \
        [False, False, False, True] * 3
    assert (w.hidden, w.heads, w.kv_heads, w.head_dim, w.rope_dim,
            w.rope_theta, w.eps) == (2048, 16, 2, 256, 64, 1e7, 1e-6)
    assert (w.key_heads, w.value_heads, w.key_dim, w.value_dim,
            w.conv_kernel, w.inner, w.conv_dim) == \
        (16, 32, 128, 128, 4, 4096, 8192)
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.norm_topk, w.expert_ffn, w.shared_ffn, w.vocab) == \
        (512, 0, 64, 10, True, 512, 512, 151936)
    # a token multiplies: a delta-rule mixer's [q | k | v | z], [b | a] and
    # out projections, or attention's q, gate, o at 4,096 and k, v at 512;
    # and in EVERY layer the router over 512, the shared expert with its
    # one-logit gate and 10 x 64 / 512 = 1.25 of its ten three-matrix
    # experts on this chip; the untied head over the whole vocabulary
    d = 2048 * (12288 + 64) + 4096 * 2048
    a = 3 * 2048 * 4096 + 2 * 2048 * 512
    e = 2048 * 512 + 3 * 2048 * 512 + 2048 + round(1.25 * 3 * 2048 * 512)
    assert ref.matmul_params_per_token(w) == \
        9 * d + 3 * a + 12 * e + 2048 * 151936
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)
    assert all(hasattr(ref, name) for name in model.REFERENCE_CONTRACT)


def _tiny(ref, layers=4):
    return ref.Widths(
        hidden=12, layers=layers, full_every=4, heads=4, kv_heads=2,
        head_dim=8, rope_dim=4, rope_theta=100.0, key_heads=2,
        value_heads=4, key_dim=5, value_dim=3, conv_kernel=4, eps=1e-6,
        expert_ffn=6, shared_ffn=7, router_experts=6, first_expert=1,
        held_experts=3, per_token=3, norm_topk=True, vocab=16)


def _tree(w, seed=0):
    rng = np.random.default_rng(seed)

    def mat(*shape, std=0.5):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    # (a zero-centred norm's scale holds 1 + w: w is drawn, not zero)
    norm = lambda n: {"scale": mat(n, std=0.2) + 1.0}
    layers = []
    for l in range(w.layers):
        lp = {"ln1": norm(w.hidden), "ln2": norm(w.hidden),
              "moe": {"router": mat(w.hidden, w.router_experts, std=1.0),
                      "wg": mat(w.held_experts, w.hidden, w.expert_ffn),
                      "wi": mat(w.held_experts, w.hidden, w.expert_ffn),
                      "wo": mat(w.held_experts, w.expert_ffn, w.hidden)},
              "shared": {"wg": mat(w.hidden, w.shared_ffn),
                         "wi": mat(w.hidden, w.shared_ffn),
                         "wo": mat(w.shared_ffn, w.hidden),
                         "gate": mat(w.hidden, 1)}}
        if w.is_full(l):
            qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
            lp["attn"] = {"wq": mat(w.hidden, qd),
                          "wq_gate": mat(w.hidden, qd),
                          "wk": mat(w.hidden, kd), "wv": mat(w.hidden, kd),
                          "wo": mat(qd, w.hidden),
                          "q_norm": norm(w.head_dim),
                          "k_norm": norm(w.head_dim)}
        else:
            lp["ssm"] = {
                "w_in": mat(w.hidden, w.conv_dim + w.inner),
                "w_ba": mat(w.hidden, 2 * w.value_heads),
                "conv_w": mat(w.conv_dim, w.conv_kernel),
                "dt_bias": mat(w.value_heads) + 1.0,
                "A_log": jnp.log(jnp.asarray(rng.uniform(
                    0.05, 2.0, w.value_heads), jnp.float32)),
                "norm": {"scale": mat(w.value_dim, std=0.2) + 1.0},
                "w_out": mat(w.inner, w.hidden)}
        layers.append(lp)
    return {"embed": {"tokens": mat(w.vocab, w.hidden, std=0.1)},
            "layers": layers, "final_norm": norm(w.hidden),
            "lm_head": mat(w.hidden, w.vocab)}


def test_experts_part_and_margin_by_hand():
    """One token through a layer's second part, in numpy: the softmax over
    ALL six logits, the three highest kept, their weights over their sum,
    the held ones' GLUs weighed, the shared expert behind its sigmoid; the
    margin is the least distance of a HELD expert's logit to the boundary
    it would have to cross."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    lp = _tree(w)["layers"][0]
    hin = jnp.asarray(np.random.default_rng(1).normal(0, 1, (5, 12)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.experts_part(hin, lp["moe"], w))
        shared = np.asarray(ref.shared_part(hin, lp["shared"]))
        margin = np.asarray(ref.held_margin(hin, lp["moe"], w))
        gate, sel = (np.asarray(t) for t in ref.route(hin, lp["moe"], w))
    m = {k: np.asarray(v, np.float64) for k, v in lp["moe"].items()}
    sh = {k: np.asarray(v, np.float64) for k, v in lp["shared"].items()}
    x = np.asarray(hin, np.float64)
    silu = lambda t: t / (1.0 + np.exp(-t))
    for t in range(5):
        logits = x[t] @ m["router"]
        probs = np.exp(logits) / np.exp(logits).sum()
        order = np.argsort(-logits)
        kept = order[:3]
        assert kept.tolist() == sel[t].tolist()
        weight = probs[kept] / probs[kept].sum()
        want = np.zeros(12)
        for e, g in zip(kept, weight):
            assert abs(gate[t, e] - g) < 1e-6
            if 1 <= e < 4:                       # experts 1..3 are held
                i = e - 1
                want += g * ((silu(x[t] @ m["wg"][i]) * (x[t] @ m["wi"][i]))
                             @ m["wo"][i])
        assert np.abs(got[t] - want).max() < 1e-4
        one = 1.0 / (1.0 + np.exp(-(x[t] @ sh["gate"])))
        assert np.abs(shared[t] - one * ((silu(x[t] @ sh["wg"]) *
                                          (x[t] @ sh["wi"])) @ sh["wo"])
                      ).max() < 1e-4
        last_in, best_out = logits[order[2]], logits[order[3]]
        moves = [logits[e] - best_out if e in kept else last_in - logits[e]
                 for e in (1, 2, 3)]
        assert abs(margin[t] - min(moves)) < 1e-5 and margin[t] >= 0


def _delta_by_hand(w, p, h):
    """The docstring's linear-attention layer in float64, token by token."""
    t = h.shape[0]
    f = lambda a: np.asarray(a, np.float64)
    silu = lambda a: a / (1.0 + np.exp(-a))
    kd, per = w.key_heads * w.key_dim, w.value_heads // w.key_heads
    qkvz, ba = f(h) @ f(p["w_in"]), f(h) @ f(p["w_ba"])
    x = np.concatenate([np.zeros((3, w.conv_dim)), qkvz[:, :w.conv_dim]])
    taps = f(p["conv_w"])
    u = silu(sum(x[i:i + t] * taps[:, i] for i in range(4)))
    z = qkvz[:, w.conv_dim:].reshape(t, w.value_heads, w.value_dim)
    unit = lambda a: a / np.sqrt((a ** 2).sum(-1, keepdims=True) + 1e-6)
    q = unit(u[:, :kd].reshape(t, w.key_heads, w.key_dim)) / \
        np.sqrt(w.key_dim)
    k = unit(u[:, kd:2 * kd].reshape(t, w.key_heads, w.key_dim))
    v = u[:, 2 * kd:].reshape(t, w.value_heads, w.value_dim)
    beta = 1.0 / (1.0 + np.exp(-ba[:, :w.value_heads]))
    g = -np.exp(f(p["A_log"])) * np.log1p(np.exp(
        ba[:, w.value_heads:] + f(p["dt_bias"])))
    out = np.zeros((t, w.value_heads, w.value_dim))
    for hv in range(w.value_heads):
        s = np.zeros((w.key_dim, w.value_dim))
        for i in range(t):
            s = np.exp(g[i, hv]) * s
            read = s.T @ k[i, hv // per]
            s = s + np.outer(k[i, hv // per], beta[i, hv] * (v[i, hv] - read))
            out[i, hv] = s.T @ q[i, hv // per]
    out = out / np.sqrt((out ** 2).mean(-1, keepdims=True) + w.eps) * \
        f(p["norm"]["scale"]) * silu(z)
    return out.reshape(t, w.inner) @ f(p["w_out"])


def test_a_whole_stack_by_its_equations_and_its_own_tokens():
    """One period by hand from the docstring's equations: three delta-rule
    layers in float64 numpy, the full layer's head norms, partial rotary
    and output gate written out, every zero-centred norm as ``x̂·(1 + w)``;
    and ``argmax_gaps`` of the stack's own greedy tokens is zero."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    params = _tree(w, 3)
    tokens = np.random.default_rng(2).integers(0, 16, 24)
    got = ref.logits_of(w, params, tokens, CPU)

    def rms0(x, scale):     # w = scale - 1, spelled out
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * \
            (1.0 + (np.asarray(scale, np.float64) - 1.0))

    def rope(x):            # [T, H, 8]: rotate-half on dims 0-3, theta 100
        inv = 100.0 ** (-np.arange(0, 4, 2) / 4)
        ang = np.arange(24)[:, None] * inv[None]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        x1, x2 = x[..., :2], x[..., 2:4]
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                               x[..., 4:]], axis=-1)

    f = lambda a: np.asarray(a, np.float64)
    x = f(params["embed"]["tokens"])[tokens]
    with jax.default_matmul_precision("highest"):
        for l, lp in enumerate(params["layers"]):
            h = rms0(x, lp["ln1"]["scale"])
            if w.is_full(l):
                a = lp["attn"]
                q = rope(rms0((h @ f(a["wq"])).reshape(24, 4, 8),
                              a["q_norm"]["scale"]))
                k = rope(rms0((h @ f(a["wk"])).reshape(24, 2, 8),
                              a["k_norm"]["scale"]))
                k = np.repeat(k, 2, axis=1)
                v = np.repeat((h @ f(a["wv"])).reshape(24, 2, 8), 2, axis=1)
                s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(8)
                s = np.where(np.tril(np.ones((24, 24), bool))[None], s,
                             -np.inf)
                pr = np.exp(s - s.max(-1, keepdims=True))
                pr = pr / pr.sum(-1, keepdims=True)
                o = np.einsum("hqk,khd->qhd", pr, v).reshape(24, 32)
                o = o / (1.0 + np.exp(-(h @ f(a["wq_gate"]))))
                x = x + o @ f(a["wo"])
            else:
                x = x + _delta_by_hand(w, lp["ssm"], h)
            h2 = jnp.asarray(rms0(x, lp["ln2"]["scale"]), jnp.float32)
            x = x + f(ref.experts_part(h2, lp["moe"], w)) + \
                f(ref.shared_part(h2, lp["shared"]))
        want = rms0(x, params["final_norm"]["scale"]) @ f(params["lm_head"])
    # (float32 against float64 through four layers of weights at 0.5: the
    # first positions agree to 1e-6, the last to 1e-3 of the logits' 5.8)
    assert np.abs(got - want).max() < 2e-3 * np.abs(want).max()
    assert np.abs(got - want)[:4].max() < 1e-4
    # margins of zero judge every token: its own argmax reads a gap of 0
    prompt, out = tokens[:8].tolist(), []
    for _ in range(6):
        out.append(int(ref.logits_of(w, params, prompt + out, CPU)[-1]
                       .argmax()))
    kept = (ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN,
            ref.STATE_LOGIT_MARGIN)
    ref.UNDECIDED_LOGIT_MARGIN = ref.NEIGHBOUR_LOGIT_MARGIN = \
        ref.STATE_LOGIT_MARGIN = 0.0
    try:
        assert np.array_equal(
            ref.argmax_gaps(w, params, [prompt], [out], CPU), np.zeros(6))
        other = list(out)
        other[3] = (other[3] + 1) % 16
        gaps = ref.argmax_gaps(w, params, [prompt], [other], CPU)
        full = ref.logits_of(w, params, prompt + other[:3], CPU)[-1]
        assert abs(gaps[3] - (full.max() - full[other[3]])) < 1e-4
    finally:
        (ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN,
         ref.STATE_LOGIT_MARGIN) = kept
    assert abs(ref.loss(w, params, tokens[None], CPU) - float(np.mean([
        np.log(np.exp(got[t]).sum()) - got[t, tokens[t + 1]]
        for t in range(23)]))) < 1e-4


def test_the_recurrence_carries_a_state():
    """Two halves from the state the first left are the whole."""
    ref, _ = _ref_and_widths()
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(10, 4, 5)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(10, 4, 3)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (10, 4)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0, 1, (10, 4)), jnp.float32)
    whole, s_whole = ref.recurrence(q, k, v, beta, g)
    first, s = ref.recurrence(*(t[:6] for t in (q, k, v, beta, g)))
    second, s = ref.recurrence(*(t[6:] for t in (q, k, v, beta, g)), state=s)
    assert np.abs(np.asarray(jnp.concatenate([first, second])) -
                  np.asarray(whole)).max() < 1e-6
    assert np.abs(np.asarray(s) - np.asarray(s_whole)).max() < 1e-6


def test_decided_holds_a_position_and_those_its_mixers_still_hold():
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    margin = np.full(12, 1.0)
    assert ref.decided(margin, w).all()
    margin[4] = ref.STATE_LOGIT_MARGIN / 2          # under every margin
    got = ref.decided(margin, w)
    reach = w.conv_kernel - 1 + ref.STATE_REACH
    assert not got[4:4 + reach + 1].any() and got[:4].all() and \
        got[4 + reach + 1:].all()
    margin[4] = (ref.STATE_LOGIT_MARGIN + ref.NEIGHBOUR_LOGIT_MARGIN) / 2
    got = ref.decided(margin, w)    # its own fails, the state's reach holds
    assert not got[4:4 + w.conv_kernel].any() and \
        got[4 + w.conv_kernel:].all()


def test_work_functions_at_the_cells_widths():
    cfg = SimpleNamespace(layer_kinds=(6, 6, 6, 0) * 3, ssm_heads=32,
                          ssm_head_dim=128, ssm_state_size=128)
    assert delta_rule_work.delta_layers(cfg) == 9
    assert delta_rule_work.state_values(cfg) == 2 ** 19     # 2 MiB float32
    # a launch that advances 47 rows: 9 layers x 47 x 4 MiB in and out
    assert delta_rule_work.state_bytes(cfg, 47) == 9 * 47 * 2 * 2 * 2 ** 20
    assert 2.1e-3 < delta_rule_work.state_bytes(cfg, 47) / 819e9 < 2.2e-3
    # 832 fed tokens: read with k, corrected, read with q: 6 FLOPs a value
    assert delta_rule_work.rule_flops(cfg, 832) == 9 * 832 * 6 * 2 ** 19
    assert delta_rule_work.rule_flops(cfg, 832) / 197e12 < 1.3e-4


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
        "device": {"ops": {}, "rows": rows, "scoped_ns": 0, "sum_ns": 0},
        "steps": 2, "events": events}
    return run


def test_the_roofline_reader_on_a_recorded_run():
    cfg = SimpleNamespace(delta_rule=True, layer_kinds=(6, 6, 6, 0) * 3,
                          ssm_heads=32, ssm_head_dim=128, ssm_state_size=128)
    split = {"program": "split", "tokens": 832, "state_rows": 47,
             "state_resets": 1, "ssm_chunk_tokens": 790}
    rows = {("serve_split_r64_c128", "delta_rule", "fusion"): 20.0e6,
            ("serve_split_r64_c128", "ssm_state", "fusion"): 4.0e6,
            ("serve_split_r64_c128", "moe_experts", "fusion"): 30.0e6}
    # the untraced first step is not counted; two launches of 47 rows: the
    # state's bytes bound them (4.3 ms at the HBM peak of the 24 ms under
    # the two scopes), not the rule's 6 FLOPs a value
    run = _recorded_run(cfg, [split] * 3, rows)
    least = 2 * 47 * 9 * 2 * 2 * 2 ** 20 / 819e9
    got = _reader("delta_rule_roofline").read(run)
    assert abs(got - 100 * least / 24.0e-3) < 1e-9 and 17 < got < 19
    # a launch without the counters (the parent's program), a program
    # without the scopes, a stack without the kind: nothing
    bare = {"program": "split", "tokens": 832}
    assert _reader("delta_rule_roofline").read(
        _recorded_run(cfg, [bare] * 3, rows)) is None
    assert _reader("delta_rule_roofline").read(_recorded_run(
        cfg, [split] * 3, {("serve_split_r64_c128", "moe_experts",
                            "fusion"): 30.0e6})) is None
    other = SimpleNamespace(delta_rule=False, recurrent=True)
    assert _reader("delta_rule_roofline").read(
        _recorded_run(other, [split] * 3, rows)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_from_an_empty_run(name):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    roofline = name.endswith("_roofline")
    assert entry == {"name": name, "unit": "%" if roofline else "ms",
                     "better": "higher" if roofline else "lower",
                     "source": "device_trace",
                     "layer": "kernels" if roofline else "step programs",
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}
    reader = _reader(name)
    assert reader.read(run) is None
    assert (reader.LAYER, reader.MOVES) == (entry["layer"], entry["moves"])


def test_the_cell_is_the_issues_and_the_mix_untouched():
    from benchmark.lib import traffic
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rag-closed64", 1)
    mix = traffic.load_mix("rag-closed64")
    assert mix["arrival"] == {"process": "closed", "clients": 64}
    assert (mix["max_total_tokens"], mix["cycle_seed"], mix["ramp_seconds"],
            mix["trace_seconds"]) == (11008, 49, 60, 3)
    conf = model.load_config(CONFIG)
    jamba = model.load_config("jamba2-3b-l28-serve")
    # cell 10's engine and frontend blocks, to the letter
    assert conf["engine"] == jamba["engine"] and \
        conf["frontend"] == jamba["frontend"] == {"token_budget": 832}
    engine = conf["engine"]
    assert engine["max_sequences"] == mix["arrival"]["clients"] == 64
    # no request can fail: 64 x 11,008 tokens are the arena
    assert 64 * mix["max_total_tokens"] <= \
        engine["num_blocks"] * engine["block_size"]
    assert mix["max_total_tokens"] <= engine["max_seq_len"]
    assert conf["reduced"] == ["num_hidden_layers", "expert_share"] == \
        next(c for c in bench["configs"] if c["name"] == CONFIG)["reduced"]
    assert conf["vocab_size"] == 151936 and conf["expert_share"] == {
        "router_experts": 512, "first_expert": 0, "held_experts": 64}
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) | {
        "ssm_ms_per_step", "moe_ms_per_step", "moe_router_ms_per_step",
        "moe_shared_ms_per_step", "attn_chunk_ms_per_step",
        "prefill_tokens_per_step", "rows_per_step",
        "decode_program_step_share", "idle_attributed_share.serve",
        "setup_programs_built"} <= mine
    assert not mine & {"serve_mlp_ms_per_step", "ssm_scan_roofline",
                       "selective_scan_roofline", "ssm_select_ms_per_step",
                       "paged_attn_lse_roofline"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}


def test_rehearsal_of_the_cell():
    """Tiny widths, the mix as it is: every check, and the counts a CPU
    run can give."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "6200000062", "--seconds", "6",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    checks = next(l for l in lines if l.get("phase") == "checks")
    assert [k for k, v in checks.items() if v is False] == []
    last = lines[-1]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and \
        last["correct"]
    assert {"rows_per_step", "token_slot_utilization",
            "decode_program_step_share"} <= set(last["metrics"])
