"""The LFM2-MoE reference (``reference/lfm2_moe_decoder.py``) on its own:
the contract, the gated short convolution by hand, the whole stack by its
equations, what ``argmax_gaps`` judges, the three new readers' work
functions against a hand count, and the cell's entries. After
``test_jamba_reference.py``; the program against this reference is
``tests/test_lfm2_moe.py``."""

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

from benchmark.lib import flops, model, short_conv_work, stats
from benchmark.trace import reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "lfm2-24b-a2b-l40-e8-serve"
CELL = "lfm2-24b-a2b-l40-e8-serve-chat-closed64"
CPU = jax.devices("cpu")[0]
NEW_READERS = ("conv_mixer_ms_per_step", "conv_state_ms_per_step",
               "conv_mixer_roofline")


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


def test_the_lfm2_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("lfm2_moe_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert w.layers == 40 and w.kinds.count("conv") == 30 and [
        l for l, k in enumerate(w.kinds) if k == "full_attention"
    ] == list(range(2, 40, 4)) and w.dense_layers == 2
    assert (w.hidden, w.heads, w.kv_heads, w.head_dim, w.eps, w.theta,
            w.dense_ffn, w.expert_ffn, w.vocab, w.conv_kernel) == \
        (2048, 32, 8, 64, 1e-5, 1e6, 11776, 1536, 65536, 3)
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.norm_topk, w.routed_scale) == (64, 0, 8, 4, True, 1.0)
    # a token multiplies ON THIS CHIP: a convolution mixer's 4 x 2,048², an
    # attention mixer's 10.5M, a dense layer's three matrices, the router
    # and 4 x 8/64 of an expert's three, the tied head
    assert ref.matmul_params_per_token(w) == \
        30 * 4 * 2048 ** 2 + 10 * (2 * 2048 * 2048 + 2 * 2048 * 512) + \
        2 * 3 * 2048 * 11776 + \
        38 * (2048 * 64 + round(0.5 * 3 * 2048 * 1536)) + 2048 * 65536
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)
    assert all(hasattr(ref, name) for name in model.REFERENCE_CONTRACT)
    for key, value in (("conv_bias", True),
                       ("layer_types", ["conv", "mamba"] * 20)):
        try:
            ref.Widths.from_hf({**model.published_keys(model.load_config(
                CONFIG)), key: value})
        except ValueError:
            continue
        raise AssertionError(f"{key} {value!r} was taken")


def _tiny(ref, kinds=("conv", "conv", "full_attention", "conv"), **over):
    return ref.Widths(**{**dict(
        hidden=12, kinds=tuple(kinds), dense_layers=1, heads=2, kv_heads=1,
        conv_kernel=3, eps=1e-5, theta=1e6, dense_ffn=10, expert_ffn=7,
        router_experts=6, first_expert=0, held_experts=6, per_token=2,
        norm_topk=True, routed_scale=1.0, vocab=16), **over})


def _tree(w, seed=0):
    rng = np.random.default_rng(seed)

    def mat(*shape, std=0.5):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    scale = lambda n: {"scale": mat(n, std=0.1) + 1.0}
    d, layers = w.hidden, []
    for l, kind in enumerate(w.kinds):
        lp = {"ln1": scale(d), "ln2": scale(d)}
        if kind == "conv":
            lp["conv"] = {"w_in": mat(d, 3 * d), "conv_w": mat(d, 3),
                          "w_out": mat(d, d)}
        else:
            qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
            lp["attn"] = {"wq": mat(d, qd), "wk": mat(d, kd),
                          "wv": mat(d, kd), "wo": mat(qd, d),
                          "q_norm": scale(w.head_dim),
                          "k_norm": scale(w.head_dim)}
        if l < w.dense_layers:
            lp["mlp"] = {"wg": mat(d, w.dense_ffn), "wi": mat(d, w.dense_ffn),
                         "wo": mat(w.dense_ffn, d)}
        else:
            e, f = w.held_experts, w.expert_ffn
            lp["moe"] = {"router": mat(d, w.router_experts),
                         "router_bias": mat(w.router_experts, std=0.2),
                         "wg": mat(e, d, f), "wi": mat(e, d, f),
                         "wo": mat(e, f, d)}
        layers.append(lp)
    return {"embed": {"tokens": mat(w.vocab, d, std=0.1)}, "layers": layers,
            "final_norm": scale(d)}


def test_the_gated_short_convolution_by_hand():
    """Float64 numpy, a token and a channel at a time: ``[B | C | x̃]`` in
    that order, three taps over ``B ⊙ x̃`` from zeros before the sequence,
    no bias, no activation, the out gate ``C``."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    p = _tree(w, 1)["layers"][0]["conv"]
    hin = np.random.default_rng(2).normal(0, 1, (9, 12))
    q = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    bcx = hin @ q["w_in"]
    b, c, x = bcx[:, :12], bcx[:, 12:24], bcx[:, 24:]
    u = b * x
    want = np.zeros((9, 12))
    for t in range(9):
        for ch in range(12):
            acc = sum(q["conv_w"][ch, i] * u[t - 2 + i, ch]
                      for i in range(3) if t - 2 + i >= 0)
            want[t, ch] = c[t, ch] * acc
    want = want @ q["w_out"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.conv_mixer(w, p, jnp.asarray(hin, jnp.float32)))
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    # the gates' order and the taps' order count
    d = 12
    swapped = dict(p, w_in=jnp.concatenate(
        [p["w_in"][:, d:2 * d], p["w_in"][:, :d], p["w_in"][:, 2 * d:]], 1))
    reversed_taps = dict(p, conv_w=p["conv_w"][:, ::-1])
    for wrong in (swapped, reversed_taps):
        with jax.default_matmul_precision("highest"):
            other = np.asarray(ref.conv_mixer(
                w, wrong, jnp.asarray(hin, jnp.float32)))
        assert np.abs(other - want).max() > 1e-2


def test_a_whole_stack_by_its_equations_and_its_own_tokens():
    """Four layers by hand from the docstring's equations (the mixer is the
    one checked above; attention written out with the head norms BEFORE the
    rotation; the router's pick by ``s + bias``, its weight ``s / (Σ s +
    1e-6)``), two norms a layer, the tied head; ``teacher_forced``'s gap
    is zero for the stack's own greedy tokens and the plain
    logit difference of any other; ``loss`` is the mean next-token
    cross-entropy."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    params = _tree(w, 3)
    tokens = np.random.default_rng(2).integers(0, 16, 24)
    got = ref.logits_of(w, params, tokens, CPU)
    norm = ref.dense._rms_norm
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(tokens)]
        pos = jnp.arange(24)
        for l, (kind, lp) in enumerate(zip(w.kinds, params["layers"])):
            h = norm(x, lp["ln1"]["scale"], w.eps)
            if kind == "conv":
                x = x + ref.conv_mixer(w, lp["conv"], h)
            else:
                a = lp["attn"]
                q = ref.dense._rope(norm((h @ a["wq"]).reshape(24, 2, 6),
                                         a["q_norm"]["scale"], w.eps), pos,
                                    w.theta)
                k = ref.dense._rope(norm((h @ a["wk"]).reshape(24, 1, 6),
                                         a["k_norm"]["scale"], w.eps), pos,
                                    w.theta)
                k = jnp.repeat(k, 2, axis=1)
                v = jnp.repeat((h @ a["wv"]).reshape(24, 1, 6), 2, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) * 6 ** -0.5
                s = jnp.where(jnp.tril(jnp.ones((24, 24), bool))[None], s,
                              -jnp.inf)
                x = x + jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                   v).reshape(24, 12) @ a["wo"]
            h2 = norm(x, lp["ln2"]["scale"], w.eps)
            if l < w.dense_layers:
                m = lp["mlp"]
                x = x + (jax.nn.silu(h2 @ m["wg"]) * (h2 @ m["wi"])) \
                    @ m["wo"]
                continue
            m = lp["moe"]
            s = jax.nn.sigmoid(h2 @ m["router"])
            _, sel = jax.lax.top_k(s + m["router_bias"], 2)
            kept = jnp.take_along_axis(s, sel, -1)
            kept = kept / (kept.sum(-1, keepdims=True) + 1e-6)
            out = jnp.zeros_like(x)
            for t in range(24):
                for j in range(2):
                    e = int(sel[t, j])
                    out = out.at[t].add(kept[t, j] * (
                        (jax.nn.silu(h2[t] @ m["wg"][e]) *
                         (h2[t] @ m["wi"][e])) @ m["wo"][e]))
            x = x + out
        want = norm(x, params["final_norm"]["scale"], w.eps) \
            @ params["embed"]["tokens"].T
    assert np.abs(got - np.asarray(want)).max() < 1e-4
    prompt, out = tokens[:8].tolist(), []
    for _ in range(6):
        out.append(int(ref.logits_of(w, params, prompt + out, CPU)[-1]
                       .argmax()))
    seen = ref.teacher_forced(w, params, [prompt], [out], CPU)
    assert np.array_equal(seen["gap"], np.zeros(6)) and \
        seen["lead"].shape == (6,) and (seen["lead"] > 0).all()
    other = list(out)
    other[3] = (other[3] + 1) % 16
    gaps = ref.teacher_forced(w, params, [prompt], [other], CPU)["gap"]
    full = ref.logits_of(w, params, prompt + other[:3], CPU)[-1]
    assert abs(gaps[3] - (full.max() - full[other[3]])) < 1e-4
    assert abs(ref.loss(w, params, tokens[None], CPU) - float(np.mean([
        np.log(np.exp(got[t]).sum()) - got[t, tokens[t + 1]]
        for t in range(23)]))) < 1e-4


def test_what_argmax_gaps_judges(monkeypatch):
    """``teacher_forced`` gives every generated token's gap and the lead of
    the reference's best logit over its second; ``argmax_gaps`` returns the
    gaps of the tokens whose argmax the reference decides by
    ``UNDECIDED_ARGMAX_MARGIN`` alone."""
    ref, _ = _ref_and_widths()
    assert 0.49 < ref.UNDECIDED_ARGMAX_MARGIN < 4.29    # its two readings
    w = _tiny(ref)
    params = _tree(w, 3)
    prompt = [1, 2, 3, 4]
    out = [5, 6, 7, 8, 9, 10, 11, 12]
    seen = ref.teacher_forced(w, params, [prompt], [out], CPU)
    assert seen["gap"].shape == seen["lead"].shape == (8,)
    for j in range(8):
        logits = ref.logits_of(w, params, prompt + out[:j], CPU)[-1]
        top = np.sort(logits)
        assert abs(seen["lead"][j] - (top[-1] - top[-2])) < 1e-4
        assert abs(seen["gap"][j] - (top[-1] - logits[out[j]])) < 1e-4
    # (at this size the logits spread by a tenth of the margin: the median
    # lead stands in for it)
    monkeypatch.setattr(ref, "UNDECIDED_ARGMAX_MARGIN",
                        float(np.median(seen["lead"])))
    decided = seen["lead"] >= ref.UNDECIDED_ARGMAX_MARGIN
    assert decided.sum() == 4
    gaps = ref.argmax_gaps(w, params, [prompt], [out], CPU)
    assert np.array_equal(gaps, seen["gap"][decided])
    # a token that is NOT the decided argmax scores at least the lead
    assert (gaps[gaps > 0] >= ref.UNDECIDED_ARGMAX_MARGIN - 1e-6).all()


def test_work_functions_against_a_hand_count():
    cfg = SimpleNamespace(
        layer_kinds=tuple(0 if l % 4 == 2 else 5 for l in range(40)),
        hidden_size=2048, ssm_conv_kernel=3)
    assert short_conv_work.conv_layers(cfg) == 30
    assert short_conv_work.conv_layers(SimpleNamespace()) == 0 and \
        short_conv_work.conv_layers(SimpleNamespace(layer_kinds=None)) == 0
    # a slot: 2 x (2,048 x 6,144 + 2,048 x 2,048) + 3 taps and 2 gates
    per_slot = 2 * 4 * 2048 ** 2 + 2 * 3 * 2048 + 2 * 2048
    assert short_conv_work.mixer_flops(cfg, 1024) == 30 * 1024 * per_slot
    # ISSUE 56's count: ≈ 1.0 TFLOP a launch of 1,024 slots, 5.2 ms at peak
    assert 1.02e12 < short_conv_work.mixer_flops(cfg, 1024) < 1.04e12
    # a launch: the weights once a layer (16.78M + the taps, bf16), a slot's
    # input and output rows, a row's two carried rows in and out
    weights = 2 * (4 * 2048 ** 2 + 3 * 2048)
    assert short_conv_work.mixer_bytes(cfg, 1, 1024, 64) == 30 * (
        weights + 2 * 1024 * 2 * 2048 + 2 * 64 * 2 * 2 * 2048)
    # at 1,024 slots the FLOPs bound the mixers (5.2 ms), not the bytes (1.6)
    assert short_conv_work.mixer_flops(cfg, 1024) / 197e12 > \
        2 * short_conv_work.mixer_bytes(cfg, 1, 1024, 64) / 819e9
    # ... and a decode launch of 64 slots is bound by the weights' bytes
    assert short_conv_work.mixer_flops(cfg, 64) / 197e12 < \
        short_conv_work.mixer_bytes(cfg, 1, 64, 64) / 819e9


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
    cfg = SimpleNamespace(
        layer_kinds=tuple(0 if l % 4 == 2 else 5 for l in range(40)),
        hidden_size=2048, ssm_conv_kernel=3, recurrent=True)
    split = {"program": "split", "tokens": 830, "slots": 1024, "rows": 64,
             "state_rows": 64, "state_resets": 1, "ssm_chunk_tokens": 770}
    decode = {"program": "decode", "tokens": 64, "slots": 64, "rows": 64,
              "state_rows": 64, "state_resets": 0, "ssm_chunk_tokens": 0}
    rows = {("serve_split_r64_c128", "conv_mixer", "forward"): 14.0e6,
            ("serve_decode_r64", "conv_mixer", "forward"): 2.0e6,
            ("serve_split_r64_c128", "conv_state", "forward"): 3.0e6,
            ("serve_split_r64_c128", "moe", "forward"): 30.0e6}
    # the untraced first step is not counted: one split launch of 1,024
    # slots (FLOP-bound) and one decode launch of 64 (its bytes are under
    # the two launches' FLOPs together): the larger side of the SUMS
    run = _recorded_run(cfg, [split, split, decode], rows)
    least = max(short_conv_work.mixer_flops(cfg, 1088) / 197e12,
                short_conv_work.mixer_bytes(cfg, 2, 1088, 128) / 819e9)
    got = _reader("conv_mixer_roofline").read(run)
    assert abs(got - 100 * least / 16.0e-3) < 1e-9 and 30 < got < 40
    assert _reader("conv_mixer_ms_per_step").read(run) == 8.0
    assert _reader("conv_state_ms_per_step").read(run) == 1.5
    # a launch without the counters (the parent's program), a program
    # without the scope, a stack without the kind: nothing, and no raise
    bare = {"program": "split", "tokens": 830}
    assert _reader("conv_mixer_roofline").read(
        _recorded_run(cfg, [bare] * 3, rows)) is None
    no_scope = {("serve_split_r64_c128", "moe", "forward"): 30.0e6}
    for name in NEW_READERS:
        assert _reader(name).read(
            _recorded_run(cfg, [split] * 3, no_scope)) is None, name
    mamba = SimpleNamespace(layer_kinds=(3, 0, 3), recurrent=True)
    assert _reader("conv_mixer_roofline").read(
        _recorded_run(mamba, [split] * 3, rows)) is None
    assert _reader("conv_mixer_roofline").read(
        _recorded_run(SimpleNamespace(), [split] * 3, rows)) is None


def test_the_new_readers_read_nothing_from_an_empty_run():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better, layer in (
            ("conv_mixer_ms_per_step", "ms", "lower", "step programs"),
            ("conv_state_ms_per_step", "ms", "lower", "step programs"),
            ("conv_mixer_roofline", "%", "higher", "kernels")):
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
        "chat-closed64") >= 3           # three stacks under one mix
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
    # NOTHING is cut but the experts held: every published key at its
    # published value, all 40 layers, the whole vocabulary
    assert conf["reduced"] == ["expert_share"] and \
        conf["expert_share"] == {"router_experts": 64, "first_expert": 0,
                                 "held_experts": 8}
    published = model.load_published(conf)
    assert published["source"] == conf["source"] and all(
        conf[k] == v for k, v in published.items())
    assert conf["num_hidden_layers"] == 40 and conf["vocab_size"] == 65536
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine >= set(NEW_READERS) | {
        "device_idle_share.serve", "ttft_p90_closed_ms", "rows_per_step",
        "token_slot_utilization", "decode_program_step_share",
        "moe_ms_per_step", "moe_router_ms_per_step",
        "idle_ms_per_step.fanout", "idle_ms_per_step.frontend",
        "idle_ms_per_step.caller", "idle_ms_per_step.launch_and_fetch",
        "idle_attributed_share.serve", "setup_import_s",
        "setup_engine_init_s", "setup_program_trace_s",
        "setup_program_lower_s", "setup_program_load_s",
        "setup_program_compile_s", "setup_programs_built"}
    assert not {n for n in mine if n.startswith(("ssm_", "moe_shared"))}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}


def test_rehearsal_of_the_cell():
    """Tiny widths, the mix as it is: every check, and the counts a CPU
    run can give."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5600000056", "--seconds", "8",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    start = next(l for l in lines if l.get("phase") == "start")
    assert "lfm2_moe_decoder" in json.dumps(start)
    checks = next(l for l in lines if l.get("phase") == "checks")
    assert [k for k, v in checks.items() if v is False] == []
    last = lines[-1]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and \
        last["correct"]
    assert {"rows_per_step", "token_slot_utilization",
            "decode_program_step_share"} <= set(last["metrics"])
