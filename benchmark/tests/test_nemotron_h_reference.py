"""The Nemotron-H configuration's own pieces of the yardstick: the
reference's side of the contract and its equations by hand (a numpy loop a
token, a head and a state value at a time), ``lib/ssm_work``'s counts on
hand-worked shapes, the two new readers on a recorded ``facts`` fixture and
on a run that has nothing for them, the mix untouched, and ``--rehearse`` of
the cell on the CPU."""

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

from benchmark.lib import flops, model, ssm_work, stats
from benchmark.trace import reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "nemotron3-nano-l26-e16-serve"
CELL = "nemotron3-nano-l26-e16-serve-chat-closed64"
CPU = jax.devices("cpu")[0]


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


def test_the_nemotron_h_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("nemotron_h_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert w.pattern == "MEMEM*EMEMEM*EMEMEM*EMEMEM" and w.layers == 26
    assert (w.hidden, w.heads, w.kv_heads, w.head_dim, w.eps) == \
        (2688, 32, 2, 128, 1e-5)
    assert (w.ssm_heads, w.ssm_head_dim, w.ssm_groups, w.ssm_state,
            w.conv_kernel, w.inner, w.conv_dim) == \
        (64, 64, 8, 128, 4, 4096, 6144)
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.expert_ffn, w.shared_ffn, w.routed_scale, w.vocab) == \
        (128, 0, 16, 6, 1856, 3712, 2.5, 16384)
    # a token multiplies: an M layer's in and out projections; a * layer's
    # q, o at 4,096 and k, v at 256; an E layer's router, the shared expert
    # and 6 x 16 / 128 = 3/4 of ONE two-matrix expert on this chip
    m = 2688 * 10304 + 4096 * 2688
    a = 2 * 2688 * 4096 + 2 * 2688 * 256
    e = 2688 * 128 + 2 * 2688 * 3712 + 2 * 2688 * 1856 * 3 // 4
    assert ref.matmul_params_per_token(w) == \
        12 * m + 3 * a + 11 * e + 2688 * 16384
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)
    # its routing margins are its own readings (three of them: a mixer
    # hands a flipped expert's effect to the positions after it)
    assert (ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN,
            ref.STATE_LOGIT_MARGIN) == (0.03, 0.02, 0.005)


def _tiny(ref, pattern):
    return ref.Widths(hidden=12, pattern=pattern, heads=4, kv_heads=2,
                      head_dim=4, ssm_heads=4, ssm_head_dim=3, ssm_groups=2,
                      ssm_state=5, conv_kernel=4, eps=1e-5, expert_ffn=6,
                      shared_ffn=7, router_experts=6, first_expert=1,
                      held_experts=3, per_token=2, norm_topk=True,
                      routed_scale=2.5, vocab=16)


def test_mamba_layer_by_hand():
    """One ``M`` layer at a tiny size against the equations written out
    with numpy, a token and a head at a time: the convolution from zero
    history, softplus steps, the state's decay and outer-product update,
    the read-out and the skip, the gate BEFORE the grouped norm."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref, "M")
    T, d, H, P, G, N, K = 256, 12, 4, 3, 2, 5, 4
    rng = np.random.default_rng(0)
    g = lambda *s: rng.standard_normal(s)
    p = {"w_in": g(d, 2 * 12 + 2 * G * N + H) * 0.3,
         "conv_w": g(12 + 2 * G * N, K) * 0.5, "conv_b": g(12 + 2 * G * N),
         "dt_bias": g(H) - 2.0, "A_log": np.log(np.arange(1, H + 1.0)),
         "D": g(H), "norm": {"scale": g(12)}, "w_out": g(12, d) * 0.3}
    lp = {"ln1": {"scale": g(d)}, "ssm": p}
    x = g(T, d)
    with jax.default_matmul_precision("highest"):
        got, margin = ref._layer(
            jnp.asarray(x, jnp.float32),
            jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), lp), w, "M")
    assert np.isinf(np.asarray(margin)).all()
    h = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * \
        lp["ln1"]["scale"]
    zxbcdt = h @ p["w_in"]
    z, xbc, dt = zxbcdt[:, :12], zxbcdt[:, 12:12 + 32], zxbcdt[:, 44:]
    silu = lambda a: a / (1 + np.exp(-a))
    u = np.zeros_like(xbc)
    for t in range(T):
        acc = p["conv_b"].copy()
        for i in range(K):
            if t - K + 1 + i >= 0:
                acc += p["conv_w"][:, i] * xbc[t - K + 1 + i]
        u[t] = silu(acc)
    xs, B, C = u[:, :12].reshape(T, H, P), \
        u[:, 12:22].reshape(T, G, N), u[:, 22:].reshape(T, G, N)
    delta = np.log1p(np.exp(dt + p["dt_bias"]))
    y = np.zeros((T, H, P))
    for hd in range(H):
        S, grp = np.zeros((P, N)), hd // (H // G)
        for t in range(T):
            S = np.exp(-delta[t, hd] * (hd + 1)) * S + \
                delta[t, hd] * np.outer(xs[t, hd], B[t, grp])
            y[t, hd] = S @ C[t, grp] + p["D"][hd] * xs[t, hd]
    gated = (y.reshape(T, 12) * silu(z)).reshape(T, G, 6)
    o = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
         ).reshape(T, 12) * p["norm"]["scale"]
    assert np.abs(np.asarray(got) - (x + o @ p["w_out"])).max() < 2e-5


def test_experts_layer_by_hand_and_its_margin():
    """One ``E`` layer: sigmoid scores, the selection bias that picks and
    does not weigh, top-2 renormalised and scaled by 2.5 over the HELD
    experts 1-3 of 6, un-gated relu² experts, the shared expert once; and
    ``held_margin`` is the move of a held expert's logit that flips it."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref, "E")
    T, d = 256, 12
    rng = np.random.default_rng(1)
    g = lambda *s: rng.standard_normal(s)
    moe = {"router": g(d, 6), "router_bias": g(6) * 0.3,
           "wi": g(3, d, 6) * 0.3, "wo": g(3, 6, d) * 0.3}
    lp = {"ln1": {"scale": g(d)}, "moe": moe,
          "shared": {"wi": g(d, 7) * 0.3, "wo": g(7, d) * 0.3}}
    x = g(T, d)
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                    tree)
    with jax.default_matmul_precision("highest"):
        got, margin = ref._layer(jnp.asarray(x, jnp.float32), f32(lp), w,
                                 "E")
    h = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * \
        lp["ln1"]["scale"]
    logits = h @ moe["router"]
    s = 1 / (1 + np.exp(-logits))
    relu2 = lambda a: np.maximum(a, 0) ** 2
    out = relu2(h @ lp["shared"]["wi"]) @ lp["shared"]["wo"]
    for i in range(T):
        sel = np.argsort(-(s[i] + moe["router_bias"]))[:2]
        for e in sel:
            if 1 <= e < 4:
                out[i] += 2.5 * s[i, e] / (s[i, sel].sum() + 1e-20) * (
                    relu2(h[i] @ moe["wi"][e - 1]) @ moe["wo"][e - 1])
    assert np.abs(np.asarray(got) - (x + out)).max() < 2e-5
    # nudge the router's column of the held expert that decides token 0's
    # margin by a little more than that margin: its selection flips
    margin = np.asarray(margin)
    assert np.isfinite(margin).all() and (margin > 0).all()
    i = int(np.argmax(margin < np.median(margin)))
    sel0 = set(np.argsort(-(s[i] + moe["router_bias"]))[:2])
    flips = 0
    for e in (1, 2, 3):
        for sign in (-1, 1):
            moved = logits[i].copy()
            moved[e] += sign * margin[i] * 1.001
            pick = 1 / (1 + np.exp(-moved)) + moe["router_bias"]
            flips += set(np.argsort(-pick)[:2]) != sel0
            moved[e] -= sign * margin[i] * 0.002        # ... and a hair less
            pick = 1 / (1 + np.exp(-moved)) + moe["router_bias"]
            assert set(np.argsort(-pick)[:2]) == sel0
    assert flips >= 1


def test_a_whole_stack_judges_its_own_tokens():
    ref, _ = _ref_and_widths()
    w = _tiny(ref, "ME*M")
    rng = np.random.default_rng(2)
    g = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    ssm = lambda: {"w_in": g(12, 48), "conv_w": g(32, 4), "conv_b": g(32),
                   "dt_bias": g(4), "A_log": g(4), "D": g(4),
                   "norm": {"scale": g(12) + 1}, "w_out": g(12, 12)}
    ln = lambda: {"ln1": {"scale": g(12) + 1}}
    params = {"embed": {"tokens": g(16, 12) * 4}, "lm_head": g(12, 16) * 4,
              "final_norm": {"scale": g(12) + 1}, "layers": [
        {**ln(), "ssm": ssm()},
        {**ln(), "moe": {"router": g(12, 6), "router_bias": g(6),
                         "wi": g(3, 12, 6), "wo": g(3, 6, 12)},
         "shared": {"wi": g(12, 7), "wo": g(7, 12)}},
        {**ln(), "attn": {"wq": g(12, 16), "wk": g(12, 8), "wv": g(12, 8),
                          "wo": g(16, 12)}},
        {**ln(), "ssm": ssm()}]}
    tokens = rng.integers(0, 16, 40)
    logits = ref.logits_of(w, params, tokens, CPU)
    assert logits.shape == (40, 16) and np.isfinite(logits).all()
    # causal: a later token changes no earlier logit
    other = tokens.copy()
    other[30] = (other[30] + 1) % 16
    again = ref.logits_of(w, params, other, CPU)
    assert np.array_equal(again[:30], logits[:30]) and \
        np.abs(again[30:] - logits[30:]).max() > 1e-4
    # its own greedy continuation scores 0 below its argmax wherever the
    # routing is decided, and a wrong token more
    prompt, out = list(tokens[:30]), []
    for _ in range(6):
        out.append(int(ref.logits_of(w, params, prompt + out, CPU)[-1]
                       .argmax()))
    gaps = ref.argmax_gaps(w, params, [prompt], [out], CPU)
    assert len(gaps) <= 6 and (gaps == 0.0).all()
    wrong = [(t + 1) % 16 for t in out]
    assert ref.argmax_gaps(w, params, [prompt], [wrong], CPU).sum() > 0 or \
        len(gaps) == 0
    loss = ref.loss(w, params, np.stack([tokens, other]), CPU)
    assert np.isfinite(loss) and loss > 0.0


def test_decided_holds_a_position_and_those_its_mixers_still_hold():
    ref, w = _ref_and_widths()
    assert (ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN,
            ref.STATE_LOGIT_MARGIN, ref.STATE_REACH, w.conv_kernel) == \
        (0.03, 0.02, 0.005, 3, 4)
    m = np.full(20, 0.5)
    m[1], m[9], m[15] = 0.004, 0.025, 0.015
    # 1: its own 0.004, and positions 2-7 hold it among their six; 9: its
    # own 0.025 (a neighbour's margin would do, its own does not) and none
    # after it; 15: its own, and 16-18 hold it among their THREE (0.015 is
    # under 0.02), 19-21 among the three before those (over 0.005: fine)
    want = np.ones(20, bool)
    want[1:8] = False
    want[9] = False
    want[15:19] = False
    assert ref.decided(m, w).tolist() == want.tolist()
    assert ref.neighbours_decided(m, w)[9] and \
        ref.neighbours_decided(m, w)[15]
    assert ref.decided(np.full(5, np.inf), w).all()


def test_ssm_work_by_hand():
    cfg = SimpleNamespace(layer_kinds=(3, -1, 3, -1, 3, 0), ssm_heads=64,
                          ssm_head_dim=64, ssm_groups=8, ssm_state_size=128)
    assert ssm_work.ssm_layers(cfg) == 3
    assert ssm_work.state_values(cfg) == 524288            # 2 MiB of float32
    # 64 rows: each reads and writes 2 MiB in each of the 3 layers
    assert ssm_work.state_bytes(cfg, 64) == 3 * 64 * 2 * 2 * 2 ** 20
    # a token: 2 FLOPs a state value for the update, 2 for the read-out,
    # and its own pair: C·B over 8 x 128, the mix over 64 x 64
    assert ssm_work.scan_flops(cfg, 1) == \
        3 * (4 * 524288 + 2 * (1024 + 4096))
    # 64 one-token rows: 405 MFLOP (2 us at the peak) against 805 MB (983
    # us): the bytes bound it
    assert ssm_work.scan_flops(cfg, 64) / 197e12 < \
        ssm_work.state_bytes(cfg, 64) / 819e9 / 400
    none = SimpleNamespace(layer_kinds=(0, 1), ssm_heads=0, ssm_head_dim=0,
                           ssm_groups=1, ssm_state_size=0)
    assert ssm_work.state_bytes(none, 64) == 0.0


def _recorded_run(model_cfg, dispatch_args, rows):
    """A run as the harness hands it to a reader, from recorded facts: two
    traced server steps whose launches carry ``dispatch_args``, and a
    device attribution ``rows`` {(program, scope, kind): ns}."""
    steps = [{"name": "serving/engine_step", "ph": "X", "ts": 10.0 * i,
              "dur": 9.0, "tid": 1, "args": {"program": "split"}}
             for i in range(3)]
    events = list(steps) + [
        {"name": "serving/dispatch", "ph": "X", "ts": 10.0 * i + 1,
         "dur": 2.0, "tid": 1, "args": dict(dispatch_args)}
        for i in range(3)]
    run = SimpleNamespace(
        facts={"traced_step_range": (1, 3), "model": model_cfg,
               "steps": [None] * 3, "spans": events},
        trace=None, peaks={"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9},
        span_name="benchmark/serve_step", flops=flops, stats=stats,
        reduce=reduce,
        program_spans=lambda name: [e for e in events if e["name"] == name])
    run._scopes_analysis = {
        "device": {"rows": rows, "scoped_ns": sum(rows.values()),
                   "sum_ns": sum(rows.values())},
        "steps": 2, "events": events}
    return run


def test_the_two_readers_on_a_recorded_run():
    cfg = SimpleNamespace(recurrent=True, layer_kinds=(3, -1, 0) * 4,
                          ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
                          ssm_state_size=128)
    rows = {("serve_split_r64_c128", "ssm_scan", "fwd"): 6.0e6,
            ("serve_split_r64_c128", "ssm_state", "fwd"): 2.0e6,
            ("serve_split_r64_c128", "ssm_in", "fwd"): 1.0e6,
            ("serve_split_r64_c128", "ssm_out", "fwd"): 0.5e6,
            ("serve_split_r64_c128", "moe", "fwd"): 9.0e6}
    args = {"program": "split", "tokens": 300, "state_rows": 64,
            "state_resets": 1, "ssm_chunk_tokens": 240}
    run = _recorded_run(cfg, args, rows)
    # the six scopes' 9.5 ms over the two traced steps
    assert _reader("ssm_ms_per_step").read(run) == 4.75
    # two traced launches of 64 rows: 2 x 64 x 4 layers x 4 MiB over
    # 819 GB/s = 2.62 ms of the 8 ms under ssm_scan + ssm_state
    least = 2 * 64 * 4 * 2 * 2 * 2 ** 20 / 819e9
    got = _reader("ssm_scan_roofline").read(run)
    assert abs(got - 100 * least / 8.0e-3) < 1e-9 and 32 < got < 33
    # a launch without the counters (the parent's program) gives nothing
    bare = _recorded_run(cfg, {"program": "split", "tokens": 300}, rows)
    assert _reader("ssm_scan_roofline").read(bare) is None
    # nor does a program without the scopes
    other = _recorded_run(cfg, args, {("serve_split_r64_c128", "moe",
                                       "fwd"): 9.0e6})
    assert _reader("ssm_scan_roofline").read(other) is None
    assert _reader("ssm_ms_per_step").read(other) is None


def test_new_readers_read_nothing_from_an_empty_run():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    for name, layer in (("ssm_ms_per_step", "step programs"),
                        ("ssm_scan_roofline", "kernels")):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["layer"] == layer and \
            entry["moves"] == "serve_tokens_per_s"
        assert _reader(name).read(run) is None


def test_the_cell_is_the_issues_and_the_mix_untouched():
    from benchmark.lib import traffic
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chat-closed64", 1)
    mix = traffic.load_mix("chat-closed64")
    assert mix["arrival"] == {"process": "closed", "clients": 64}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.9, "min": 16, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 96,
                                    "sigma": 0.7, "min": 8, "max": 512}
    assert (mix["max_total_tokens"], mix["cycle_seed"],
            mix["ramp_seconds"]) == (4096, 11, 10)
    conf = model.load_config(CONFIG)["engine"]
    assert conf["prefill_chunk"] == 128 == \
        model.load_config(CONFIG)["chunk_size"]
    assert conf["max_sequences"] == mix["arrival"]["clients"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"moe_ms_per_step", "moe_shared_ms_per_step", "rows_per_step",
            "idle_attributed_share.serve"} <= mine
    assert not mine & {"expert_matmul_roofline", "serve_mlp_ms_per_step",
                       "kv_window_dead_share", "kv_extent_utilization"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}


def test_rehearsal_of_the_cell():
    """Tiny widths, the mix as it is: every check, and the counts a CPU
    run can give."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000043", "--seconds", "6",
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
