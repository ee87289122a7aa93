"""The Jamba reference (``reference/jamba_decoder.py``) on its own: the
contract, the Mamba-1 mixer by hand, the whole stack by its equations, the
two new readers and the cell's entries. After
``test_granitemoehybrid_reference.py``; the program against this reference
is ``tests/test_jamba.py``."""

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

from benchmark.lib import flops, model, selective_scan_work, stats
from benchmark.trace import reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "jamba2-3b-l28-serve"
CELL = "jamba2-3b-l28-serve-rag-closed64"
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


def test_the_jamba_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("jamba_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert w.layers == 28 and [
        l for l, name in enumerate(w.layer_types) if name == "attention"
    ] == [7, 21] and w.layer_types.count("mamba") == 26
    assert (w.hidden, w.heads, w.kv_heads, w.head_dim, w.eps, w.ffn,
            w.vocab, w.tied) == (2560, 20, 1, 128, 1e-6, 8192, 65536, True)
    assert (w.inner, w.ssm_state, w.dt_rank, w.conv_kernel) == \
        (5120, 16, 160, 4)
    # a token multiplies: a mamba mixer's in (2 x 5,120 wide) and out
    # projections, W_x and W_dt; or attention's q, o at 2,560 and k, v at
    # 128; the MLP's three matrices in EVERY layer; the tied head
    m = 3 * 2560 * 5120 + 5120 * 192 + 160 * 5120
    a = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert ref.matmul_params_per_token(w) == \
        26 * m + 2 * a + 28 * 3 * 2560 * 8192 + 2560 * 65536
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)
    assert all(hasattr(ref, name) for name in model.REFERENCE_CONTRACT)
    # the family's sparse layers are another reference's
    try:
        ref.Widths.from_hf({**model.published_keys(model.load_config(
            CONFIG)), "num_experts": 16})
    except ValueError as e:
        assert "num_experts" in str(e)
    else:
        raise AssertionError("num_experts 16 was taken")


def _tiny(ref, types=("mamba", "attention", "mamba")):
    return ref.Widths(
        hidden=12, layer_types=tuple(types), heads=4, kv_heads=1, head_dim=3,
        inner=24, ssm_state=5, dt_rank=3, conv_kernel=4, eps=1e-6, ffn=10,
        vocab=16, tied=True)


def _tree(w, seed=0):
    rng = np.random.default_rng(seed)

    def mat(*shape, std=0.5):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    d, n, r = w.inner, w.ssm_state, w.dt_rank
    layers = []
    for name in w.layer_types:
        lp = {"ln1": {"scale": mat(w.hidden, std=0.1) + 1.0},
              "ln2": {"scale": mat(w.hidden, std=0.1) + 1.0},
              "mlp": {"wg": mat(w.hidden, w.ffn), "wi": mat(w.hidden, w.ffn),
                      "wo": mat(w.ffn, w.hidden)}}
        if name == "mamba":
            lp["ssm"] = {
                "w_in": mat(w.hidden, 2 * d),
                "conv_w": mat(d, w.conv_kernel), "conv_b": mat(d),
                "w_x": mat(d, r + 2 * n),
                "dt_norm": {"scale": mat(r, std=0.1) + 1.0},
                "b_norm": {"scale": mat(n, std=0.1) + 1.0},
                "c_norm": {"scale": mat(n, std=0.1) + 1.0},
                "w_dt": mat(r, d), "dt_bias": mat(d) - 2.0,
                "A_log": mat(n, d, std=0.3), "D": mat(d) + 1.0,
                "w_out": mat(d, w.hidden)}
        else:
            qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
            lp["attn"] = {"wq": mat(w.hidden, qd), "wk": mat(w.hidden, kd),
                          "wv": mat(w.hidden, kd), "wo": mat(qd, w.hidden)}
        layers.append(lp)
    return {"embed": {"tokens": mat(w.vocab, w.hidden, std=0.1)},
            "layers": layers,
            "final_norm": {"scale": mat(w.hidden, std=0.1) + 1.0}}


def _mixer_by_hand(w, p, hin):
    """The Mamba-1 mixer in float64 numpy, a token and a CHANNEL at a time,
    from the published module's equations (``[d, N]`` state, as it has
    it)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(hin, np.float64)
    t_len, d, n, r, k = len(x), w.inner, w.ssm_state, w.dt_rank, \
        w.conv_kernel
    silu = lambda v: v / (1.0 + np.exp(-v))
    rms = lambda v, s: v / np.sqrt(np.mean(v * v) + w.eps) * s
    xz = x @ p["w_in"]
    xs, z = xz[:, :d], xz[:, d:]
    a = -np.exp(p["A_log"]).T                                   # [d, N]
    state = np.zeros((d, n))
    out = np.zeros((t_len, d))
    for t in range(t_len):
        u = np.array([silu(sum(p["conv_w"][ch, i] * xs[t - k + 1 + i, ch]
                               for i in range(k) if t - k + 1 + i >= 0)
                           + p["conv_b"][ch]) for ch in range(d)])
        dbc = u @ p["w_x"]
        delta = rms(dbc[:r], p["dt_norm"]["scale"]) @ p["w_dt"] + \
            p["dt_bias"]
        delta = np.log1p(np.exp(delta))                         # softplus
        b = rms(dbc[r:r + n], p["b_norm"]["scale"])
        c = rms(dbc[r + n:], p["c_norm"]["scale"])
        state = np.exp(delta[:, None] * a) * state + \
            (delta * u)[:, None] * b[None, :]
        out[t] = (state @ c + p["D"] * u) * silu(z[t])
    return out @ p["w_out"]


def test_the_mamba1_mixer_by_hand():
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    p = _tree(w, 1)["layers"][0]["ssm"]
    hin = jnp.asarray(np.random.default_rng(2).normal(0, 1, (9, 12)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.mamba_mixer(w, p, hin))
    want = _mixer_by_hand(w, p, hin)
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    # each of the three inner norms, the step's bias, D and the gate count
    for leaf, wrong in (("dt_norm", None), ("b_norm", None),
                        ("c_norm", None), ("dt_bias", 0.0), ("D", 0.0)):
        q = dict(p)
        q[leaf] = {"scale": p[leaf]["scale"] * 1.5} if wrong is None \
            else p[leaf] * wrong
        with jax.default_matmul_precision("highest"):
            other = np.asarray(ref.mamba_mixer(w, q, hin))
        assert np.abs(other - want).max() > 1e-2, leaf


def test_a_whole_stack_by_its_equations_and_its_own_tokens():
    """Three layers by hand from the docstring's equations (the mixer is
    the one checked above, the attention written out: four query heads over
    ONE key / value head, no positions), two norms a layer, the tied head;
    ``argmax_gaps`` of the stack's own greedy tokens is zero and of any
    other the plain logit difference; ``loss`` is the mean next-token
    cross-entropy."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    params = _tree(w, 3)
    tokens = np.random.default_rng(2).integers(0, 16, 24)
    got = ref.logits_of(w, params, tokens, CPU)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(tokens)]
        for name, lp in zip(w.layer_types, params["layers"]):
            h = ref.dense._rms_norm(x, lp["ln1"]["scale"], w.eps)
            if name == "mamba":
                mixed = ref.mamba_mixer(w, lp["ssm"], h)
            else:
                a = lp["attn"]
                q = (h @ a["wq"]).reshape(24, 4, 3)
                k = jnp.repeat((h @ a["wk"]).reshape(24, 1, 3), 4, axis=1)
                v = jnp.repeat((h @ a["wv"]).reshape(24, 1, 3), 4, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) * 3 ** -0.5
                s = jnp.where(jnp.tril(jnp.ones((24, 24), bool))[None], s,
                              -jnp.inf)
                mixed = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                   v).reshape(24, 12) @ a["wo"]
            x = x + mixed
            h2 = ref.dense._rms_norm(x, lp["ln2"]["scale"], w.eps)
            m = lp["mlp"]
            x = x + (jax.nn.silu(h2 @ m["wg"]) * (h2 @ m["wi"])) @ m["wo"]
        want = ref.dense._rms_norm(x, params["final_norm"]["scale"], w.eps) \
            @ params["embed"]["tokens"].T
    assert np.abs(got - np.asarray(want)).max() < 1e-4
    prompt, out = tokens[:8].tolist(), []
    for _ in range(6):
        out.append(int(ref.logits_of(w, params, prompt + out, CPU)[-1]
                       .argmax()))
    assert np.array_equal(
        ref.argmax_gaps(w, params, [prompt], [out], CPU), np.zeros(6))
    other = list(out)
    other[3] = (other[3] + 1) % 16
    gaps = ref.argmax_gaps(w, params, [prompt], [other], CPU)
    full = ref.logits_of(w, params, prompt + other[:3], CPU)[-1]
    assert len(gaps) == 6 and \
        abs(gaps[3] - (full.max() - full[other[3]])) < 1e-4
    assert abs(ref.loss(w, params, tokens[None], CPU) - float(np.mean([
        np.log(np.exp(got[t]).sum()) - got[t, tokens[t + 1]]
        for t in range(23)]))) < 1e-4


def test_an_untied_head_is_read_where_the_file_says_so():
    ref, _ = _ref_and_widths()
    w = ref.Widths(**{**_tiny(ref).__dict__, "tied": False})
    params = _tree(w, 4)
    params["lm_head"] = jnp.asarray(
        np.random.default_rng(5).normal(0, 0.5, (12, 16)), jnp.float32)
    tied = ref.logits_of(_tiny(ref), params, [1, 2, 3], CPU)
    untied = ref.logits_of(w, params, [1, 2, 3], CPU)
    assert np.abs(tied - untied).max() > 1e-2


def test_work_functions_at_the_cells_widths():
    cfg = SimpleNamespace(
        layer_kinds=tuple(0 if l in (7, 21) else 4 for l in range(28)),
        ssm_inner_size=5120, ssm_state_size=16)
    assert selective_scan_work.selective_layers(cfg) == 26
    assert selective_scan_work.state_values(cfg) == 81920   # 320 KiB float32
    # ISSUE 49's count: state_rows x 26 x 2 x 327,680 B
    assert selective_scan_work.state_bytes(cfg, 64) == 64 * 26 * 2 * 327680
    assert 1.3e-3 < selective_scan_work.state_bytes(cfg, 64) / 819e9 < 1.4e-3
    # 4 FLOPs a state value and fed token: 1,200 tokens a launch
    assert selective_scan_work.scan_flops(cfg, 1200) == \
        26 * 1200 * 4 * 81920
    # a launch of 64 rows and 1,200 tokens: the bytes bound it, not the FLOPs
    assert selective_scan_work.scan_flops(cfg, 1200) / 197e12 < \
        selective_scan_work.state_bytes(cfg, 64) / 819e9
    # the other kind's work library counts none of this stack's layers
    from benchmark.lib import ssm_work
    assert ssm_work.ssm_layers(cfg) == 0


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
    cfg = SimpleNamespace(
        selective=True, recurrent=True,
        layer_kinds=tuple(0 if l in (7, 21) else 4 for l in range(28)),
        ssm_inner_size=5120, ssm_state_size=16)
    split = {"program": "split", "tokens": 1200, "state_rows": 64,
             "state_resets": 1, "ssm_chunk_tokens": 1145}
    decode = {"program": "decode", "tokens": 64, "state_rows": 64,
              "state_resets": 0, "ssm_chunk_tokens": 0}
    rows = {("serve_split_r64_c128", "ssm_scan", "forward"): 6.0e6,
            ("serve_split_r64_c128", "ssm_state", "forward"): 1.0e6,
            ("serve_decode_r64", "ssm_scan", "forward"): 1.0e6,
            ("serve_split_r64_c128", "ssm_select", "forward"): 4.0e6,
            ("serve_split_r64_c128", "mlp", "forward"): 30.0e6}
    # the untraced first step is not counted; two traced launches advance
    # 128 rows: 128 x 26 x 640 KiB over 819 GB/s = 2.66 ms of the 8 ms
    # under the two scopes (the FLOPs, 1,264 tokens' worth, are 0.05 ms)
    run = _recorded_run(cfg, [split, split, decode], rows)
    least = 128 * 26 * 2 * 327680 / 819e9
    got = _reader("selective_scan_roofline").read(run)
    assert abs(got - 100 * least / 8.0e-3) < 1e-9 and 33 < got < 34
    # a launch without the counters (the parent's program), a program
    # without the scopes, another kind of stack: nothing
    bare = {"program": "split", "tokens": 1200}
    assert _reader("selective_scan_roofline").read(
        _recorded_run(cfg, [bare] * 3, rows)) is None
    assert _reader("selective_scan_roofline").read(_recorded_run(
        cfg, [split] * 3,
        {("serve_split_r64_c128", "mlp", "forward"): 30.0e6})) is None
    mamba2 = SimpleNamespace(recurrent=True, selective=False)
    assert _reader("selective_scan_roofline").read(
        _recorded_run(mamba2, [split] * 3, rows)) is None
    assert _reader("selective_scan_roofline").read(
        _recorded_run(SimpleNamespace(), [split] * 3, rows)) is None


def test_the_new_readers_read_nothing_from_an_empty_run():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["selective_scan_roofline"] == {
        "name": "selective_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert entries["ssm_select_ms_per_step"] == {
        "name": "ssm_select_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step programs",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    for name in ("selective_scan_roofline", "ssm_select_ms_per_step"):
        reader = _reader(name)
        assert (reader.LAYER, reader.MOVES) == \
            (entries[name]["layer"], entries[name]["moves"])
        assert reader.read(run) is None


def test_the_cell_is_the_issues_and_its_lengths_are_cell_6s():
    from benchmark.lib import traffic
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rag-closed64", 1) and bench["workloads"][-1] == cell
    mix, six = traffic.load_mix("rag-closed64"), \
        traffic.load_mix("rag-closed16")
    assert mix["arrival"] == {"process": "closed", "clients": 64}
    for key in ("prompt_tokens", "output_tokens", "max_total_tokens",
                "ramp_seconds", "trace_seconds"):
        assert mix[key] == six[key], key
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 5120,
                                    "sigma": 0.5, "min": 1024, "max": 10240}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.5, "min": 64, "max": 768}
    assert (mix["max_total_tokens"], mix["cycle_seed"], mix["ramp_seconds"],
            mix["trace_seconds"]) == (11008, 49, 60, 3)
    conf = model.load_config(CONFIG)
    engine = conf["engine"]
    assert engine == {"dtype": "bfloat16", "max_sequences": 64,
                      "num_blocks": 5504, "block_size": 128,
                      "max_seq_len": 11008, "max_batch_tokens": 2048,
                      "prefill_chunk": 128}
    # no request can fail: 64 x 11,008 tokens fit the arena
    assert 64 * mix["max_total_tokens"] <= \
        engine["num_blocks"] * engine["block_size"]
    assert mix["max_total_tokens"] <= engine["max_seq_len"]
    # NOTHING is cut: every published key at its published value
    assert conf["reduced"] == [] and conf["changed"] == {}
    published = model.load_published(conf)
    assert published["source"] == conf["source"] and all(
        conf[k] == v for k, v in published.items())
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == {
        "device_idle_share.serve", "ttft_p90_closed_ms", "rows_per_step",
        "token_slot_utilization", "decode_program_step_share",
        "prefill_tokens_per_step", "ssm_ms_per_step",
        "idle_ms_per_step.fanout", "idle_ms_per_step.frontend",
        "idle_ms_per_step.caller", "idle_ms_per_step.launch_and_fetch",
        "idle_attributed_share.serve", "ssm_select_ms_per_step",
        "selective_scan_roofline",
        # the MQA 20/1 layers' own attention and merge (the history
        # kernel's readers move ``itl_p95_ms`` or want a window kind's
        # counts in the dispatch span: PERF.md section 3)
        "attn_chunk_ms_per_step"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"serve_tokens_per_s", "setup_s"}


def test_rehearsal_of_the_cell():
    """Tiny widths, the mix as it is: every check, and the counts a CPU
    run can give."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4900000049", "--seconds", "20",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    start = next(l for l in lines if l.get("phase") == "start")
    assert "jamba_decoder" in json.dumps(start)
    checks = next(l for l in lines if l.get("phase") == "checks")
    assert [k for k, v in checks.items() if v is False] == []
    last = lines[-1]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and \
        last["correct"]
    assert {"rows_per_step", "token_slot_utilization",
            "decode_program_step_share"} <= set(last["metrics"])
