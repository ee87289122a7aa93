"""The GraniteMoeHybrid reference (``reference/granitemoehybrid_decoder.py``)
on its own: the contract, a layer by hand, its margins, the new reader and
the cell's entries. After ``test_nemotron_h_reference.py``; the program
against this reference is ``tests/test_granitemoehybrid.py``."""

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

from benchmark.lib import expert_matmul_work, flops, model, ssm_work, stats
from benchmark.trace import reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "granite-4.0-h-small-l10-e36-serve"
CELL = "granite-4.0-h-small-l10-e36-serve-reason-closed64"
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


def test_the_granitemoehybrid_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("granitemoehybrid_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert w.layer_types == ("mamba",) * 5 + ("attention",) + \
        ("mamba",) * 4 and w.layers == 10
    assert (w.hidden, w.heads, w.kv_heads, w.head_dim, w.eps) == \
        (4096, 32, 8, 128, 1e-5)
    assert (w.ssm_heads, w.ssm_head_dim, w.ssm_groups, w.ssm_state,
            w.conv_kernel, w.inner, w.conv_dim) == \
        (128, 64, 1, 128, 4, 8192, 8448)
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.expert_ffn, w.shared_ffn, w.vocab) == \
        (72, 0, 36, 10, 768, 1536, 50176)
    assert (w.embedding_multiplier, w.attention_multiplier,
            w.residual_multiplier, w.logits_scaling) == \
        (12.0, 0.0078125, 0.22, 16.0)
    # a token multiplies: a mamba mixer's in and out projections, or
    # attention's q, o at 4,096 and k, v at 1,024; and in EVERY layer the
    # router over 72, the shared expert and 10 x 36 / 72 = 5 of its ten
    # three-matrix experts on this chip; the tied head over the slice
    m = 4096 * 16768 + 8192 * 4096
    a = 2 * 4096 * 4096 + 2 * 4096 * 1024
    e = 4096 * 72 + 3 * 4096 * 1536 + 5 * 3 * 4096 * 768
    assert ref.matmul_params_per_token(w) == \
        9 * m + a + 10 * e + 4096 * 50176
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)
    assert all(hasattr(ref, name) for name in model.REFERENCE_CONTRACT)


def _tiny(ref, types=("mamba", "attention")):
    return ref.Widths(
        hidden=12, layer_types=tuple(types), heads=4, kv_heads=2, head_dim=3,
        ssm_heads=4, ssm_head_dim=6, ssm_groups=1, ssm_state=5,
        conv_kernel=4, eps=1e-5, expert_ffn=6, shared_ffn=7,
        router_experts=6, first_expert=1, held_experts=3, per_token=3,
        embedding_multiplier=12.0, attention_multiplier=0.125,
        residual_multiplier=0.22, logits_scaling=16.0, vocab=16)


def _tree(w, seed=0):
    rng = np.random.default_rng(seed)

    def mat(*shape, std=0.5):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    d, cd = w.inner, w.conv_dim
    layers = []
    for name in w.layer_types:
        lp = {"ln1": {"scale": mat(w.hidden, std=0.1) + 1.0},
              "ln2": {"scale": mat(w.hidden, std=0.1) + 1.0},
              "moe": {"router": mat(w.hidden, w.router_experts, std=1.0),
                      "wg": mat(w.held_experts, w.hidden, w.expert_ffn),
                      "wi": mat(w.held_experts, w.hidden, w.expert_ffn),
                      "wo": mat(w.held_experts, w.expert_ffn, w.hidden)},
              "shared": {"wg": mat(w.hidden, w.shared_ffn),
                         "wi": mat(w.hidden, w.shared_ffn),
                         "wo": mat(w.shared_ffn, w.hidden)}}
        if name == "mamba":
            lp["ssm"] = {
                "w_in": mat(w.hidden, d + cd + w.ssm_heads),
                "conv_w": mat(cd, w.conv_kernel), "conv_b": mat(cd),
                "dt_bias": mat(w.ssm_heads),
                "A_log": jnp.log(jnp.arange(1, w.ssm_heads + 1,
                                            dtype=jnp.float32)),
                "D": mat(w.ssm_heads) + 1.0,
                "norm": {"scale": mat(d, std=0.1) + 1.0},
                "w_out": mat(d, w.hidden)}
        else:
            qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
            lp["attn"] = {"wq": mat(w.hidden, qd), "wk": mat(w.hidden, kd),
                          "wv": mat(w.hidden, kd), "wo": mat(qd, w.hidden)}
        layers.append(lp)
    return {"embed": {"tokens": mat(w.vocab, w.hidden, std=0.1)},
            "layers": layers,
            "final_norm": {"scale": mat(w.hidden, std=0.1) + 1.0}}


def test_experts_part_and_margin_by_hand():
    """One token through a layer's second part, in numpy: the three
    largest of six logits kept, their softmax, the held ones' GLUs
    weighed, the shared expert; the margin is the least distance of a HELD
    expert's logit to the boundary it would have to cross."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    lp = _tree(w)["layers"][0]
    hin = jnp.asarray(np.random.default_rng(1).normal(0, 1, (5, 12)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.experts_part(hin, lp["moe"], w))
        margin = np.asarray(ref.held_margin(hin, lp["moe"], w))
        gate, sel = (np.asarray(t) for t in ref.route(hin, lp["moe"], w))
    m = {k: np.asarray(v, np.float64) for k, v in lp["moe"].items()}
    x = np.asarray(hin, np.float64)
    silu = lambda t: t / (1.0 + np.exp(-t))
    for t in range(5):
        logits = x[t] @ m["router"]
        order = np.argsort(-logits)
        kept = order[:3]
        assert kept.tolist() == sel[t].tolist()
        weight = np.exp(logits[kept]) / np.exp(logits[kept]).sum()
        want = np.zeros(12)
        for e, g in zip(kept, weight):
            assert abs(gate[t, e] - g) < 1e-6
            if 1 <= e < 4:                       # experts 1..3 are held
                i = e - 1
                want += g * ((silu(x[t] @ m["wg"][i]) * (x[t] @ m["wi"][i]))
                             @ m["wo"][i])
        assert np.abs(got[t] - want).max() < 1e-4
        last_in, best_out = logits[order[2]], logits[order[3]]
        moves = [logits[e] - best_out if e in kept else last_in - logits[e]
                 for e in (1, 2, 3)]
        assert abs(margin[t] - min(moves)) < 1e-5 and margin[t] >= 0


def test_a_whole_stack_by_its_equations_and_its_own_tokens():
    """Two layers by hand from the docstring's equations (the mixer is
    ``nemotron_h_decoder``'s, checked there against ``transformers``), the
    four scalars where they belong; and ``argmax_gaps`` of the stack's own
    greedy tokens is zero, in units of the logits' spread for any other."""
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    params = _tree(w, 3)
    tokens = np.random.default_rng(2).integers(0, 16, 24)
    got = ref.logits_of(w, params, tokens, CPU)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(tokens)] * 12.0
        for name, lp in zip(w.layer_types, params["layers"]):
            h = ref.dense._rms_norm(x, lp["ln1"]["scale"], w.eps)
            if name == "mamba":
                mixed = ref.hybrid.mamba_mixer(w, lp["ssm"], h)
            else:
                a = lp["attn"]
                q = (h @ a["wq"]).reshape(24, 4, 3)
                k = jnp.repeat((h @ a["wk"]).reshape(24, 2, 3), 2, axis=1)
                v = jnp.repeat((h @ a["wv"]).reshape(24, 2, 3), 2, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) * 0.125
                s = jnp.where(jnp.tril(jnp.ones((24, 24), bool))[None], s,
                              -jnp.inf)
                mixed = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                   v).reshape(24, 12) @ a["wo"]
            x = x + 0.22 * mixed
            h2 = ref.dense._rms_norm(x, lp["ln2"]["scale"], w.eps)
            sh = lp["shared"]
            x = x + 0.22 * (ref.experts_part(h2, lp["moe"], w) +
                            ref._glu_unit(h2, sh["wg"], sh["wi"], sh["wo"]))
        want = ref.dense._rms_norm(x, params["final_norm"]["scale"], w.eps) \
            @ params["embed"]["tokens"].T / 16.0
    assert np.abs(got - np.asarray(want)).max() < 1e-5
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
        assert abs(gaps[3] - 1.28 / full.std() *
                   (full.max() - full[other[3]])) < 1e-3
    finally:
        (ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN,
         ref.STATE_LOGIT_MARGIN) = kept
    assert abs(ref.loss(w, params, tokens[None], CPU) - float(np.mean([
        np.log(np.exp(got[t]).sum()) - got[t, tokens[t + 1]]
        for t in range(23)]))) < 1e-4


def test_decided_holds_a_position_and_those_its_mixers_still_hold():
    ref, _ = _ref_and_widths()
    w = _tiny(ref)
    big = 1.0
    margin = np.full(12, big)
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
    cfg = SimpleNamespace(
        layer_kinds=(3,) * 5 + (0,) + (3,) * 4, layer_sparse=(1,) * 10,
        ssm_heads=128, ssm_head_dim=64, ssm_groups=1, ssm_state_size=128,
        hidden_size=4096, intermediate_size=768, num_experts=72,
        experts_held=(0, 36), num_experts_per_tok=10)
    assert ssm_work.state_values(cfg) == 2 ** 20            # 4 MiB float32
    # a 64-row decode step: 9 layers x 64 rows x 8 MiB in and out = 4.8 GB
    assert ssm_work.state_bytes(cfg, 64) == 9 * 64 * 2 * 4 * 2 ** 20
    assert 5.8e-3 < ssm_work.state_bytes(cfg, 64) / 819e9 < 6.0e-3
    # 64 rows x top-10 of 72: every held expert is hit (1 - 0.861 ** 64)
    hit = expert_matmul_work.expected_experts_hit(cfg, 64)
    assert 35.99 < hit <= 36.0
    # 10 layers x 36 experts x 3 x 4096 x 768 x 2 B = 6.8 GB a step
    # (+ 320 routed rows in and out a layer: 0.05 GB)
    assert 6.79e9 < expert_matmul_work.decode_step_bytes(cfg, 64) < 6.86e9


def _recorded_run(model_cfg, launches, ops):
    """A run as the harness hands it to a reader, from recorded facts:
    three server steps of which the last two are traced, each with one
    launch (``launches``: the ``serving/dispatch`` arguments), and a device
    attribution ``ops`` {(program, instruction): (ns, scope)}."""
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
        "device": {"ops": ops, "rows": {}, "scoped_ns": 0, "sum_ns": 0},
        "steps": 2, "events": events}
    return run


def test_the_decode_state_reader_on_a_recorded_run():
    cfg = SimpleNamespace(recurrent=True,
                          layer_kinds=(3,) * 5 + (0,) + (3,) * 4,
                          ssm_heads=128, ssm_head_dim=64, ssm_groups=1,
                          ssm_state_size=128)
    decode = {"program": "decode", "tokens": 64, "state_rows": 64,
              "state_resets": 0, "ssm_chunk_tokens": 0}
    split = {"program": "split", "tokens": 150, "state_rows": 64,
             "state_resets": 1, "ssm_chunk_tokens": 90}
    ops = {("serve_decode_r64", "fusion.1"): (5.0e6, "ssm_scan"),
           ("serve_decode_r64", "fusion.2"): (3.0e6, "ssm_state"),
           ("serve_decode_r64", "fusion.3"): (9.0e6, "moe_experts"),
           ("serve_split_r64_c128", "fusion.4"): (7.0e6, "ssm_scan")}
    # the untraced first step is not counted; of the two traced launches
    # ONE is a decode step: 64 rows x 9 layers x 8 MiB over 819 GB/s =
    # 5.9 ms of the 8 ms under the two scopes in the decode program
    run = _recorded_run(cfg, [decode, split, decode], ops)
    least = 64 * 9 * 2 * 4 * 2 ** 20 / 819e9
    got = _reader("ssm_decode_state_roofline").read(run)
    assert abs(got - 100 * least / 8.0e-3) < 1e-9 and 73 < got < 74
    # no decode launch in the traced range, a launch without the counters
    # (the parent's program), a program without the scopes: nothing
    assert _reader("ssm_decode_state_roofline").read(
        _recorded_run(cfg, [decode, split, split], ops)) is None
    bare = {"program": "decode", "tokens": 64}
    assert _reader("ssm_decode_state_roofline").read(
        _recorded_run(cfg, [bare, bare, bare], ops)) is None
    assert _reader("ssm_decode_state_roofline").read(_recorded_run(
        cfg, [decode] * 3, {("serve_decode_r64", "fusion.3"):
                            (9.0e6, "moe_experts")})) is None
    dense = SimpleNamespace(recurrent=False)
    assert _reader("ssm_decode_state_roofline").read(
        _recorded_run(dense, [decode] * 3, ops)) is None


def test_the_new_reader_reads_nothing_from_an_empty_run():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "ssm_decode_state_roofline")
    assert entry == {"name": "ssm_decode_state_roofline", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "serve_tokens_per_s",
                     "workloads": [CELL]}
    assert _reader("ssm_decode_state_roofline").read(run) is None


def test_the_cell_is_the_issues_and_the_mix_untouched():
    from benchmark.lib import traffic
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "reason-closed64", 1)
    mix = traffic.load_mix("reason-closed64")
    assert mix["arrival"] == {"process": "closed", "clients": 64}
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 16,
                                    "max": 128}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384,
                                    "max": 640}
    assert (mix["max_total_tokens"], mix["cycle_seed"], mix["ramp_seconds"],
            mix["trace_seconds"]) == (768, 17, 40, 3)
    conf = model.load_config(CONFIG)
    engine = conf["engine"]
    assert engine["max_sequences"] == mix["arrival"]["clients"] == 64
    # no request can fail: 64 x 768 tokens fit the arena
    assert 64 * mix["max_total_tokens"] <= \
        engine["num_blocks"] * engine["block_size"]
    assert mix["max_total_tokens"] <= engine["max_seq_len"]
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "vocab_size", "expert_share"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"ssm_decode_state_roofline", "ssm_scan_roofline",
            "ssm_ms_per_step", "expert_matmul_roofline", "moe_ms_per_step",
            "moe_router_ms_per_step", "moe_shared_ms_per_step",
            "rows_per_step", "decode_program_step_share",
            "idle_attributed_share.serve"} <= mine
    assert not mine & {"serve_mlp_ms_per_step", "kv_window_dead_share",
                       "kv_extent_utilization", "paged_attn_lse_roofline"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert {"serve_tokens_per_s", "setup_s"} <= e2e <= \
        {"serve_tokens_per_s", "setup_s", "itl_p95_ms"}


def test_rehearsal_of_the_cell():
    """Tiny widths, the mix as it is: every check, and the counts a CPU
    run can give."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4500000043", "--seconds", "6",
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
