"""The Cohere2-MoE configuration's own pieces of the yardstick: the
reference's side of the contract and its equations by hand, the counts of
``lib/paged_pairs_work``, the three new readers on a run that has nothing
for them, and ``--rehearse`` of the cell on the CPU."""

import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import model, paged_hist_work, paged_pairs_work

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "command-a-plus-l4-e16-serve"
CELL = "command-a-plus-l4-e16-serve-rag-closed16"


def _ref_and_widths():
    conf = model.load_config(CONFIG)
    ref = model.load_reference(conf)
    return ref, ref.Widths.from_hf(model.published_keys(conf))


def test_the_cohere2_reference_keeps_the_contract():
    ref, w = _ref_and_widths()
    assert ref.__name__.endswith("cohere2_moe_decoder")
    assert hash(w) == hash(_ref_and_widths()[1])
    assert (w.hidden, w.heads, w.kv_heads, w.head_dim, w.rope_dim,
            w.theta, w.window, w.eps) == (4096, 128, 8, 128, 128, 50000.0,
                                          4096, 1e-5)
    assert w.kinds == (1, 1, 1, 0) and w.shared_experts == 4
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.expert_ffn, w.vocab) == (128, 0, 16, 8, 4096, 32768)
    # a token multiplies: q and o at 16,384, k and v at 1,024, the router,
    # four shared experts, and ONE of its eight experts on this chip
    layer = 2 * 4096 * 16384 + 2 * 4096 * 1024 + 4096 * 128 + \
        4 * 3 * 4096 * 4096 + 3 * 4096 * 4096
    assert ref.matmul_params_per_token(w) == 4 * layer + 4096 * 32768
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)


def test_cohere2_layer_by_hand():
    """One window layer at a tiny size against the equations written out
    with numpy: LayerNorm, interleaved rotary, the window mask, sigmoid
    top-k renormalised over the held experts, the shared experts' mean,
    the parallel residual."""
    ref, _ = _ref_and_widths()
    d, H, KV, dh, f, E, k, n, T, W = 16, 4, 1, 8, 8, 6, 2, 2, 256, 5
    w = ref.Widths(hidden=d, heads=H, kv_heads=KV, head_dim=dh, rope_dim=dh,
                   theta=50000.0, window=W, eps=1e-5, layers=1, kinds=(1,),
                   expert_ffn=f, shared_experts=n, router_experts=E,
                   first_expert=1, held_experts=3, per_token=k,
                   norm_topk=True, vocab=32)
    rng = np.random.default_rng(0)
    g = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.3
    lp = {"ln1": {"scale": 1 + g(d)},
          "attn": {"wq": g(d, H * dh), "wk": g(d, KV * dh),
                   "wv": g(d, KV * dh), "wo": g(H * dh, d)},
          "moe": {"router": g(d, E), "wg": g(3, d, f), "wi": g(3, d, f),
                  "wo": g(3, f, d)},
          "shared": {"wg": g(d, n * f), "wi": g(d, n * f),
                     "wo": g(n * f, d)}}
    x = g(T, d) + 0.5
    with jax.default_matmul_precision("highest"):
        got, _ = ref._layer(jnp.asarray(x), jax.tree.map(jnp.asarray, lp),
                            w, 1)
    x64 = x.astype(np.float64)
    h = (x64 - x64.mean(-1, keepdims=True)) / np.sqrt(
        x64.var(-1, keepdims=True) + 1e-5) * lp["ln1"]["scale"]
    silu = lambda a: a / (1 + np.exp(-a))

    def rope(t):                       # [T, heads, dh], pairs (2i, 2i+1)
        ang = np.arange(T)[:, None] * 50000.0 ** (-np.arange(0, dh, 2) / dh)
        out = t.copy()
        out[..., 0::2] = t[..., 0::2] * np.cos(ang)[:, None] - \
            t[..., 1::2] * np.sin(ang)[:, None]
        out[..., 1::2] = t[..., 1::2] * np.cos(ang)[:, None] + \
            t[..., 0::2] * np.sin(ang)[:, None]
        return out

    q = rope((h @ lp["attn"]["wq"]).reshape(T, H, dh))
    kk = rope((h @ lp["attn"]["wk"]).reshape(T, KV, dh))
    v = (h @ lp["attn"]["wv"]).reshape(T, KV, dh)
    att = np.zeros((T, H, dh))
    for i in range(T):
        lo = max(0, i - W + 1)
        for hd in range(H):
            s = kk[lo:i + 1, 0] @ q[i, hd] / np.sqrt(dh)
            p = np.exp(s - s.max())
            att[i, hd] = (p / p.sum()) @ v[lo:i + 1, 0]
    a = att.reshape(T, H * dh) @ lp["attn"]["wo"]
    z = 1 / (1 + np.exp(-(h @ lp["moe"]["router"])))
    r = np.zeros((T, d))
    for i in range(T):
        sel = np.argsort(-z[i])[:k]
        for e in sel:
            if 1 <= e < 4:
                m = lp["moe"]
                r[i] += z[i, e] / z[i, sel].sum() * (
                    (silu(h[i] @ m["wg"][e - 1]) * (h[i] @ m["wi"][e - 1]))
                    @ m["wo"][e - 1])
    sh = lp["shared"]
    s = sum((silu(h @ sh["wg"][:, j * f:(j + 1) * f]) *
             (h @ sh["wi"][:, j * f:(j + 1) * f])) @
            sh["wo"][j * f:(j + 1) * f] for j in range(n)) / n
    assert np.abs(np.asarray(got) - (x64 + a + r + s)).max() < 2e-5


def test_history_pairs_and_flops_by_hand():
    cfg = SimpleNamespace(layer_kinds=(1, 1, 1, 0), num_heads=128,
                          head_dim=128, v_dim=128, kv_heads=8,
                          window_kv_heads=None)
    args = {"attn_pairs_full": 1000, "attn_pairs_own_full": 100,
            "attn_pairs_window": 700, "attn_pairs_own_window": 100}
    assert paged_pairs_work.history_pairs(args) == (900, 600)
    assert paged_pairs_work.history_pairs({"tokens": 3}) is None
    # a pair costs a head 2 x 128 for its score and 2 x 128 for its sum
    assert paged_pairs_work.history_flops(cfg, 900, 600) == \
        4 * 128 * 128 * (900 + 3 * 600)
    # a chunk of 128 live queries over 5,000 history tokens of one row:
    # 640,000 pairs a layer against 5,000 tokens of K and V: 2,048 FLOPs a
    # byte, far over the chip's ridge of 240
    flops = paged_pairs_work.history_flops(cfg, 128 * 5000, 0)
    nbytes = paged_hist_work.history_bytes(cfg, 5000, 0)
    assert flops / nbytes == 2048.0 > 197e12 / 819e9


def test_new_readers_read_nothing_from_an_empty_run():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          program_spans=lambda name: [])
    for name in ("attn_chunk_ms_per_step", "paged_attn_lse_pairs_roofline",
                 "prefill_tokens_per_step"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
        spec = __import__("importlib.util").util.spec_from_file_location(
            "reader_" + name, path)
        mod = __import__("importlib.util").util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if name != "attn_chunk_ms_per_step":   # (its helper wants a trace)
            assert mod.read(run) is None


def test_the_mix_is_the_issues_to_the_letter():
    from benchmark.lib import traffic
    mix = traffic.load_mix("rag-closed16")
    assert mix["arrival"] == {"process": "closed", "clients": 16}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 5120,
                                    "sigma": 0.5, "min": 1024, "max": 10240}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.5, "min": 64, "max": 768}
    assert (mix["max_total_tokens"], mix["cycle_seed"], mix["ramp_seconds"],
            mix["trace_seconds"]) == (11008, 37, 60, 3)
    sizes = traffic.size_cycle(mix)
    conf = model.load_config(CONFIG)["engine"]
    assert sizes.sum(1).max() <= mix["max_total_tokens"] == \
        conf["max_seq_len"]
    # the arena holds every row at the cap: no request can end kv_exhausted
    assert conf["max_sequences"] * conf["max_seq_len"] == \
        conf["num_blocks"] * conf["block_size"] == 176128
    past = (sizes[:, 0] > 4096).mean()
    assert 0.6 < past < 0.75                   # two thirds pass the window


def test_rehearsal_of_the_cell():
    """Tiny widths, the mix as it is (prompts of 1,024-10,240 tokens
    through a window of 256): every check but the tails' samples, which a
    CPU window of seconds cannot give (a first token is minutes away)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "6",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    checks = next(l for l in lines if l.get("phase") == "checks")
    failed = [k for k, v in checks.items() if v is False]
    assert failed in ([], ["tails_have_samples"]), failed
    last = lines[-1]
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0
    assert {"prefill_tokens_per_step", "kv_window_dead_share",
            "token_slot_utilization"} <= set(last["metrics"])
