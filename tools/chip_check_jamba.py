"""A chip-side check of Jamba's stack outside the benchmark's cell (run it
through ``chiprun --chips 1 --timeout 3000 -- python3
tools/chip_check_jamba.py``; on the CPU add ``--rehearse`` for tiny widths,
where the controls are NOT all caught: tiny widths are a null model). After
``tools/chip_check_granite_h.py``, whose walk this is.

The configuration is the cell's (``benchmark/configs/jamba2-3b-l28-serve.
json``: AI21-Jamba2-3B WHOLE, 28 layers at the published widths) and so is
the engine block, so the programs are the timed path's own 64-row ones.
First every slot of the state pools is DIRTIED: 64 throwaway sequences are
prefilled and flushed. Then two JUDGED sequences — a LONG prompt of
``--prompt`` tokens (5,200: forty whole chunks and one of 80, the selective
scan's chunk form from a state CARRIED through every one of 41 launches, the
MQA history kernel over up to 41 pages) and a ONE-CHUNK one of 16 (every
judged position within reach of what its slot held before), each followed by
``--steps`` greedy tokens — run beside 40 background sequences that decode a
random token a step: the long prompt's first chunk rides the fresh program,
its later chunks GROUPED split steps (one row in the chunk form, the others
stepping the recurrence by slot in one pass over each layer's pool), its
decode steps the 64-row decode program. Every position's LOGITS from the
prompt's last on are held against the plain float32 reference's FULL FORWARD
of the same tokens (``benchmark/reference/jamba_decoder.py``: the per-token
recurrence), in plain logit units (the tied head spreads them by about 1.0).

The bf16 phase holds the serve runner's limits on the argmax over every
judged position of both rows, and ``LOGIT_DIFF_LIMIT`` on the MEDIAN of each
row's largest logit difference; its control — every weight matrix rounded to
float8, the nearest precision below the one the configuration states — must
not pass. The float32 phase (the published widths, layers mamba / attention
/ mamba, ``highest`` matmul precision, the XLA forms) holds the LARGEST
difference, ``F32_LOGIT_DIFF_LIMIT``, and then the same tokens teacher-forced
through programs that are WRONG in one way each, which must not pass: the
step's inner norm dropped, ``B``'s, the skip ``D·u``, a row at position 0
left with what its slot held, the state rounded to bfloat16 on its way to
the pool. ``--kernel`` first times the ``selective_scan`` Pallas kernel
against the XLA loop over the positions on random rows at the published
widths, inside a jit that makes Δ and gates y as the step program does, and
holds the two to each other (``--phases none``: that alone). One JSON object
a line; the last says ``ok``."""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "jamba2-3b-l28-serve"
#: the most the MEDIAN over a judged row's positions of the largest logit
#: difference may be in the bf16 phase. Between two sets of readings on the
#: v5e (PERF.md §6, PR 49): the sound bf16 program's 0.138 / 0.142 (long /
#: short row; 0.163 / 0.167 at their 90th percentiles, 0.235 at most; its
#: worst token 0.081 under the reference's argmax, 91% exactly it) and 4.79 /
#: 4.92 with every weight matrix in float8 (0.8% exactly the argmax)
LOGIT_DIFF_LIMIT = 0.5
#: the float32 phase holds the LARGEST difference over its positions: the
#: sound float32 program reads 1.6e-5 on the chip (5e-6 at the median); the
#: state rounded to bf16 on its way to the pool 0.184, the step's norm
#: dropped 0.97, a stale slot 1.30 (0.0067 on the long row, whose judged
#: positions lie 5,200 tokens past the slot's old state), ``B``'s norm
#: dropped 1.66, ``D·u`` dropped 7.41
F32_LOGIT_DIFF_LIMIT = 0.002
BACKGROUND = 40


def kernel_check(args, hf):
    """The Pallas kernel beside the XLA loop at the published widths, in the
    context the step program gives them — inside ONE jit that makes Δ from a
    bf16 pre-activation, hands the scan a float32 u and gates y into bf16:
    16 rows of a 128-token chunk, float32 state. Three mixes of live tokens
    (9 rows live to the end + 5 of one token + 2 empty; ONE live token a
    row: what is left is traffic that does not depend on the live tokens;
    all live) → a line with the times and the two forms' largest
    difference."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import ssm
    d = int(hf["mamba_expand"]) * int(hf["hidden_size"])
    n, m, c = int(hf["mamba_d_state"]), 16, 128
    ks = jax.random.split(jax.random.PRNGKey(args.seed & 0x7FFFFFFF), 8)
    bf = jnp.bfloat16
    p = {"A_log": jnp.broadcast_to(jnp.log(jnp.arange(
             1, n + 1, dtype=jnp.float32))[:, None], (n, d)),
         "D": jax.random.normal(ks[0], (d,), jnp.float32),
         "dt_bias": jax.random.normal(ks[6], (d,), jnp.float32) - 4.0}
    u, dt, z = (jax.random.normal(k, (m, c, d), bf)
                for k in (ks[1], ks[2], ks[7]))
    b, cc = (jax.random.normal(k, (m, c, n), jnp.float32) for k in ks[3:5])
    state = jax.random.normal(ks[5], (m, n, d), jnp.float32)
    mixes = {"live": [c] * 9 + [1] * 5 + [0] * 2, "one_a_row": [1] * m,
             "all": [c] * m}

    def around(kernel):
        def fn(u, dt, z, b, cc, state, counts):
            delta = ssm.step_sizes(p, dt, counts)
            y, s = ssm.selective_chunk(None, p, u.astype(jnp.float32),
                                       (delta, b, cc), state, counts,
                                       kernel=kernel)
            return ssm.selective_gate(y, z, bf), s
        return jax.jit(fn)

    line = {"phase": "kernel", "rows": m, "chunk": c, "channels": d,
            "states": n, "live_tokens": {k: sum(v) for k, v in mixes.items()}}
    out = {}
    on_chip = not args.rehearse and jax.default_backend() == "tpu"
    for name, kernel in (("xla_loop", False), ("selective_scan", True)):
        if kernel and not on_chip:
            continue
        fn = around(kernel)
        for mix, counts in mixes.items():
            if not kernel and mix != "live":
                continue
            counts = jnp.asarray(counts, jnp.int32)
            got = jax.block_until_ready(fn(u, dt, z, b, cc, state, counts))
            t0 = time.perf_counter()
            for _ in range(20 if on_chip else 1):
                got = fn(u, dt, z, b, cc, state, counts)
            jax.block_until_ready(got)
            line[f"{name}_{mix}_ms"] = round(
                (time.perf_counter() - t0) / (20 if on_chip else 1) * 1e3, 3)
            if mix == "live":
                out[name] = got
    ok = True
    if len(out) == 2:
        live = (jnp.arange(c)[None] < jnp.asarray(mixes["live"])[:, None])
        y0, s0 = out["xla_loop"]
        y1, s1 = out["selective_scan"]
        line["y_max_diff"] = float(jnp.abs(jnp.where(
            live[..., None], y0.astype(jnp.float32) - y1.astype(jnp.float32),
            0.0)).max())
        line["state_max_diff"] = float(jnp.abs(s0 - s1).max())
        line["y_scale"] = float(jnp.abs(y0.astype(jnp.float32)).max())
        # (y is compared after its rounding to bf16: one unit in its last
        # place, 2^-8 of its scale, is the most the two may differ by)
        ok = line["y_max_diff"] <= 2 ** -7 * line["y_scale"] and \
            line["state_max_diff"] <= 1e-4 * float(jnp.abs(s0).max())
    line["passes"] = bool(ok)
    print(json.dumps(line), flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=4900000049)
    ap.add_argument("--prompt", type=int, default=5200)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--only", default=None,
                    help="comma-separated controls to run (default: all)")
    ap.add_argument("--phases", default="bf16,float32")
    ap.add_argument("--kernel", action="store_true",
                    help="first: the Pallas kernel beside the XLA loop")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmark.lib import model as model_lib
    from benchmark.runners.serve import MIN_EXACT_ARGMAX, NEAR_TIE_LOGITS
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.hf_loader import config_from_hf
    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.telemetry.registry import registry
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    conf = model_lib.load_config(CONFIG)
    ref = model_lib.load_reference(conf)
    dev = jax.devices()[0]
    hf = model_lib.published_keys(conf, args.rehearse)
    engine_conf = dict(conf["engine"])
    if args.rehearse:
        args.prompt, args.steps = 300, 12
        engine_conf.update(num_blocks=128, max_seq_len=1024)
    kernel_ok = kernel_check(args, hf) if args.kernel else True
    rng = np.random.default_rng(args.seed)
    rows, steps = engine_conf["max_sequences"], args.steps
    counted = {name: registry.counter("dispatch/" + name) for name in (
        "steps.fresh", "steps.split", "steps.decode", "split_grouped_steps",
        "state_rows", "state_resets", "ssm_chunk_tokens")}

    def phase(tag, hf, engine_conf, limit, which, controls):
        """One configuration: the sound program's greedy walk against the
        reference, then each control's teacher-forced walk → (sound
        passes, {control: caught}). ``limit`` holds each row's largest
        logit differences at their median (``which`` 0) or their largest
        (2)."""
        sound_cfg = config_from_hf(hf)
        w = ref.Widths.from_hf(hf)
        vocab = sound_cfg.vocab_size
        prompts = {0: rng.integers(0, vocab, args.prompt).tolist(),
                   1: rng.integers(0, vocab, 16).tolist()}
        junk = [rng.integers(0, vocab, 24).tolist() for _ in range(rows)]
        others = [rng.integers(0, vocab, int(n)).tolist()
                  for n in rng.integers(8, 33, BACKGROUND)]
        fed_others = rng.integers(
            0, vocab, (args.prompt // 100 + steps + 8, BACKGROUND))

        def walk(cfg, params, tokens):
            """Teacher-forced (``tokens[uid]`` longer than the prompt) or
            greedy: each judged row's logits at its last prompt position
            and at its decode positions, and the tokens it was fed."""
            eng = RaggedInferenceEngineTPU(cfg, engine_conf, params=params,
                                           rng=model_lib.prng_key(args.seed))
            junk_ids = list(range(1000, 1000 + rows))
            eng.put(junk_ids, junk)     # dirty every slot, hand them back
            for uid in junk_ids:
                eng.flush(uid)
            ids = list(range(2, BACKGROUND + 2))
            seqs = {u: list(tokens[u][:len(prompts[u])]) for u in prompts}
            got = {u: [] for u in prompts}
            eng._put_validated(ids + list(seqs), others + list(seqs.values()))
            turn = 0
            while any(len(g) < steps for g in got.values()):
                out = eng.step_with_budget(mode=None)
                eng._put_validated(ids, [[int(t)] for t in fed_others[turn]])
                turn += 1
                for u in prompts:
                    if u not in out or len(got[u]) == steps:
                        continue    # its chunks are still going in, or done
                    got[u].append(np.asarray(out[u], np.float32))
                    nxt = int(tokens[u][len(seqs[u])]) \
                        if len(seqs[u]) < len(tokens[u]) \
                        else int(np.argmax(got[u][-1]))
                    seqs[u].append(nxt)
                    if len(got[u]) < steps:
                        eng._put_validated([u], [[nxt]])
            programs = sorted(fn.__name__ for fn in eng._step_fns.values())
            return eng, {u: np.stack(g) for u, g in got.items()}, seqs, \
                programs

        before = {name: c.value for name, c in counted.items()}
        t0 = time.perf_counter()
        eng, logits, seqs, programs = walk(sound_cfg, None, prompts)
        walked = time.perf_counter() - t0
        work = {name: int(c.value - before[name])
                for name, c in counted.items()}
        params = eng.params
        del eng
        gc.collect()
        want = {}
        t0 = time.perf_counter()
        for u, seq in seqs.items():
            at = slice(len(prompts[u]) - 1, len(seq) - 1)
            want[u] = ref.logits_of(w, params, seq[:-1], dev)[at]
        referred = time.perf_counter() - t0

        def judge(name, got):
            """A line a judged row, then one over both rows' positions
            together."""
            gaps, exact, medians, largest = [], [], [], []
            finite = True
            for u in prompts:
                fed = np.asarray(seqs[u][len(prompts[u]):])
                gap = want[u].max(-1) - want[u][np.arange(steps),
                                                got[u].argmax(-1)]
                diff = np.abs(got[u] - want[u]).max(-1)
                gaps.append(gap)
                exact.append(got[u].argmax(-1) == want[u].argmax(-1))
                quartiles = [round(float(q), 6) for q in
                             np.percentile(diff, (50, 90, 100))]
                medians.append(quartiles[0])
                largest.append(quartiles[2])
                finite = finite and bool(np.isfinite(got[u]).all())
                print(json.dumps({
                    "phase": f"{tag}:{name}", "prompt": len(prompts[u]),
                    "positions": steps,
                    "logit_diff_p50_p90_max": quartiles,
                    "logit_spread": round(float(want[u].std(-1).mean()), 4),
                    "gap_p99_max": [round(float(q), 5) for q in
                                    np.percentile(gap, (99, 100))],
                    "exact_argmax": float(exact[-1].mean()),
                    "fed_is_its_argmax":
                        float((got[u].argmax(-1) == fed).mean()),
                    "finite": finite}), flush=True)
            gaps, exact = np.concatenate(gaps), np.concatenate(exact)
            held = max(medians) if which == 0 else max(largest)
            line = {"phase": f"{tag}:{name}", "prompt": "both",
                    "logit_diff_limit": limit,
                    "held": "each row's median" if which == 0
                    else "the largest", "reads": held,
                    "worst_gap_of_its_argmax": float(gaps.max()),
                    "exact_argmax_share": float(exact.mean())}
            line["passes"] = bool(
                finite and line["worst_gap_of_its_argmax"] <= NEAR_TIE_LOGITS
                and line["exact_argmax_share"] >= MIN_EXACT_ARGMAX
                and held <= limit)
            print(json.dumps(line), flush=True)
            return line["passes"]

        sound = judge("sound", logits)
        print(json.dumps({"phase": f"{tag}:programs", "names": programs,
                          "contexts": [len(s) for s in seqs.values()],
                          "launches": work, "walk_s": round(walked, 1),
                          "reference_s": round(referred, 1),
                          "memory_peak_bytes": int((dev.memory_stats() or {})
                                                   .get("peak_bytes_in_use",
                                                        0))}), flush=True)
        caught = {}
        for name, (change, patches) in controls.items():
            if args.only and name not in args.only.split(","):
                continue
            kept = {attr: getattr(ssm, attr) for attr in patches}
            for attr, fn in patches.items():
                setattr(ssm, attr, fn)
            try:
                _eng, got, _seqs, _ = walk(sound_cfg, change(params), seqs)
            finally:
                for attr, fn in kept.items():
                    setattr(ssm, attr, fn)
            del _eng
            gc.collect()    # an engine and its step programs are a cycle
            caught[name] = not judge(name, got)
        return sound, caught

    def weights_in_float8(params):
        """Every weight matrix rounded to float8, IN PLACE (no room for a
        second copy beside the engine): the last control of its phase."""
        groups = [params["embed"], params] + [
            g for lp in params["layers"] for g in lp.values()]
        for group in groups:
            for key in list(group):
                if hasattr(group[key], "ndim") and group[key].ndim >= 2 \
                        and key != "A_log":
                    group[key] = group[key].astype(
                        jnp.float8_e4m3fn).astype(group[key].dtype)
        return params

    def rounded(scan):
        def wrapped(*scan_args, **scan_kwargs):
            y, s = scan(*scan_args, **scan_kwargs)
            # (``reduce_precision`` to bf16's 8 + 7 bits: the compiler
            # folds a pair of converts away, and the control with it)
            return y, jax.lax.reduce_precision(s, 8, 7)
        return wrapped

    def in_mixers(**leaves):
        def change(params):
            return dict(params, layers=[
                dict(lp, ssm=dict(lp["ssm"], **{
                    k: f(lp["ssm"][k]) for k, f in leaves.items()}))
                if "ssm" in lp else lp for lp in params["layers"]])
        return change

    def norms_without(name):
        norms = ssm.select_norms

        def wrong(cfg, p, dbc, dtype):
            delta, b, c = norms(cfg, p, dbc, dtype)
            r, n = cfg.ssm_dt_rank, cfg.ssm_state_size
            if name == "dt":
                delta = dbc[..., :r].astype(dtype)
            else:
                b = dbc[..., r:r + n].astype(jnp.float32)
            return delta, b, c
        return wrong

    same = lambda params: params
    #: name -> (the tree's change, ops/ssm.py functions replaced)
    wrong = {
        "step_norm_dropped": (same, {"select_norms": norms_without("dt")}),
        "b_norm_dropped": (same, {"select_norms": norms_without("b")}),
        "skip_term_dropped": (in_mixers(D=jnp.zeros_like), {}),
        "stale_state_in_a_reused_slot": (same, {
            "fresh_rows": lambda starts: jnp.zeros(starts.shape, bool)}),
        "state_pool_in_bf16": (same, {
            "selective_step": rounded(ssm.selective_step),
            "selective_chunk": rounded(ssm.selective_chunk)}),
    }
    served = exact = True
    caught = {}
    if "bf16" in args.phases:
        served, reported = phase(
            "bf16", hf, engine_conf, LOGIT_DIFF_LIMIT, 0,
            # the nearest precision below the one the configuration
            # states: what the runner's limits have to catch
            {"weights_in_float8": (weights_in_float8, {})})
        for name, was in reported.items():
            caught["bf16:" + name] = was
    if "float32" in args.phases:
        # the controls that change the PROGRAM are held in float32 at three
        # layers (a full-depth program is minutes to compile, and a bf16
        # program's own rounding is of the size of what some of them move):
        # the same widths, a mixer of each kind and a second selective one
        with jax.default_matmul_precision("highest"):
            exact, caught32 = phase(
                "float32", dict(hf, num_hidden_layers=3, attn_layer_period=2,
                                attn_layer_offset=1),
                # (the history kernel's float32 block does not fit VMEM:
                # this phase holds the pools and the equations, not it)
                dict(engine_conf, dtype="float32", use_pallas=False),
                F32_LOGIT_DIFF_LIMIT, 2, wrong)
        for name, was in caught32.items():
            caught["float32:" + name] = was
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    ok = served and exact and kernel_ok and all(caught.values())
    print(json.dumps({"ok": bool(ok), "sound_passes": served,
                      "sound_float32_passes": exact,
                      "kernel_is_the_loop": kernel_ok,
                      "controls_caught": caught,
                      "memory_peak_bytes": int(peak),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
