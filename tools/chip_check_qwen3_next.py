"""A chip-side check of Qwen3-Next's stack outside the benchmark's cell (run it
through ``chiprun --chips 1 --timeout 3000 -- python3
tools/chip_check_qwen3_next.py``; on the CPU add ``--rehearse`` for tiny
widths, where the controls are NOT all caught: tiny widths are a null model).
After ``tools/chip_check_jamba.py``, whose walk this is.

The configuration is the cell's (``benchmark/configs/qwen3-next-80b-a3b-l12-
e64-serve.json``: three periods ``delta delta delta full`` at the published
widths, experts 0-63 of 512 held) and so is the engine block, so the
programs are the timed path's own 64-row ones. First every slot of the state
pools is DIRTIED: 64 throwaway sequences are prefilled and flushed. Then two
JUDGED sequences — a LONG prompt of ``--prompt`` tokens (several chunks: the
delta rule's WY form from a state CARRIED through every launch, the history
kernel over 256-wide pages), fresh in its slot's first use after the
dirtying, and a ONE-CHUNK one of 16 RESUMED in a reused slot, each followed
by ``--steps`` greedy tokens — run beside 40 background sequences that decode
a random token a step: the long prompt's first chunk rides the fresh
program, its later chunks GROUPED split steps (its row in the chunk form, the
others stepping the recurrence by slot in one pass over each layer's pool),
its decode steps the 64-row decode program. Every position's LOGITS from the
prompt's last on are held against the plain float32 reference's FULL FORWARD
of the same tokens (``benchmark/reference/qwen3_next_decoder.py``: the
per-token recurrence), in plain logit units (the head spreads them by 0.9).

The bf16 phase holds the serve runner's limits on the argmax over the judged
positions whose routing the reference's margins decide
(``qwen3_next_decoder.decided``: ``--margins`` prints the count and the worst
gap at 0, 1/2, 1 and 2 times the file's constants), and
``LOGIT_DIFF_LIMIT`` on the MEDIAN of each row's largest logit difference;
its control — every weight matrix rounded to float8, the nearest precision
below the one the configuration states — must not pass. The float32 phase
(the published widths, ONE period, ``highest`` matmul precision, the XLA
forms) holds the LARGEST difference, ``F32_LOGIT_DIFF_LIMIT``, and then the
same tokens teacher-forced through programs that are WRONG in one way each,
which must not pass: the state rounded to bfloat16 on its way to the pool,
the decay ``e^g`` dropped, the gate on the wrong side of the mixer's norm,
rotary over the whole head, a row at position 0 left with what its slot
held. ``--forms`` first times the delta rule's two forms on random rows at
the published widths — the chunk form at 8 and 64 rows of 128 positions (and
at 8 rows filled as cell 14's launch fills them: six whole, one of 23, one
empty), in XLA at sub-blocks of 16 and 32 with the chunk's own products at
full float32 and in bf16 passes, and as the Pallas kernel at turns of 32, 64
and 128 positions (``the_programs``: the width ``ssm.DELTA_KERNEL_SUB``
states), every float32 form held to the token-by-token recurrence; the
one-token pass over a pool of 65 slots — (``--phases none``: that alone).
One JSON object a line; the last says ``ok``."""

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "qwen3-next-80b-a3b-l12-e64-serve"
#: the most the MEDIAN over a judged row's positions of the largest logit
#: difference may be in the bf16 phase. Between two sets of readings on the
#: v5e (PERF.md §6, PR 62): the sound bf16 program's 0.067 / 0.075 (long /
#: short row; 0.147 / 0.148 at their 90th percentiles, 0.261 at most; 94.6%
#: of 10,560 background positions the reference's argmax, none over the
#: runner's near-tie limit) and 4.07 / 4.11 with every weight matrix in
#: float8 (1% exactly the argmax). (Before the mixer's projections took
#: their inputs unrounded, ``typed_layers._linear_wide``: 0.267 / 0.271.)
LOGIT_DIFF_LIMIT = 0.5
#: the float32 phase holds the LARGEST difference over its positions: the
#: sound float32 program reads 1.7e-5 on the chip; the state rounded to bf16
#: on its way to the pool 0.143, rotary over the whole head 0.64, a stale
#: slot 0.88, the gate before the mixer's norm 3.27, ``e^g`` dropped 7.18
F32_LOGIT_DIFF_LIMIT = 0.002
BACKGROUND = 40


def forms_check(args, hf):
    """The delta rule's two forms at the published widths, each jitted
    alone on random rows (unit q and k, β in (0, 1), g from the
    initialiser's ``A`` and a unit-variance ``a``): times, and the chunk
    form's largest difference from the recurrence stepped token by token
    (``ssm.delta_step``) over the same rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.models.hf_loader import config_from_hf
    from deepspeed_tpu.ops import ssm
    cfg = config_from_hf(hf)
    hv, cd, c = cfg.ssm_heads, cfg.ssm_conv_dim, 128
    ks = jax.random.split(jax.random.PRNGKey(args.seed & 0x7FFFFFFF), 6)
    p = {"A_log": jnp.log(jax.random.uniform(ks[0], (hv,), jnp.float32,
                                             1e-3, 16.0)),
         "dt_bias": jnp.ones((hv,), jnp.float32)}
    on_chip = not args.rehearse and jax.default_backend() == "tpu"
    reps = 10 if on_chip else 1
    ok = True

    def rows(m, counts=None):
        u = jax.random.normal(ks[1], (m, c, cd), jnp.float32)
        ba = tuple(jax.random.normal(k, (m, c, hv), jnp.float32)
                   for k in ks[2:4])
        state = jax.random.normal(ks[4], (m,) + ssm.state_shape(cfg),
                                  jnp.float32)
        counts = jnp.asarray(
            counts or ([c] * (m - 2) + [c // 2, 1])[:m], jnp.int32)
        return u, ba, state, counts

    def timed(fn, *fn_args):
        out = jax.block_until_ready(fn(*fn_args))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*fn_args)
        jax.block_until_ready(out)
        return out, round((time.perf_counter() - t0) / reps * 1e3, 3)

    def chunk_form(kernel=False):
        # (a function a variant: jax keeps a trace by the function it traced)
        def chunk(u, ba, state, counts):
            return ssm.delta_chunk(cfg, p, u, ssm.delta_inputs(
                cfg, p, u, ba, counts), state, counts, kernel=kernel)
        return jax.jit(chunk)

    @jax.jit
    def stepped(u, ba, state, counts):
        def one(s, inp):
            u_t, b_t, a_t, t = inp
            live = (t < counts).astype(jnp.int32)
            sel = ssm.delta_inputs(cfg, p, u_t[:, None],
                                   (b_t[:, None], a_t[:, None]), live)
            o, s = ssm.delta_step(cfg, p, None, sel, s, live)
            return s, o[:, 0]
        s, o = lax.scan(one, state, (
            u.swapaxes(0, 1), ba[0].swapaxes(0, 1), ba[1].swapaxes(0, 1),
            jnp.arange(c)))
        return o.swapaxes(0, 1), s

    def held(line, o, s, want_o, want_s, live, judged=True):
        """A form's largest differences from the recurrence; ``judged`` (a
        float32 form: float32 sums in another order): ``passes`` within 1e-4
        of the scale."""
        line.update(
            o_max_diff=float(jnp.abs(jnp.where(live, o - want_o, 0.0)).max()),
            state_max_diff=float(jnp.abs(s - want_s).max()),
            o_scale=float(jnp.abs(want_o).max()),
            state_scale=float(jnp.abs(want_s).max()))
        if judged:
            line["passes"] = bool(
                line["o_max_diff"] <= 1e-4 * line["o_scale"] and
                line["state_max_diff"] <= 1e-4 * line["state_scale"])
        return line.get("passes", True)

    #: 8 rows as cell 14's launch fills them: six whole chunks, a piece of
    #: 23, an empty row (PERF.md section 6, PR 62: 6.2 whole-chunk equivalents)
    cell_rows = [c] * 6 + [23, 0]
    for m, counts, mix in ((8, None, "full"), (8, cell_rows, "cell14"),
                           (64, None, "full")) if on_chip else \
            ((2, None, "full"), (2, [23, 0], "cell14")):
        u, ba, state, counts = rows(m, counts)
        want_o, want_s = jax.block_until_ready(stepped(u, ba, state, counts))
        live = (jnp.arange(c)[None] < counts[:, None])[..., None]
        for sub in (16, 32) if mix == "full" else (32,):
            for name, prec in (("float32", lax.Precision.HIGHEST),
                               ("bf16_3_passes", lax.Precision.HIGH),
                               ("bf16_1_pass", lax.Precision.DEFAULT)):
                if mix != "full" and name != "float32":
                    continue
                kept = ssm.DELTA_SUB_BLOCK, ssm.DELTA_CHUNK_PRECISION
                ssm.DELTA_SUB_BLOCK, ssm.DELTA_CHUNK_PRECISION = sub, prec
                try:
                    (o, s), ms = timed(chunk_form(), u, ba, state, counts)
                finally:
                    ssm.DELTA_SUB_BLOCK, ssm.DELTA_CHUNK_PRECISION = kept
                line = {"phase": "forms", "form": "chunk", "rows": m,
                        "mix": mix, "sub_block": sub,
                        "inner_products": name, "ms": ms}
                ok = held(line, o, s, want_o, want_s, live,
                          judged=name == "float32") and ok
                print(json.dumps(line), flush=True)
        # the kernel (float32 as the file states it), a turn of its walk at
        # the width the program takes and at the two beside it
        for turn in (32, 64, 128) if on_chip else ():
            kept = ssm.DELTA_KERNEL_SUB
            ssm.DELTA_KERNEL_SUB = turn
            try:
                (o, s), ms = timed(chunk_form(kernel=True), u, ba, state,
                                   counts)
            finally:
                ssm.DELTA_KERNEL_SUB = kept
            line = {"phase": "forms", "form": "chunk_kernel", "rows": m,
                    "mix": mix, "turn": turn, "the_programs": turn == kept,
                    "ms": ms}
            ok = held(line, o, s, want_o, want_s, live) and ok
            print(json.dumps(line), flush=True)
    m = 65
    u, ba, state, _ = rows(m)
    live = jnp.ones((m,), jnp.int32).at[-1].set(0)

    @jax.jit
    def step(u, ba, state):
        sel = ssm.delta_inputs(cfg, p, u[:, :1],
                               (ba[0][:, :1], ba[1][:, :1]), live)
        return ssm.delta_step(cfg, p, None, sel, state, live,
                              jnp.zeros((m,), bool).at[3].set(True))
    _, ms = timed(step, u, ba, state)
    pool_bytes = 2 * state.size * 4
    print(json.dumps({"phase": "forms", "form": "step", "slots": m, "ms": ms,
                      "pool_read_and_write_GB": round(pool_bytes / 1e9, 3),
                      "GB_per_s": round(pool_bytes / 1e9 / (ms / 1e3), 1)}),
          flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=6200000062)
    ap.add_argument("--prompt", type=int, default=1100)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--only", default=None,
                    help="comma-separated controls to run (default: all)")
    ap.add_argument("--phases", default="bf16,float32")
    ap.add_argument("--forms", action="store_true",
                    help="first: the delta rule's two forms, timed")
    ap.add_argument("--margins", action="store_true",
                    help="bf16 phase: the judged count by margin")
    ap.add_argument("--judge-background", type=int, default=0,
                    help="bf16 phase: this many of the background rows' "
                    "positions too (their argmax under the reference's, "
                    "with each position's margin: chiprun_out/pr62/"
                    "margins.npz)")
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
    forms_ok = forms_check(args, hf) if args.forms else True
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
            picked = {u: [] for u in ids[:args.judge_background]}
            eng._put_validated(ids + list(seqs), others + list(seqs.values()))
            turn = 0
            while any(len(g) < steps for g in got.values()):
                out = eng.step_with_budget(mode=None)
                eng._put_validated(ids, [[int(t)] for t in fed_others[turn]])
                turn += 1
                for u in picked:        # (every launch holds every such row)
                    picked[u].append(int(np.argmax(out[u])))
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
                programs, picked

        before = {name: c.value for name, c in counted.items()}
        t0 = time.perf_counter()
        eng, logits, seqs, programs, picked = walk(sound_cfg, None, prompts)
        walked = time.perf_counter() - t0
        work = {name: int(c.value - before[name])
                for name, c in counted.items()}
        params = eng.params
        del eng
        gc.collect()
        want, judged = {}, {}
        t0 = time.perf_counter()
        scale, head = ref._head_of(params, dev)
        for u, seq in seqs.items():
            at = slice(len(prompts[u]) - 1, len(seq) - 1)
            (x,), (margin,) = ref.hidden_and_margins(
                w, params, [ref._padded(seq[:-1])], dev)
            with jax.default_matmul_precision("highest"):
                want[u] = np.asarray(ref._head(x[at], scale, head, w.eps))
            margin = np.asarray(margin)[:len(seq) - 1]
            judged[u] = {f: _decided(ref, margin, w, f)[at]
                         for f in (0.0, 0.5, 1.0, 2.0)}
        if picked:
            background_margins(picked, others, fed_others, params, w, scale,
                               head)
        referred = time.perf_counter() - t0

        def judge(name, got):
            """A line a judged row, then one over both rows' positions
            together."""
            gaps, exact, medians, largest, decided = [], [], [], [], []
            finite = True
            for u in prompts:
                fed = np.asarray(seqs[u][len(prompts[u]):])
                gap = want[u].max(-1) - want[u][np.arange(steps),
                                                got[u].argmax(-1)]
                diff = np.abs(got[u] - want[u]).max(-1)
                gaps.append(gap)
                decided.append(judged[u][1.0])
                exact.append(got[u].argmax(-1) == want[u].argmax(-1))
                quartiles = [round(float(q), 6) for q in
                             np.percentile(diff, (50, 90, 100))]
                medians.append(quartiles[0])
                largest.append(quartiles[2])
                finite = finite and bool(np.isfinite(got[u]).all())
                line = {
                    "phase": f"{tag}:{name}", "prompt": len(prompts[u]),
                    "positions": steps,
                    "logit_diff_p50_p90_max": quartiles,
                    "logit_spread": round(float(want[u].std(-1).mean()), 4),
                    "gap_p99_max": [round(float(q), 5) for q in
                                    np.percentile(gap, (99, 100))],
                    "exact_argmax": float(exact[-1].mean()),
                    "fed_is_its_argmax":
                        float((got[u].argmax(-1) == fed).mean()),
                    "finite": finite}
                if args.margins and name == "sound":
                    line["judged_by_margin_factor"] = {
                        str(f): {"judged": int(ok.sum()),
                                 "worst_gap": float(gap[ok].max())
                                 if ok.any() else 0.0,
                                 "exact": float(exact[-1][ok].mean())
                                 if ok.any() else 1.0}
                        for f, ok in judged[u].items()}
                print(json.dumps(line), flush=True)
            gaps, exact, decided = (np.concatenate(t)
                                    for t in (gaps, exact, decided))
            held = max(medians) if which == 0 else max(largest)
            # the bf16 phase judges the argmax where the reference's margins
            # decide the routing, as the cell does; float32 everywhere
            ok = decided if which == 0 else np.ones_like(decided)
            line = {"phase": f"{tag}:{name}", "prompt": "both",
                    "logit_diff_limit": limit,
                    "held": "each row's median" if which == 0
                    else "the largest", "reads": held,
                    "judged_tokens": int(ok.sum()),
                    "worst_gap_of_its_argmax": float(gaps[ok].max())
                    if ok.any() else float("inf"),
                    "exact_argmax_share": float(exact[ok].mean())
                    if ok.any() else 0.0}
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
        for name, (change_cfg, change, patches) in controls.items():
            if args.only and name not in args.only.split(","):
                continue
            kept = {attr: getattr(ssm, attr) for attr in patches}
            for attr, fn in patches.items():
                setattr(ssm, attr, fn)
            try:
                _eng, got, *_ = walk(change_cfg(sound_cfg), change(params),
                                     seqs)
            finally:
                for attr, fn in kept.items():
                    setattr(ssm, attr, fn)
            del _eng
            gc.collect()    # an engine and its step programs are a cycle
            caught[name] = not judge(name, got)
        return sound, caught

    def background_margins(picked, others, fed_others, params, w, scale,
                           head):
        """The background rows' positions, teacher-forced by the random
        tokens they were fed: the program's argmax under the reference's,
        and each position's least margin — a line by margin factor, and the
        arrays for a closer look."""
        gaps, exact, decided, saved = [], [], {}, {}
        for u, mine in picked.items():
            i = u - 2
            seq = list(others[i]) + [int(t) for t in
                                     fed_others[:len(mine) - 1, i]]
            (x,), (margin,) = ref.hidden_and_margins(
                w, params, [ref._padded(seq)], dev)
            at = slice(len(others[i]) - 1, len(seq))
            with jax.default_matmul_precision("highest"):
                logits = np.asarray(ref._head(x[at], scale, head, w.eps))
            margin = np.asarray(margin)[:len(seq)]
            gap = logits.max(-1) - logits[np.arange(len(mine)),
                                          np.asarray(mine)]
            gaps.append(gap)
            exact.append(gap == 0.0)
            for f in (0.0, 0.5, 1.0, 2.0, 4.0):
                decided.setdefault(f, []).append(
                    _decided(ref, margin, w, f)[at])
            saved[f"gap{u}"], saved[f"margin{u}"] = gap, margin
            saved[f"first{u}"] = np.asarray(len(others[i]) - 1)
            top = np.sort(logits, axis=-1)[:, -2:]
            saved[f"top2gap{u}"] = top[:, 1] - top[:, 0]
        os.makedirs("chiprun_out/pr62", exist_ok=True)
        np.savez("chiprun_out/pr62/margins.npz", **saved)
        gaps, exact = np.concatenate(gaps), np.concatenate(exact)
        print(json.dumps({
            "phase": "bf16:background", "rows": len(picked),
            "positions": int(len(gaps)),
            "gap_p50_p99_max": [float(q) for q in
                                np.percentile(gaps, (50, 99, 100))],
            "by_margin_factor": {
                str(f): {"judged": int(ok.sum()),
                         "worst_gap": float(gaps[ok].max()) if ok.any()
                         else 0.0,
                         "over_near_tie": int((gaps[ok] > NEAR_TIE_LOGITS
                                               ).sum()),
                         "exact": float(exact[ok].mean()) if ok.any()
                         else 1.0}
                for f, ok in ((f, np.concatenate(d))
                              for f, d in decided.items())}}), flush=True)

    def weights_in_float8(params):
        """Every weight matrix rounded to float8, IN PLACE (no room for a
        second copy beside the engine): the last control of its phase."""
        groups = [params["embed"], params] + [
            g for lp in params["layers"] for g in lp.values()]
        for group in groups:
            for key in list(group):
                if hasattr(group[key], "ndim") and group[key].ndim >= 2:
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

    def no_decay(cfg, p, u, ba, counts):
        q, k, v, beta, g = ssm_delta_inputs(cfg, p, u, ba, counts)
        return q, k, v, beta, jnp.zeros_like(g)

    def gate_first(cfg, p, y, z, dtype, groups, gate_first):
        scale = jnp.tile(p["norm"]["scale"], groups)    # a head's, each head
        return ssm_gated_norm(cfg, dict(p, norm={"scale": scale}), y, z,
                              dtype, groups, True)

    ssm_delta_inputs, ssm_gated_norm = ssm.delta_inputs, ssm.gated_norm
    same = lambda x: x
    whole_head = lambda cfg: dataclasses.replace(cfg, rotary_pct=1.0)
    #: name -> (the model's change, the tree's, ops/ssm.py functions replaced)
    wrong = {
        "state_pool_in_bf16": (same, same, {
            "delta_step": rounded(ssm.delta_step),
            "delta_chunk": rounded(ssm.delta_chunk)}),
        "decay_dropped": (same, same, {"delta_inputs": no_decay}),
        "gate_before_the_norm": (same, same, {"gated_norm": gate_first}),
        "rotary_over_the_whole_head": (whole_head, same, {}),
        "stale_state_in_a_reused_slot": (same, same, {
            "fresh_rows": lambda starts: jnp.zeros(starts.shape, bool)}),
    }
    served = exact = True
    caught = {}
    if "bf16" in args.phases:
        served, reported = phase(
            "bf16", hf, engine_conf, LOGIT_DIFF_LIMIT, 0,
            # the nearest precision below the one the configuration
            # states: what the runner's limits have to catch
            {"weights_in_float8": (same, weights_in_float8, {})})
        for name, was in reported.items():
            caught["bf16:" + name] = was
    if "float32" in args.phases:
        # the controls that change the PROGRAM are held in float32 at one
        # period (a full-depth program is minutes to compile, and a bf16
        # program's own rounding is of the size of what some of them move):
        # the same widths, three delta-rule layers and a full one
        with jax.default_matmul_precision("highest"):
            exact, caught32 = phase(
                "float32", dict(hf, num_hidden_layers=4),
                # (the history kernel's float32 block does not fit VMEM:
                # this phase holds the pools and the equations, not it)
                dict(engine_conf, dtype="float32", use_pallas=False,
                     num_blocks=min(engine_conf["num_blocks"], 1024)),
                F32_LOGIT_DIFF_LIMIT, 2, wrong)
        for name, was in caught32.items():
            caught["float32:" + name] = was
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    ok = served and exact and forms_ok and all(caught.values())
    print(json.dumps({"ok": bool(ok), "sound_passes": served,
                      "sound_float32_passes": exact,
                      "forms_are_the_recurrence": forms_ok,
                      "controls_caught": caught,
                      "memory_peak_bytes": int(peak),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    sys.exit(0 if ok else 1)


def _decided(ref, margin, w, factor):
    """``ref.decided`` with the file's three margins times ``factor``."""
    kept = (ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN,
            ref.STATE_LOGIT_MARGIN)
    ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN, \
        ref.STATE_LOGIT_MARGIN = (m * factor for m in kept)
    try:
        return ref.decided(margin, w)
    finally:
        ref.UNDECIDED_LOGIT_MARGIN, ref.NEIGHBOUR_LOGIT_MARGIN, \
            ref.STATE_LOGIT_MARGIN = kept


if __name__ == "__main__":
    main()
