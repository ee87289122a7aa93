"""A chip-side check of Nemotron-H's hybrid stack outside the benchmark's
cell (run it through ``chiprun --chips 1 --timeout 3000 -- python3
tools/chip_check_nemotron_h.py``; on the CPU add ``--rehearse`` for tiny
widths, where the controls are NOT all caught: tiny widths are a null
model).

The configuration is the cell's (``benchmark/configs/nemotron3-nano-l26-
e16-serve.json``, the published widths) and so is the engine block, so the
programs are the timed path's own 64-row ones. First every slot of the
state pools is DIRTIED: 64 throwaway sequences are prefilled and flushed.
Then two JUDGED sequences — a LONG prompt of ``--prompt`` tokens (1,000:
under ``chat-closed64``'s lengths, seven whole chunks and one of 104) and a
SHORT one of 16 (the mix's shortest: every judged position within reach of
what its slot held before), each followed by ``--steps`` greedy tokens —
run beside 40 background sequences that decode a random token a step: the
long prompt's first chunk rides the fresh program, its later
chunks GROUPED split steps (one row at the chunk's width in the chunk form
from the state the earlier launches left, 40 rows of one query stepping the
recurrence, the pools carried through the capacity loop), its decode steps
the 64-row decode program (the layer's whole region in one pass, by slot).
Every position's logits from the prompt's last on are held against the
plain float32 reference's FULL FORWARD of the same tokens (the per-token
recurrence). A bf16 program's experts flip against a float32 walk's at one
token in three of this stack (11 routings a token, 26 layers of rounding),
and a flip parts the logits of the positions AFTER it too, so the bf16
phase holds: the serve runner's limits on the argmax over the positions
whose routing the reference's own margins decide (both rows' together);
``LOGIT_DIFF_LIMIT`` on the MEDIAN logit difference over those; and
``ROW_MEDIAN_LIMIT`` on the median over ALL of each row's positions. Then
the same tokens teacher-forced through programs that are WRONG in one way
each — the state rounded to bfloat16 on its way to the pool, the ``D·x``
term dropped, the convolution's bias dropped, a row at position 0 left with
what its slot held, every weight matrix rounded to float8 — which must not
pass. The pool's PRECISION is held in a second phase in float32 (the
published widths, layers ``MEM*``, two held experts, ``highest`` matmul
precision, the XLA history reader: no expert flips there, so it holds the
LARGEST difference, ``F32_LOGIT_DIFF_LIMIT``): a state rounded to bfloat16
between steps moves a bf16 program's logits by about what its own rounding
does.
``by_margin`` on every line says what each candidate
``UNDECIDED_LOGIT_MARGIN`` would have judged (the positions before held
by the reference's ``neighbours_decided`` as they are): [positions,
worst gap, largest logit difference]. One JSON object a line; the last says ``ok``."""

import argparse
import gc
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "nemotron3-nano-l26-e16-serve"
#: the most the MEDIAN logit difference over the decided positions may be
#: in the bf16 phase (the largest difference over a vocabulary of 16,384; a
#: median, because a logit difference over 0.25 marks an expert that flipped
#: somewhere behind the position, which even a decided position meets now
#: and then). Between two sets of readings on the v5e (PERF.md §6, PR 43):
#: the sound bf16 program's 0.054 over the 24 decided positions of both rows
#: (0.076 at their 90th percentile, 0.245 at most; 0.063–0.076 over ALL
#: positions, where the 90th percentile is 0.44 and the largest 1.6: flipped
#: experts), and 2.82 (every weight matrix in float8), 4.09 (the
#: convolution's bias dropped), 4.24 (``D·x`` dropped)
LOGIT_DIFF_LIMIT = 0.4
#: ... and the most the median over ALL of a judged row's positions may be:
#: what a STALE state in a reused slot moves — every early position of the
#: short row, decided or not: 0.325 there (0.081 on the long row, whose
#: judged positions lie 1,000 tokens past the slot's old state) against the
#: sound program's 0.063 / 0.076 (0.08–0.11 in a CPU replica of the bf16
#: program over 30 sequences) and 0.085–0.089 with the pool in bf16
ROW_MEDIAN_LIMIT = 0.2
#: the float32 phase holds the LARGEST difference over its decided
#: positions: the sound float32 program reads 8e-5 on the chip (1e-5 at the
#: median), the state rounded to bf16 on its way to the pool 0.0091–0.0127
#: (0.0043–0.0048 at the median), a stale slot 0.024 and 1.08
F32_LOGIT_DIFF_LIMIT = 0.001
MARGINS = (0.0, 0.01, 0.02, 0.04, 0.08, 0.16)
BACKGROUND = 40


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=4300000043)
    ap.add_argument("--prompt", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--only", default=None,
                    help="comma-separated controls to run (default: all)")
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
    rng = np.random.default_rng(args.seed)
    rows, steps = engine_conf["max_sequences"], args.steps
    counted = {name: registry.counter("dispatch/" + name) for name in (
        "steps.fresh", "steps.split", "steps.decode", "split_grouped_steps",
        "state_rows", "state_resets", "ssm_chunk_tokens")}

    def phase(tag, hf, engine_conf, limit, which, row_limit, controls):
        """One configuration: the sound program's greedy walk against the
        reference, then each control's teacher-forced walk → (sound
        passes, {control: caught}). ``limit`` holds the decided positions'
        logit differences at their median (``which`` 0) or their largest
        (2)."""
        cfg = config_from_hf(hf)
        w = ref.Widths.from_hf(hf)
        vocab = cfg.vocab_size
        # the judged sequences: LONG (the state carried over eight chunks)
        # and SHORT (every judged position within reach of what its slot
        # held before it)
        prompts = {0: rng.integers(0, vocab, args.prompt).tolist(),
                   1: rng.integers(0, vocab, 16).tolist()}
        junk = [rng.integers(0, vocab, 24).tolist() for _ in range(rows)]
        others = [rng.integers(0, vocab, int(n)).tolist()
                  for n in rng.integers(8, 33, BACKGROUND)]
        fed_others = rng.integers(
            0, vocab, (args.prompt // 100 + steps + 8, BACKGROUND))

        def walk(params, tokens):
            """Teacher-forced (``tokens[uid]`` longer than the prompt) or
            greedy: each judged row's logits at its last prompt position
            and at its decode positions, and the tokens it was fed."""
            eng = RaggedInferenceEngineTPU(cfg, engine_conf, params=params,
                                           rng=model_lib.prng_key(args.seed))
            # dirty every slot, then hand them all back
            junk_ids = list(range(1000, 1000 + rows))
            eng.put(junk_ids, junk)
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
        eng, logits, seqs, programs = walk(None, prompts)
        work = {name: int(c.value - before[name])
                for name, c in counted.items()}
        params = eng.params
        del eng
        gc.collect()
        # reference logits at the positions that predicted each fed token
        want, margin, judged = {}, {}, {}
        for u, seq in seqs.items():
            at = slice(len(prompts[u]) - 1, len(seq) - 1)
            want[u] = ref.logits_of(w, params, seq[:-1], dev)[at]
            _, (m,) = ref.hidden_and_margins(
                w, params, [ref._padded(seq[:-1])], dev)
            m = np.asarray(m)[:len(seq) - 1]
            margin[u], judged[u] = m[at], ref.decided(m, w)[at]
            # the same rule at other own-position margins, for by_margin
            margin[u] = np.where(ref.neighbours_decided(m, w)[at],
                                 margin[u], -1.0)

        def judge(name, got):
            """A line a judged row, then one over both rows' decided
            positions together (a short row may have none of its own):
            the runner's limits on them, ``limit`` on their logit
            differences (``which``: 0 the median, 2 the largest) and, where
            given, ``row_limit`` on the median over ALL of each row's
            positions (what a stale slot moves, which parts every early
            position of the short row and few decided ones)."""
            gaps, diffs, exact, rows_ok = [], [], [], True
            for u in prompts:
                decided = judged[u]
                fed = np.asarray(seqs[u][len(prompts[u]):])
                gap = want[u].max(-1) - \
                    want[u][np.arange(steps), got[u].argmax(-1)]
                diff = np.abs(got[u] - want[u]).max(-1)
                gaps.append(gap[decided])
                diffs.append(diff[decided])
                exact.append((got[u].argmax(-1) ==
                              want[u].argmax(-1))[decided])
                line = {"phase": f"{tag}:{name}", "prompt": len(prompts[u]),
                        "decided": int(decided.sum()), "of": steps,
                        "logit_diff_decided_p50_p90_max": [
                            round(float(q), 5) for q in np.percentile(
                                diff[decided], (50, 90, 100))]
                        if decided.any() else None,
                        "logit_diff_all_p50_p90_max": [
                            round(float(q), 5)
                            for q in np.percentile(diff, (50, 90, 100))],
                        "fed_is_its_argmax":
                            float((got[u].argmax(-1) == fed).mean()),
                        "finite": bool(np.isfinite(got[u]).all())}
                rows_ok = rows_ok and line["finite"] and (
                    row_limit is None or
                    line["logit_diff_all_p50_p90_max"][0] <= row_limit)
                # what each candidate margin would have judged
                line["by_margin"] = {
                    str(m): [int((margin[u] >= m).sum()),
                             round(float(gap[margin[u] >= m].max()), 4),
                             round(float(diff[margin[u] >= m].max()), 6)]
                    for m in MARGINS if (margin[u] >= m).any()}
                print(json.dumps(line), flush=True)
            gaps, diffs, exact = (np.concatenate(t)
                                  for t in (gaps, diffs, exact))
            if not len(gaps):       # nothing decided: nothing was held
                print(json.dumps({"phase": f"{tag}:{name}", "prompt": "both",
                                  "decided": 0, "passes": False}),
                      flush=True)
                return False
            line = {"phase": f"{tag}:{name}", "prompt": "both",
                    "decided": len(gaps), "logit_diff_limit": limit,
                    "row_median_limit": row_limit,
                    "logit_diff_decided_p50_p90_max": [
                        round(float(q), 5)
                        for q in np.percentile(diffs, (50, 90, 100))],
                    "worst_gap_of_its_argmax": float(gaps.max()),
                    "exact_argmax_share": float(exact.mean())}
            line["passes"] = bool(
                rows_ok and
                line["worst_gap_of_its_argmax"] <= NEAR_TIE_LOGITS and
                line["exact_argmax_share"] >= MIN_EXACT_ARGMAX and
                line["logit_diff_decided_p50_p90_max"][which] <= limit)
            print(json.dumps(line), flush=True)
            return line["passes"]

        sound = judge("sound", logits)
        print(json.dumps({"phase": f"{tag}:programs", "names": programs,
                          "contexts": [len(s) for s in seqs.values()],
                          "launches": work}), flush=True)
        caught = {}
        for name, (change, patches) in controls.items():
            if args.only and name not in args.only.split(","):
                continue
            kept = {attr: getattr(ssm, attr) for attr in patches}
            for attr, fn in patches.items():
                setattr(ssm, attr, fn)
            try:
                _eng, got, _seqs, _ = walk(change(params), seqs)
            finally:
                for attr, fn in kept.items():
                    setattr(ssm, attr, fn)
            del _eng
            gc.collect()    # an engine and its step programs are a cycle
            caught[name] = not judge(name, got)
        return sound, caught

    def in_ssm(**leaves):
        def change(params):
            return dict(params, layers=[
                dict(lp, ssm=dict(lp["ssm"], **{
                    k: f(lp["ssm"][k]) for k, f in leaves.items()}))
                if "ssm" in lp else lp for lp in params["layers"]])
        return change

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
        def wrapped(*scan_args):
            y, s = scan(*scan_args)
            # (``reduce_precision`` to bf16's 8 + 7 bits: the compiler
            # folds a pair of converts away, and the control with it)
            return y, jax.lax.reduce_precision(s, 8, 7)
        return wrapped

    same = lambda params: params
    #: name -> (the tree's change, ops/ssm.py functions replaced)
    state_in_bf16 = (same, {"scan_step": rounded(ssm.scan_step),
                            "scan_chunk": rounded(ssm.scan_chunk)})
    stale = (same, {"fresh_rows":
                    lambda starts: jnp.zeros(starts.shape, bool)})
    served, caught = phase("bf16", hf, engine_conf, LOGIT_DIFF_LIMIT, 0,
                           ROW_MEDIAN_LIMIT, {
        "state_pool_in_bf16": state_in_bf16,
        "stale_state_in_a_reused_slot": stale,
        "skip_term_dropped": (in_ssm(D=jnp.zeros_like), {}),
        "convolution_bias_dropped": (in_ssm(conv_b=jnp.zeros_like), {}),
        # the nearest precision below the one the configuration states:
        # what the margin of the reference has to let the runner catch
        "weights_in_float8": (weights_in_float8, {}),
    })
    # a state rounded to bf16 between steps moves a bf16 program's logits
    # by about as much as its own rounding does, so the pool's precision is
    # held in float32: the same widths, one layer of each kind and a second
    # mixer, two held experts
    with jax.default_matmul_precision("highest"):
        exact, caught32 = phase(
            "float32", dict(hf, num_hidden_layers=4,
                            hybrid_override_pattern="MEM*",
                            n_routed_experts=min(2, hf["n_routed_experts"])),
            # (the history kernel's float32 block of 16 queries a KV head x
            # 128 does not fit VMEM: this phase holds the pools, not it)
            dict(engine_conf, dtype="float32", use_pallas=False),
            F32_LOGIT_DIFF_LIMIT, 2, None,
            {"state_pool_in_bf16": state_in_bf16,
             "stale_state_in_a_reused_slot": stale})
    for name, was in caught32.items():
        caught["float32:" + name] = was
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    # (the bf16 phase's reading of the pool's precision is reported — 0.062
    # against the sound 0.054 at the decided positions' median: inside its
    # own rounding — and the float32 phase's decides)
    deciding = {k: v for k, v in caught.items() if k != "state_pool_in_bf16"}
    ok = served and exact and all(deciding.values())
    print(json.dumps({"ok": bool(ok), "sound_passes": served,
                      "sound_float32_passes": exact,
                      "controls_caught": caught,
                      "memory_peak_bytes": int(peak),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
