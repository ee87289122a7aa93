"""A chip-side check of Cohere2-MoE's block outside the benchmark's cell (run
it through ``chiprun --chips 1 --timeout 2400 -- python3
tools/chip_check_command_a.py``; on the CPU add ``--rehearse`` for tiny
widths, where the controls are NOT caught: tiny widths are a null model).

The configuration is the cell's (``benchmark/configs/command-a-plus-l4-e16-
serve.json``, the published widths): a prompt of 47 chunks (6,016 tokens:
from the 33rd chunk on the live queries of a chunk see chunk + history
ACROSS the window's edge through ``paged_attn_lse(window=)`` and
``merge_attention``, and the full layer's history grows past the window),
then 256 greedy tokens through the decode program, every step's logits
against the plain float32 reference's full forward of the same tokens, on
the positions whose routing the reference's own margins decide: by the
serve runner's limits on the argmax, and by ``LOGIT_DIFF_LIMIT`` on the
logits themselves. Then the same tokens teacher-forced through programs
that are WRONG in one way each — the window one token short, rotary
applied on the full layers, the shared experts summed and not averaged, the
stream re-normalised before the experts (a sequential block), K and V
rounded to float8 on their way into the cache, every weight matrix rounded
to float8 — which must not pass. The
window ONE TOKEN SHORT is held in a second phase in float32 (the published
widths, a window and a full layer, two held experts, ``highest`` matmul
precision): one key of 4,096 moves the logits by less than bf16's own
rounding. ``by_margin`` on every line says what each candidate
``UNDECIDED_LOGIT_MARGIN`` would have judged: [positions, worst gap,
largest logit difference] (the reference's constant was chosen from it).
One JSON object a line; the last says ``ok``."""

import argparse
import dataclasses
import gc
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "command-a-plus-l4-e16-serve"
#: the most a decided position's logits may stray from the reference's.
#: Between two readings on the v5e (PERF.md §6, PR 37): the sound bf16
#: program's 0.047 (at every margin from 0.01 up), and 0.146-0.384 (K and
#: V in float8; 0.743 at margins under 0.04), 1.09 (rotary on the full
#: layers), 6.3 (a sequential block), 7.8 (the shared experts summed)
LOGIT_DIFF_LIMIT = 0.1
#: the same for the float32 phase (the window's edge): the sound float32
#: program reads 0.0020 on the chip (the kernel's float32 dots and the
#: "highest" matmuls are bf16 passes there; 2e-6 on the CPU), the window
#: one token short 0.081
F32_LOGIT_DIFF_LIMIT = 0.01
MARGINS = (0.0, 0.01, 0.02, 0.04, 0.08, 0.16)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=3700000037)
    ap.add_argument("--chunks", type=int, default=47)
    ap.add_argument("--steps", type=int, default=256)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmark.lib import model as model_lib
    from benchmark.runners.serve import MIN_EXACT_ARGMAX, NEAR_TIE_LOGITS
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    conf = model_lib.load_config(CONFIG)
    ref = model_lib.load_reference(conf)
    dev = jax.devices()[0]
    hf = model_lib.published_keys(conf, args.rehearse)
    chunk = conf["engine"]["prefill_chunk"]
    if args.rehearse:
        args.chunks, args.steps = 5, 16     # past the rehearsal's window 256
    rng = np.random.default_rng(args.seed)
    write_kv = pa.write_kv

    def float8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def phase(tag, hf, engine_conf, steps, limit, controls):
        """One configuration: the sound program's greedy walk against the
        reference, then each control's teacher-forced walk → (sound
        passes, {control: caught})."""
        from deepspeed_tpu.models.hf_loader import config_from_hf
        cfg = config_from_hf(hf)
        w = ref.Widths.from_hf(hf)
        prompt = rng.integers(0, cfg.vocab_size, args.chunks * chunk).tolist()

        def walk(model, params, tokens):
            """Teacher-forced (``tokens`` longer than the prompt) or
            greedy: the logits of the last prompt position and of the
            decode positions, and the tokens fed."""
            eng = RaggedInferenceEngineTPU(model, engine_conf, params=params,
                                           rng=model_lib.prng_key(args.seed))
            seq, rows = list(tokens[:len(prompt)]), []
            out = eng.put([0], [seq])
            for i in range(steps):
                rows.append(np.asarray(out[0], np.float32))
                nxt = int(tokens[len(seq)]) if len(seq) < len(tokens) \
                    else int(np.argmax(rows[-1]))
                seq.append(nxt)
                out = eng.put([0], [[nxt]])
            programs = sorted(fn.__name__ for fn in eng._step_fns.values())
            return eng, np.stack(rows), seq, programs

        eng, logits, seq, programs = walk(cfg, None, prompt)
        params = eng.params
        del eng
        gc.collect()
        # reference logits at the positions that predicted each fed token
        want = ref.logits_of(w, params, seq[:-1], dev)[len(prompt) - 1:]
        _, (margin,) = ref.hidden_and_margins(
            w, params, [ref._padded(seq[:-1])], dev)
        margin = np.asarray(margin)[len(prompt) - 1:len(seq) - 1]
        decided = margin >= ref.UNDECIDED_LOGIT_MARGIN

        def judge(name, got):
            fed = np.asarray(seq[len(prompt):])
            gap = want.max(-1) - want[np.arange(steps), got.argmax(-1)]
            diff = np.abs(got - want).max(-1)
            line = {"phase": f"{tag}:{name}", "decided": int(decided.sum()),
                    "of": steps, "logit_diff_limit": limit,
                    "max_logit_diff_decided":
                        float(diff[decided].max(initial=0.0)),
                    "max_logit_diff_all": float(diff.max()),
                    "worst_gap_of_its_argmax":
                        float(gap[decided].max(initial=0.0)),
                    "exact_argmax_share":
                        float((got.argmax(-1) == want.argmax(-1))[decided]
                              .mean()) if decided.any() else 0.0,
                    "fed_is_its_argmax":
                        float((got.argmax(-1) == fed).mean())}
            line["passes"] = bool(
                line["worst_gap_of_its_argmax"] <= NEAR_TIE_LOGITS and
                line["exact_argmax_share"] >= MIN_EXACT_ARGMAX and
                line["max_logit_diff_decided"] <= limit and
                np.isfinite(got).all())
            # what each candidate margin would have judged
            line["by_margin"] = {
                str(m): [int((margin >= m).sum()),
                         round(float(gap[margin >= m].max()), 4),
                         round(float(diff[margin >= m].max()), 6)]
                for m in MARGINS if (margin >= m).any()}
            print(json.dumps(line), flush=True)
            return line

        sound = judge("sound", logits)
        print(json.dumps({"phase": f"{tag}:programs", "names": programs,
                          "context": len(seq),
                          "window": cfg.sliding_window}), flush=True)
        caught = {}
        for name, (changes, make_params, writer) in controls.items():
            pa.write_kv = writer
            try:
                _eng, got, _seq, _ = walk(
                    dataclasses.replace(cfg, **changes), make_params(params),
                    seq)
            finally:
                pa.write_kv = write_kv
            del _eng
            gc.collect()    # an engine and its step programs are a cycle
            caught[name] = not judge(name, got)["passes"]
        return sound["passes"], caught

    def sequential(params):
        layers = [dict(lp, ln2=lp["ln1"]) for lp in params["layers"]]
        return dict(params, layers=layers)

    def weights_in_float8(params):
        """Every weight matrix rounded to float8, IN PLACE (a second copy
        of 9.5 GB does not fit): the last control of its phase."""
        groups = [params["embed"]] + [g for lp in params["layers"]
                                      for g in lp.values()]
        for group in groups:
            for key in list(group):
                if group[key].ndim >= 2:
                    group[key] = float8(group[key])
        return params

    same = lambda params: params
    window = int(hf["sliding_window"])
    # the cell's configuration as it is served: bf16
    # (two rows and the pages a 6,272-token context needs: the engines of
    # seven walks come and go beside 9.5 GB of weights)
    engine_conf = dict(conf["engine"], max_sequences=2, num_blocks=128)
    if args.rehearse:
        engine_conf.update(num_blocks=32, max_seq_len=1024)
    served, caught = phase("bf16", hf, engine_conf, args.steps,
                           LOGIT_DIFF_LIMIT, {
        "rotary_on_the_full_layers": (dict(full_attn_rope=True), same,
                                      write_kv),
        "shared_experts_summed": (dict(shared_experts_averaged=1), same,
                                  write_kv),
        "sequential_block": (dict(parallel_block=False), sequential,
                             write_kv),
        "kv_in_float8": ({}, same, lambda ak, av, k, v, *a, **kw: write_kv(
            ak, av, float8(k), float8(v), *a, **kw)),
        # the nearest precision below the one the configuration states:
        # what the margin of the reference has to let the runner catch
        "weights_in_float8": ({}, weights_in_float8, write_kv),
    })
    # ONE key of a window's 4,096 moves a bf16 program's logits by less
    # than its own rounding does, so the window's edge is held in float32:
    # the same widths, a window and a full layer, two held experts
    share = dict(hf["expert_share"], held_experts=2)
    with jax.default_matmul_precision("highest"):
        exact, caught32 = phase(
            "float32", dict(hf, num_hidden_layers=2,
                            layer_types=["sliding_attention",
                                         "full_attention"],
                            expert_share=share),
            dict(engine_conf, dtype="float32", num_blocks=64,
                 max_seq_len=64 * engine_conf["block_size"]),
            min(args.steps, 64), F32_LOGIT_DIFF_LIMIT,
            {"window_one_token_short": (dict(sliding_window=window - 1),
                                        same, write_kv)})
    caught.update(caught32)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    ok = served and exact and all(caught.values())
    print(json.dumps({"ok": bool(ok), "sound_passes": served,
                      "sound_float32_passes": exact,
                      "controls_caught": caught,
                      "memory_peak_bytes": int(peak),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
