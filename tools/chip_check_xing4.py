"""A chip-side check of the wide-stream stack outside the benchmark's cell,
after ``tools/chip_check_latent.py`` (run it through ``chiprun --chips 1
--timeout 2400 -- python3 tools/chip_check_xing4.py``; on the CPU add
``--rehearse`` for tiny widths, where the wrong programs are NOT all caught:
tiny widths are a null model).

The configuration is the cell's (``benchmark/configs/xing4.0-29b-a4b-l6-
serve.json``: the published widths, six layers, all 64 experts, the whole
vocabulary): a prompt of five chunks (640 tokens: the later chunks' expanded
attention joins an ABSORBED history through ``merge_attention``, every token
slot solving its 12 maps), then 256 greedy tokens through the decode
program, every step's logits against the plain float32 reference's full
forward of the same tokens, on the positions whose routing the reference's
own margin decides: by the serve runner's limits on the argmax, and by
``LOGIT_DIFF_LIMIT`` on the logits themselves. The ``sound`` line also
gives, for each candidate routing margin, how many positions it decides and
the largest logit difference on them. Then the same tokens teacher-forced
through programs that are WRONG in one way each — the maps' dynamic term
``α·m`` zeroed, 2 Sinkhorn rounds for 20, the norm over the stream's ``nC``
values left out, ``H_post`` without its factor 2 — which must not pass. One
JSON object a line; the last says ``ok``."""

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "xing4.0-29b-a4b-l6-serve"
#: the most a decided position's logits may stray from the reference's.
#: Between two readings on the v5e (PERF.md §6, PR 58; two runs, the
#: positions decided by 0.08: 17 and 22 of 256): the sound bf16 program's
#: 0.073 / 0.070 (0.088 / 0.118 by 0.04), and 1.45 / 3.23 (2 Sinkhorn rounds
#: for 20), 4.66 (the dynamic term zeroed), 4.91 (no norm over the stream),
#: 4.88 (``H_post`` without its 2)
LOGIT_DIFF_LIMIT = 0.4
MARGINS = (0.0, 0.02, 0.04, 0.08, 0.16, 0.3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=5800000007)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--only", default="",
                    help="comma-separated wrong programs to run (default: "
                         "all four)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmark.lib import model as model_lib
    from benchmark.runners.serve import MIN_EXACT_ARGMAX, NEAR_TIE_LOGITS
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models import typed_layers as tl
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    conf = model_lib.load_config(CONFIG)
    ref = model_lib.load_reference(conf)
    cfg = model_lib.build_model(conf, args.rehearse)
    w = ref.Widths.from_hf(model_lib.published_keys(conf, args.rehearse))
    dev = jax.devices()[0]
    engine_conf = dict(conf["engine"])
    chunk = engine_conf["prefill_chunk"]
    if args.rehearse:
        engine_conf.update(max_sequences=2, num_blocks=16)
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg.vocab_size, 5 * chunk).tolist()
    steps = args.steps

    def walk(model, params, tokens):
        """Teacher-forced (``tokens`` longer than the prompt) or greedy:
        the logits of the last prompt position and of ``steps`` decode
        positions, and the tokens fed."""
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
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        return eng, np.stack(rows), seq, programs, int(peak)

    eng, logits, seq, programs, peak = walk(cfg, None, prompt)
    params = eng.params
    del eng
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
        line = {"phase": name, "decided": int(decided.sum()), "of": steps,
                "max_logit_diff_decided": float(diff[decided].max())
                if decided.any() else None,
                "max_logit_diff_all": float(diff.max()),
                "median_logit_diff_all": float(np.median(diff)),
                "worst_gap_of_its_argmax": float(gap[decided].max())
                if decided.any() else None,
                "exact_argmax_share":
                    float((got.argmax(-1) == want.argmax(-1))[decided]
                          .mean()) if decided.any() else None,
                "fed_is_its_argmax":
                    float((got.argmax(-1) == fed).mean()),
                "by_margin": {str(m): [
                    int((margin >= m).sum()),
                    round(float(diff[margin >= m].max()), 4),
                    round(float(gap[margin >= m].max()), 4)]
                    for m in MARGINS if (margin >= m).any()}}
        line["passes"] = bool(
            decided.any() and
            line["worst_gap_of_its_argmax"] <= NEAR_TIE_LOGITS and
            line["exact_argmax_share"] >= MIN_EXACT_ARGMAX and
            line["max_logit_diff_decided"] <= LOGIT_DIFF_LIMIT and
            np.isfinite(got).all())
        print(json.dumps(line), flush=True)
        return line

    sound = judge("sound", logits)
    print(json.dumps({"phase": "programs", "names": programs,
                      "memory_peak_bytes": peak}), flush=True)

    def alphas_zeroed():
        p = jax.tree.map(lambda a: a, params)
        for lp in p["layers"]:
            for part in ("hc_attn", "hc_ffn"):
                lp[part]["scale"] = jnp.zeros_like(lp[part]["scale"])
        return p

    def no_norm(_cfg, x):
        return jnp.ones(x[0].shape[:-1] + (1,), jnp.float32)

    #: name → (the model, its parameters, typed_layers attributes patched)
    controls = {
        "dynamic_term_zeroed": (cfg, alphas_zeroed, {}),
        "two_rounds_for_twenty": (dataclasses.replace(
            cfg, hc_sinkhorn_iters=2), lambda: params, {}),
        "norm_over_the_stream_left_out": (cfg, lambda: params,
                                          {"_hc_rms_factor": no_norm}),
        "h_post_without_its_factor_2": (cfg, lambda: params,
                                        {"HC_POST_GAIN": 1.0}),
    }
    only = [n for n in args.only.split(",") if n] or list(controls)
    caught = {}
    for name in only:
        model, make_params, patched = controls[name]
        saved = {attr: getattr(tl, attr) for attr in patched}
        for attr, value in patched.items():
            setattr(tl, attr, value)
        try:
            _eng, got, _seq, _, _ = walk(model, make_params(), seq)
        finally:
            for attr, value in saved.items():
                setattr(tl, attr, value)
        del _eng
        caught[name] = not judge(name, got)["passes"]
    ok = sound["passes"] and all(caught.values())
    print(json.dumps({"ok": bool(ok), "sound_passes": sound["passes"],
                      "controls_caught": caught,
                      "logit_diff_limit": LOGIT_DIFF_LIMIT,
                      "routing_margin": ref.UNDECIDED_LOGIT_MARGIN,
                      "memory_peak_bytes": peak,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
