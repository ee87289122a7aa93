"""A chip-side check of the latent stack outside the benchmark's cell (run
it through ``chiprun --chips 1 -- python3 tools/chip_check_latent.py``; on
the CPU add ``--rehearse`` for tiny widths).

The configuration is the cell's (``benchmark/configs/gigachat3.1-l5-e16-
serve.json``): a prompt of five chunks (640 tokens: the later chunks'
expanded attention joins an ABSORBED history through ``merge_attention``),
then 256 greedy tokens through the decode program, every step's logits
against the plain float32 reference's full forward of the same tokens, on
the positions whose routing the reference's own margins decide: by the
serve runner's limits on the argmax, and by ``LOGIT_DIFF_LIMIT`` on the
logits themselves. Then the same tokens teacher-forced through
programs that are WRONG in one way each — the latent rows or ``W_kvb``
rounded to float8, the group cut left out, YaRN's softmax scale left out —
which must not pass. One JSON object a line; the last says ``ok``."""

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "gigachat3.1-l5-e16-serve"
#: the most a decided position's logits may stray from the reference's.
#: Between two readings on the v5e (PERF.md §6, PR 35): the sound bf16
#: program's 0.114, and 1.16 (the latent pool in float8), 1.33 (``W_kvb`` in
#: float8), 1.16 (no group cut), 3.78 (no YaRN softmax scale)
LOGIT_DIFF_LIMIT = 0.4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=4000000007)
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
    cfg = model_lib.build_model(conf, args.rehearse)
    w = ref.Widths.from_hf(model_lib.published_keys(conf, args.rehearse))
    dev = jax.devices()[0]
    engine_conf = dict(conf["engine"])
    chunk = engine_conf["prefill_chunk"]
    if args.rehearse:
        engine_conf.update(max_sequences=2, num_blocks=16)
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg.vocab_size, 5 * chunk).tolist()

    def walk(model, params, tokens, steps):
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
        return eng, np.stack(rows), seq, programs

    steps = 256
    eng, logits, seq, programs = walk(cfg, None, prompt, steps)
    params = eng.params
    del eng
    # reference logits at the positions that predicted each fed token
    want = ref.logits_of(w, params, seq[:-1], dev)[len(prompt) - 1:]
    _, (margin,) = ref.hidden_and_margins(
        w, params, [ref._padded(seq[:-1])], dev)
    decided = np.asarray(margin)[len(prompt) - 1:len(seq) - 1] >= \
        ref.UNDECIDED_LOGIT_MARGIN

    def judge(name, got):
        fed = np.asarray(seq[len(prompt):])
        gap = want.max(-1) - want[np.arange(steps), got.argmax(-1)]
        line = {"phase": name, "decided": int(decided.sum()), "of": steps,
                "max_logit_diff_decided":
                    float(np.abs(got - want).max(-1)[decided].max()),
                "max_logit_diff_all": float(np.abs(got - want).max()),
                "worst_gap_of_its_argmax": float(gap[decided].max()),
                "exact_argmax_share":
                    float((got.argmax(-1) == want.argmax(-1))[decided]
                          .mean()),
                "fed_is_its_argmax":
                    float((got.argmax(-1) == fed).mean())}
        line["passes"] = bool(
            line["worst_gap_of_its_argmax"] <= NEAR_TIE_LOGITS and
            line["exact_argmax_share"] >= MIN_EXACT_ARGMAX and
            line["max_logit_diff_decided"] <= LOGIT_DIFF_LIMIT and
            np.isfinite(got).all())
        print(json.dumps(line), flush=True)
        return line

    sound = judge("sound", logits)
    print(json.dumps({"phase": "programs", "names": programs}), flush=True)

    def float8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def kvb_float8():
        p = jax.tree.map(lambda a: a, params)
        for lp in p["layers"]:
            lp["attn"]["wkv_b"] = float8(lp["attn"]["wkv_b"])
        return p

    write_rows = pa.write_rows
    controls = {
        "latent_pool_in_float8": (cfg, lambda: params, lambda pool, rows,
                                  *a, **k: write_rows(pool, float8(rows),
                                                      *a, **k)),
        "w_kvb_in_float8": (cfg, kvb_float8, write_rows),
        "group_cut_left_out": (dataclasses.replace(
            cfg, router_groups=1, router_groups_kept=1), lambda: params,
            write_rows),
        "yarn_softmax_scale_left_out": (dataclasses.replace(
            cfg, rope_yarn=cfg.rope_yarn[:5] + (0.0,)), lambda: params,
            write_rows),
    }
    caught = {}
    for name, (model, make_params, writer) in controls.items():
        pa.write_rows = writer
        try:
            _eng, got, _seq, _ = walk(model, make_params(), seq, steps)
        finally:
            pa.write_rows = write_rows
        del _eng
        caught[name] = not judge(name, got)["passes"]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    ok = sound["passes"] and all(caught.values())
    print(json.dumps({"ok": bool(ok), "sound_passes": sound["passes"],
                      "controls_caught": caught,
                      "memory_peak_bytes": int(peak),
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
