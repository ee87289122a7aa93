"""A chip-side check of the stack that PICKS ITS KEYS outside the
benchmark's cell (``chiprun --chips 1 --timeout 3000 -- python3
tools/chip_check_glm_dsa.py``; on the CPU add ``--rehearse`` for tiny
widths, where the controls are NOT all caught: tiny widths are a null
model).

The configuration is the cell's (``benchmark/configs/glm-5.2-l5-e16-serve
.json``: GLM-5.2's published widths, layers ``full shared shared shared
full``): a prompt of 20,480 tokens carried through its 160 launches (every
query past the 16th chunk picks 2,048 of its keys, on both sides of its
chunk's edge; the history read runs under the picks' mask), then 256 greedy
tokens through the decode program (each reads the 2,048 rows it picked BY
TOKEN INDEX), the last prompt position's and every decode step's LOGITS
against the plain float32 reference's full forward of the same tokens
(computed in blocks). Three kinds of walk, each judged by what it can be
held to:

- ``reference_picks``: the program HANDED THE REFERENCE'S PICKS (its own
  scores computed and dropped): everything but the selection, held to the
  reference's LOGITS on the positions whose routing is decided
  (``LOGIT_DIFF_LIMIT``), as the latent cell's check holds its program;
  ``weights_in_float8`` is the same walk with every weight matrix rounded
  to float8 and must not pass.
- ``sound``: the program with ITS OWN picks. With random weights the keys
  at the top-k boundary carry an average share of the softmax, so the
  thirty-odd picks that bf16 rounding swaps among 20,000 keys move the
  logits by some tenths at every position (the reference's docstring): the
  difference is REPORTED (``mean_logit_diff_all``: what boundary swaps
  alone cause, against ``reference_picks``' figure), and the walk is held,
  as the cell is, to the serve runner's limits on the positions whose
  routing AND argmax the reference decides.
- the picking controls, each WRONG in one way, which must not pass —
  judged as ``sound`` is AND, because two sound walks already part by some
  tenths, held against the SOUND program's own logits (one program, one
  precision: a mechanism that matters moves them, by ``CONTROL_MOVES`` at
  least at some position; the program is deterministic, so the sound walk
  against itself reads 0): the LAST ``index_topk`` keys for the top ones; scores
  without the ReLU; without the heads' weights ``w``; index keys without
  RoPE; a borrower that scores for itself with the owner's weights; an
  owner that borrows from the owner below it; every cached row read where
  a query sees more than ``index_topk``.

Two phases (``--phases``). ``bf16``: the cell's five layers in bfloat16.
``float32``: layers ``full shared full`` at the published widths in float32
under ``default_matmul_precision("highest")``, the program and the
reference alike (the XLA readers: the kernel's float32 blocks do not fit
VMEM), where ``reference_picks`` stands five times closer still.
``--only a,b`` picks controls. One JSON object a line; the last says
``ok``."""

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "glm-5.2-l5-e16-serve"
#: the most a routing-decided position's logits may stray from the
#: reference's in bfloat16 ONCE THE PROGRAM IS HANDED THE REFERENCE'S PICKS.
#: Between two readings on the v5e (PERF.md §6, PR 52): 0.076 sound, and
#: the same walk with float8 weights
LOGIT_DIFF_LIMIT = 0.4
#: a picking control is caught where it fails the runner's limits OR moves
#: some position's logits this far from the SOUND program's (PERF.md §6,
#: PR 52: the two controls that swap one owner's picks for another's move
#: them by tenths, as much as bf16's own boundary swaps move the sound
#: program from the reference)
CONTROL_MOVES = 0.05
#: the same in the float32 phase: 0.0145 sound (RoPE angles of 20,000
#: radians hold 1e-3 in float32) against bfloat16's 0.076
F32_LOGIT_DIFF_LIMIT = 0.035


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=4000000007)
    ap.add_argument("--prompt", type=int, default=20480)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--phases", default="bf16,float32")
    ap.add_argument("--only", default=None,
                    help="controls to run, comma-separated (default all)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from benchmark.lib import model as model_lib
    from benchmark.runners.serve import MIN_EXACT_ARGMAX, NEAR_TIE_LOGITS
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models import typed_layers as tl
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    conf = model_lib.load_config(CONFIG)
    ref = model_lib.load_reference(conf)
    dev = jax.devices()[0]
    only = set(args.only.split(",")) if args.only else None
    if args.rehearse:
        args.prompt, args.steps = min(args.prompt, 300), min(args.steps, 16)

    def float8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def phase(tag, hf, engine_conf, limit, wanted):
        cfg = model_lib.build_model(
            {**conf, **hf, "rehearsal": {**conf["rehearsal"], **hf}},
            args.rehearse)
        keys = model_lib.published_keys({**conf, **hf, "rehearsal": {
            **conf["rehearsal"], **hf}}, args.rehearse)
        w = ref.Widths.from_hf(keys)
        rng = np.random.default_rng(args.seed)
        prompt = rng.integers(0, cfg.vocab_size, args.prompt).tolist()
        steps = args.steps

        def walk(model, params, tokens):
            """Teacher-forced (``tokens`` longer than the prompt) or
            greedy: the logits of the last prompt position and of the
            decode positions, and the tokens fed."""
            eng = RaggedInferenceEngineTPU(
                model, engine_conf, params=params,
                rng=model_lib.prng_key(args.seed))
            seq, rows = list(tokens[:len(prompt)]), []
            out = eng.put([0], [seq])
            for _ in range(steps):
                rows.append(np.asarray(out[0], np.float32))
                nxt = int(tokens[len(seq)]) if len(seq) < len(tokens) \
                    else int(np.argmax(rows[-1]))
                seq.append(nxt)
                out = eng.put([0], [[nxt]])
            names = sorted(fn.__name__ for fn in eng._step_fns.values())
            return eng, np.stack(rows), seq, names

        eng, logits, seq, programs = walk(cfg, None, prompt)
        params = eng.params
        del eng
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        xs, margins, masks = ref.hidden_and_margins(
            w, params, [ref.latent._padded(seq[:-1])], dev)
        ref_picks = masks[0]        # an owner's [T, T] bool: 0.46 GB each
        want = ref._logits_at(w, params, xs[0], at, dev)
        decided = np.asarray(margins[0])[at] >= ref.UNDECIDED_LOGIT_MARGIN
        del xs, masks

        lead = np.sort(want, axis=-1)[:, -2:]
        lead = lead[:, 1] - lead[:, 0]
        routed = decided

        def judge(name, got, handed_picks=False):
            """``handed_picks``: the walk read the reference's picks and is
            held to its logits where the routing is decided; else it is
            held to the runner's limits where the argmax is decided too."""
            decided = routed if handed_picks else \
                routed & (lead >= ref.UNDECIDED_ARGMAX_MARGIN)
            fed = np.asarray(seq[len(prompt):])
            gap = want.max(-1) - want[np.arange(steps), got.argmax(-1)]
            line = {"phase": f"{tag}/{name}",
                    "decided": int(decided.sum()), "of": steps,
                    "max_logit_diff_decided":
                        float(np.abs(got - want).max(-1)[decided].max(initial=0.0)),
                    "max_logit_diff_all": float(np.abs(got - want).max()),
                    "mean_logit_diff_all":
                        float(np.abs(got - want).max(-1).mean()),
                    "worst_gap_of_its_argmax":
                        float(gap[decided].max(initial=0.0)),
                    "exact_argmax_share":
                        float((got.argmax(-1) == want.argmax(-1))[decided]
                              .mean()) if decided.any() else 0.0,
                    "fed_is_its_argmax":
                        float((got.argmax(-1) == fed).mean()),
                    "limit": limit}
            line["passes"] = bool(
                decided.any() and
                line["worst_gap_of_its_argmax"] <= NEAR_TIE_LOGITS and
                line["exact_argmax_share"] >= MIN_EXACT_ARGMAX and
                (line["max_logit_diff_decided"] <= limit
                 or not handed_picks) and np.isfinite(got).all())
            print(json.dumps(line), flush=True)
            return line

        sound = judge("sound", logits)
        print(json.dumps({"phase": f"{tag}/programs", "names": programs,
                          "prompt": len(prompt), "steps": steps,
                          "layer_indexer": cfg.layer_indexer}), flush=True)

        def copy(tree):
            return jax.tree.map(lambda a: a, tree)

        def weights_in_float8():
            """In place, leaf by leaf (two copies of the weights do not fit
            the chip): the LAST control of a phase, ``params`` is spent."""
            nonlocal params
            leaves, tree = jax.tree.flatten(params)
            params = None
            for i in range(len(leaves)):
                if leaves[i].ndim >= 2:
                    # two eager converts: inside ONE jitted computation the
                    # compiler drops the pair (excess precision is allowed)
                    narrow = leaves[i].astype(jnp.float8_e4m3fn)
                    leaves[i] = narrow.astype(leaves[i].dtype)
            return jax.tree.unflatten(tree, leaves)

        def owner_weights_everywhere():
            p = copy(params)
            for lp in p["layers"]:
                lp.setdefault("indexer", p["layers"][0]["indexer"])
            return p

        # each control: (model, parameters, {module attribute: stand-in})
        picks, mask, scores, qkw = (pa.topk_picks, pa.topk_mask,
                                    pa.index_scores, tl.index_qkw)

        def latest(scores):
            """Scores that rank the visible keys by position."""
            pos = jnp.arange(scores.shape[-1], dtype=jnp.float32)
            return jnp.where(scores > -jnp.inf, pos, -jnp.inf)

        def unrotated_keys(cfg, p, x, c_q, sin, cos):
            q, _, wts = qkw(cfg, p, x, c_q, sin, cos)
            _, k, _ = qkw(cfg, p, x, c_q, jnp.zeros_like(sin),
                          jnp.ones_like(cos))
            return q, k, wts

        def no_relu(q, k, wts):
            s = jnp.einsum("ncjd,nsd->ncjs", q, k.astype(q.dtype),
                           preferred_element_type=jnp.float32)
            return jnp.einsum("ncjs,ncj->ncs", s, wts)

        # THE REFERENCE'S PICKS handed to the program: what is left is
        # what the program differs by for everything BUT its picks. A
        # query's position is the count of the keys it can see, less one;
        # the owner is the one whose ``index_qkw`` was traced last
        at_owner = [-1]

        def counting_qkw(*a):
            at_owner[0] += 1
            return qkw(*a)

        def handed():
            return ref_picks[at_owner[0] % len(ref_picks)]

        def columns(row, width):
            """The first ``width`` columns, False where the reference's
            padded length is shorter (a rehearsal's)."""
            short = max(0, width - row.shape[-1])
            return jnp.pad(row, ((0, 0),) * (row.ndim - 1) + ((0, short),)
                           )[..., :width]

        def handed_picks(s, k):
            seen = s > -jnp.inf
            row = handed()[jnp.maximum(seen.sum(-1) - 1, 0)]
            return picks(jnp.where(columns(row, s.shape[-1]) & seen, 1.0,
                                   -jnp.inf), k)

        def handed_mask(s, k):
            c = s.shape[-2]
            width = s.shape[-1] - c
            seen = s > -jnp.inf
            start = seen[..., :width].sum(-1)                   # [m, c]
            last = handed().shape[0] - 1
            row = handed()[jnp.minimum(
                start + jnp.arange(c, dtype=start.dtype), last)]
            own = jnp.take_along_axis(row, jnp.minimum(
                start[..., None] + jnp.arange(c, dtype=start.dtype), last),
                axis=-1)
            return jnp.concatenate([columns(row, width), own], -1) & seen

        def handed_walk(name, make_params):
            tl.index_qkw, pa.topk_picks, pa.topk_mask = (
                counting_qkw, handed_picks, handed_mask)
            try:
                _eng, got, _seq, _ = walk(cfg, make_params(), seq)
            finally:
                tl.index_qkw, pa.topk_picks, pa.topk_mask = qkw, picks, mask
            del _eng
            return judge(name, got, handed_picks=True)

        owners = cfg.layer_indexer
        controls = {
            "last_keys_for_the_top": (cfg, lambda: params, {
                (pa, "topk_picks"): lambda s, k: picks(latest(s), k),
                (pa, "topk_mask"): lambda s, k: mask(latest(s), k)}),
            "scores_without_relu": (cfg, lambda: params, {
                (pa, "index_scores"): no_relu}),
            "scores_without_head_weights": (cfg, lambda: params, {
                (pa, "index_scores"): lambda q, k, wts: scores(
                    q, k, jnp.full_like(wts, (cfg.index_heads *
                                              cfg.index_head_dim) ** -0.5))}),
            "index_keys_without_rope": (cfg, lambda: params, {
                (tl, "index_qkw"): unrotated_keys}),
            "borrower_scores_for_itself": (dataclasses.replace(
                cfg, layer_indexer=(1,) * len(owners)),
                owner_weights_everywhere, {}),
            "owner_borrows_from_the_owner_below": (dataclasses.replace(
                cfg, layer_indexer=(1,) + (0,) * (len(owners) - 1)),
                lambda: params, {}),
            "whole_history_read": (dataclasses.replace(
                cfg, layer_indexer=None), lambda: params, {}),
        }
        caught, reported = {}, {}
        for name, (model, make_params, patches) in controls.items():
            if name not in wanted or (only and name not in only):
                continue
            kept = {(mod, attr): getattr(mod, attr)
                    for mod, attr in patches}
            for (mod, attr), fn in patches.items():
                setattr(mod, attr, fn)
            try:
                _eng, got, _seq, _ = walk(model, make_params(), seq)
            finally:
                for (mod, attr), fn in kept.items():
                    setattr(mod, attr, fn)
            del _eng
            line = judge(name, got)
            moved = float(np.abs(got - logits).max())
            print(json.dumps({"phase": f"{tag}/{name}",
                              "max_logit_diff_from_sound": moved}),
                  flush=True)
            reported[name] = moved
            if wanted[name]:            # this phase decides it
                caught[name] = not line["passes"] or moved > CONTROL_MOVES

        def asked(name):
            return name in wanted and not (only and name not in only)

        given = handed_walk("reference_picks", lambda: params) \
            if asked("reference_picks") else None
        if asked("weights_in_float8"):      # LAST: it spends ``params``
            line = handed_walk("weights_in_float8", weights_in_float8)
            reported["weights_in_float8"] = line["max_logit_diff_decided"]
            caught["weights_in_float8"] = not line["passes"]
        sound["passes"] = sound["passes"] and (given is None or
                                               given["passes"])
        return sound, caught, reported

    engine_conf = dict(conf["engine"])
    if args.rehearse:
        engine_conf.update(max_sequences=2, num_blocks=16)
    picking = ("last_keys_for_the_top", "scores_without_relu",
               "scores_without_head_weights", "index_keys_without_rope",
               "borrower_scores_for_itself",
               "owner_borrows_from_the_owner_below", "whole_history_read")
    result = {"ok": True}
    if "bf16" in args.phases:
        sound, caught, reported = phase(
            "bf16", {}, engine_conf, LOGIT_DIFF_LIMIT,
            {"weights_in_float8": True, "reference_picks": True,
             **dict.fromkeys(picking, True)})
        result.update(sound_bf16_passes=sound["passes"],
                      bf16_controls_caught=caught, bf16_reported=reported)
        result["ok"] &= sound["passes"] and all(caught.values())
    if "float32" in args.phases:
        # three layers: an owner, a borrower, an owner — every control has
        # its layer — in float32 beside a small arena
        cut = {"num_hidden_layers": 3,
               "mlp_layer_types": ["dense", "sparse", "sparse"],
               "indexer_types": ["full", "shared", "full"]}
        pages = -(-(args.prompt + args.steps) // engine_conf["block_size"])
        with jax.default_matmul_precision("highest"):
            sound, caught, reported = phase(
                "float32", cut, dict(engine_conf, dtype="float32",
                                     max_sequences=2, use_pallas=False,
                                     num_blocks=2 * pages + 2),
                F32_LOGIT_DIFF_LIMIT, {"reference_picks": True,
                                       **dict.fromkeys(picking, True)})
        result.update(sound_float32_passes=sound["passes"],
                      float32_controls_caught=caught,
                      float32_reported=reported)
        result["ok"] &= sound["passes"] and all(caught.values())
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    result.update(ok=bool(result["ok"]), memory_peak_bytes=int(peak),
                  device={"platform": dev.platform,
                          "kind": dev.device_kind})
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
