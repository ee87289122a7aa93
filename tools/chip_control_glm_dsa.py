#!/usr/bin/env python3
"""The two readings the GLM-5.2 cell's margins lie between, THROUGH THE
HARNESS (after ``tools/chip_control_command_a.py``): ``benchmark/run.py`` on
the cell as it is, served by the sound program (``--control none``) or with
every weight matrix rounded to float8 (e4m3) in place after construction
(``--control weights``, the default); every other argument is ``run.py``'s.
The runner's own ``correct`` then judges it against the reference over the
SEED'S OWN weights, which are made again after the window (the served copy
and the arena are freed first; ``memory_peak_bytes`` of such a run means
nothing). A ``control`` line gives what each candidate
``UNDECIDED_ARGMAX_MARGIN`` would have judged among the tokens whose
routing is decided: {margin: [tokens, worst gap, exact share]} — the sound
program must stay under the runner's 0.25 at the module's margin with room
below it, the float8 control must end ``"correct": false`` with room above.
Readings: PERF.md section 6, PR 52.

    chiprun --chips 1 -- python3 tools/chip_control_glm_dsa.py --control weights \\
        --workload glm-5.2-l5-e16-serve-longdoc-closed16 --seed <n> --seconds 20
"""
import argparse
import gc
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402

from benchmark import run as bench_run                # noqa: E402
from benchmark.reference import glm_moe_dsa_decoder as ref  # noqa: E402
from deepspeed_tpu.inference import RaggedInferenceEngineTPU as Eng  # noqa: E402

_ap = argparse.ArgumentParser(add_help=False)
_ap.add_argument("--control", choices=("weights", "none"), default="weights")
_own, REST = _ap.parse_known_args()
MARGINS = (0.0, 0.2, 0.4, 0.6, 0.8, 1.0, 1.2, 1.6)
state = {}
_init = Eng.__init__


def init(self, model, config, params=None, rng=None):
    _init(self, model, config, params=params, rng=rng)
    if "eng" in state:          # the second, tiny engine: left as it is
        return
    state.update(eng=self, model=model, rng=rng)
    if _own.control == "none":
        return
    p = self.params
    groups = [p["embed"], p] + [g for lp in p["layers"] for g in lp.values()]
    n = 0
    for group in groups:
        for key in list(group):
            if getattr(group[key], "ndim", 0) >= 2:
                # two eager converts (one jitted pair would be dropped)
                narrow = group[key].astype(jnp.float8_e4m3fn)
                group[key] = narrow.astype(group[key].dtype)
                n += 1
    jax.block_until_ready(p)
    bench_run.emit({"phase": "control", "weights_in_float8": n})


Eng.__init__ = init


def argmax_gaps(widths, params, prompts, outs, device):
    eng = state["eng"]
    if _own.control != "none":      # the reference reads the SEED'S weights
        for leaf in jax.tree.leaves((eng.params, eng.arena)):
            leaf.delete()
        eng.params = eng.arena = None
        del params
        gc.collect()
        params = Eng(state["model"], dict(
            dtype="bfloat16", max_sequences=1, num_blocks=2, block_size=128,
            max_seq_len=256, max_batch_tokens=128, prefill_chunk=128),
            rng=state["rng"]).params
    seen = ref.teacher_forced(widths, params, prompts, outs, device)
    routed = seen["routing"] >= ref.UNDECIDED_LOGIT_MARGIN
    table = {}
    for m in MARGINS:
        judged = routed & (~seen["picks"].astype(bool) | (seen["lead"] >= m))
        if judged.any():
            table[str(m)] = [int(judged.sum()),
                             round(float(seen["gap"][judged].max()), 4),
                             round(float((seen["gap"][judged] == 0).mean()),
                                   4)]
    bench_run.emit({"phase": "control", "control": _own.control,
                    "routing_margin": ref.UNDECIDED_LOGIT_MARGIN,
                    "argmax_margin": ref.UNDECIDED_ARGMAX_MARGIN,
                    "tokens": int(len(routed)), "routed": int(routed.sum()),
                    "by_argmax_margin": table})
    judged = routed & (~seen["picks"].astype(bool) |
                       (seen["lead"] >= ref.UNDECIDED_ARGMAX_MARGIN))
    return seen["gap"][judged]


ref.argmax_gaps = argmax_gaps

if __name__ == "__main__":
    sys.exit(bench_run.main(REST))
