#!/usr/bin/env python3
"""The two readings the Xing4.0 cell's routing margin lies between, THROUGH
THE HARNESS (after ``tools/chip_control_lfm2.py``): ``benchmark/run.py`` on
the cell as it is, served by the sound program (``--control none``) or with
every weight matrix rounded to float8 (e4m3) in place after construction
(``--control weights``, the default); every other argument is ``run.py``'s.
The runner's own ``correct`` then judges it against the reference over the
SEED'S OWN weights, which are made again after the window (the served copy
and the arena are freed first; ``memory_peak_bytes`` of such a run means
nothing). A ``control`` line gives what each candidate
``UNDECIDED_LOGIT_MARGIN`` of ``benchmark/reference/xing4_decoder.py`` would
have judged of the eight requests' generated tokens: {margin: [tokens, worst
gap, exact share]} — the sound program must read under the runner's 0.25 at
the module's margin with room and with enough tokens to be a check, the
float8 control must end ``"correct": false``. Readings: PERF.md section 6,
PR 58.

    chiprun --chips 1 -- python3 tools/chip_control_xing4.py --control none \\
        --workload xing4.0-29b-a4b-l6-serve-chat-closed64 --seed <n> --seconds 20
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
from benchmark.reference import xing4_decoder as ref  # noqa: E402
from deepspeed_tpu.inference import RaggedInferenceEngineTPU as Eng  # noqa: E402

_ap = argparse.ArgumentParser(add_help=False)
_ap.add_argument("--control", choices=("weights", "none"), default="weights")
_own, REST = _ap.parse_known_args()
MARGINS = (0.0, 0.01, 0.02, 0.04, 0.06, 0.08, 0.12, 0.16, 0.3)
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
    table = {}
    for m in MARGINS:
        gaps = seen["gap"][seen["margin"] >= m]
        if len(gaps):
            table[str(m)] = [int(len(gaps)), round(float(gaps.max()), 4),
                             round(float((gaps == 0).mean()), 4)]
    bench_run.emit({"phase": "control", "control": _own.control,
                    "routing_margin": ref.UNDECIDED_LOGIT_MARGIN,
                    "tokens": int(len(seen["gap"])),
                    "by_routing_margin": table})
    return seen["gap"][seen["margin"] >= ref.UNDECIDED_LOGIT_MARGIN]


ref.argmax_gaps = argmax_gaps

if __name__ == "__main__":
    sys.exit(bench_run.main(REST))
