#!/usr/bin/env python3
"""A lower-precision control of a typed stack's serving cell (written for
the Command A+ cell; ``--workload`` names the cell) THROUGH THE HARNESS:
``benchmark/run.py`` on the cell as it is, but the engine serves with every
weight matrix rounded to float8 (e4m3) in place after construction
(``--control weights``, the default) or with K and V rounded on their way
to the cache (``--control kv``); every other argument is ``run.py``'s. The
runner's own ``correct``
(``argmax_gaps`` at the reference's ``UNDECIDED_LOGIT_MARGIN``,
``NEAR_TIE_LOGITS``, ``MIN_EXACT_ARGMAX``) then judges it against the
reference over the SEED'S OWN weights, which are made again after the
window (the served copy and the arena are freed first: two copies of 9.5 GB
do not fit; ``memory_peak_bytes`` of such a run means nothing). A
``control`` line gives what each candidate margin would have judged:
{margin: [tokens, worst gap, exact share]}. The weights control must end
``"correct": false`` by ``tokens_within_near_tie`` with every other check
true; the cache control is NOT caught by this comparison
(``tools/chip_check_command_a.py``'s limit on the logits holds it).
Readings: PERF.md section 6, PR 37.

    chiprun --chips 1 -- python3 tools/chip_control_command_a.py --control weights \
        --workload command-a-plus-l4-e16-serve-rag-closed16 --seed <n> --seconds 20
"""
import argparse
import gc
import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                            # noqa: E402
import numpy as np                                    # noqa: E402
import jax.numpy as jnp                               # noqa: E402

from benchmark import run as bench_run                # noqa: E402
from deepspeed_tpu.inference import RaggedInferenceEngineTPU as Eng  # noqa: E402

_ap = argparse.ArgumentParser(add_help=False)
_ap.add_argument("--control", choices=("weights", "kv"), default="weights")
_own, REST = _ap.parse_known_args()
CONTROL = _own.control
state = {}
_init = Eng.__init__


def float8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def init(self, model, config, params=None, rng=None):
    _init(self, model, config, params=params, rng=rng)
    if "eng" in state:          # the second, tiny engine: left as it is
        return
    state.update(eng=self, model=model, rng=rng)
    if CONTROL == "kv":         # K and V rounded on their way to the cache
        from deepspeed_tpu.ops import paged_attention as pa
        write_kv = pa.write_kv
        pa.write_kv = lambda ak, av, k, v, *a, **kw: write_kv(
            ak, av, float8(k), float8(v), *a, **kw)
        bench_run.emit({"phase": "control", "kv_in_float8": True})
        return
    p = self.params
    groups = [p["embed"]] + [g for lp in p["layers"] for g in lp.values()]
    n = 0
    for group in groups:
        for key in list(group):
            if getattr(group[key], "ndim", 0) >= 2:
                group[key] = float8(group[key])
                n += 1
    jax.block_until_ready(p)
    bench_run.emit({"phase": "control", "weights_in_float8": n})


Eng.__init__ = init

def _reference_of(rest):
    """The reference module of the cell named after ``--workload`` (the
    tool was written for Command A+'s cell and serves any typed stack's
    whose reference has ``hidden_and_margins``)."""
    import json
    from benchmark.lib import model as model_lib
    cell = rest[rest.index("--workload") + 1]
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        cells = {w["name"]: w for w in json.load(fh)["workloads"]}
    return model_lib.load_reference(
        model_lib.load_config(cells[cell]["config"]))


ref = _reference_of(REST)
_gaps = ref.argmax_gaps
_walk = ref.hidden_and_margins
seen = []


def hidden_and_margins(w, params, rows, device):
    xs, margins = _walk(w, params, rows, device)
    seen.append((np.asarray(xs[0]), np.asarray(margins[0])))
    return xs, margins


ref.hidden_and_margins = hidden_and_margins


def by_margin(w, params, prompts, outs, device):
    """What each candidate margin would have judged, from the walk the
    harness's own call made: {margin: [tokens, worst gap, exact share]}."""
    scale = ref.dense._f32(params["final_norm"]["scale"], device)
    embed = ref.dense._f32(params["embed"]["tokens"], device)
    gaps, margins = [], []
    for (x, margin), p, o in zip(seen, prompts, outs):
        at = np.arange(len(p) - 1, len(p) - 1 + len(o))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(ref._head(jnp.asarray(x[at]), scale, embed,
                                          w.eps))
        gaps.append(logits.max(-1) - logits[np.arange(len(o)), np.asarray(o)])
        margins.append(margin[at])
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    return {str(m): [int((margins >= m).sum()),
                     round(float(gaps[margins >= m].max(initial=0.0)), 4),
                     round(float((gaps[margins >= m] == 0).mean()), 4)]
            for m in (0.0, 0.01, 0.02, 0.04, 0.08, 0.16)
            if (margins >= m).any()}


def argmax_gaps(widths, params, prompts, outs, device):
    eng = state["eng"]
    for leaf in jax.tree.leaves((eng.params, eng.arena)):
        leaf.delete()
    eng.params = eng.arena = None
    del params
    gc.collect()
    tiny = Eng(state["model"], dict(
        dtype="bfloat16", max_sequences=1, num_blocks=2, block_size=128,
        max_seq_len=256, max_batch_tokens=128, prefill_chunk=128),
        rng=state["rng"])
    bench_run.emit({"phase": "control", "reference_over": "the seed's own "
                    "weights, made again"})
    flat = _gaps(widths, tiny.params, prompts, outs, device)
    bench_run.emit({"phase": "control", "margin": ref.UNDECIDED_LOGIT_MARGIN,
                    # (another reference's head is its own: no table)
                    "by_margin": by_margin(widths, tiny.params, prompts,
                                           outs, device)
                    if hasattr(ref, "_head") else None})
    return flat


ref.argmax_gaps = argmax_gaps

if __name__ == "__main__":
    sys.exit(bench_run.main(REST))
