#!/usr/bin/env python3
"""Microbenchmark of the three resident flash kernels ALONE
(``ops/flash_attention.py``: ``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``) at the training cells' call and its neighbours: the
device's ms a call of each kernel from a profile of three calls, us a LIVE
TILE (one ``[block_q, block_k]`` score tile of one head), and what the call
spends outside the kernels (``delta``, the float32 per-query-head ``dk`` /
``dv`` and their sum over the GQA group, casts).

    chiprun --chips 1 -- python3 tools/bench_flash_tiles.py \
        --parent-file .parent_tree/deepspeed_tpu/ops/flash_attention.py

Forms, same inputs, one process: ``classed`` = this tree's kernels (an
interior tile runs no mask), ``masked`` = this tree's with every live tile
sent through the masked form (`_tile_ranges` patched to hold no interior
range: one masked loop, the kernels of before PR 59 from this tree's
source), ``parent`` = ``--parent-file``'s module (``git archive <commit> |
tar -x -C .parent_tree``). From the first
two: an EDGE tile costs ``masked ÷ live tiles``, an INTERIOR tile
``(classed − edge tiles × that) ÷ interior tiles``. ``--rehearse``: tiny
shapes in interpret mode on the CPU, control flow only — no time it prints
is a device's. One JSON object a line; the lines also go to ``--out``."""

import argparse
import glob
import importlib.util
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: name -> (batch, sequence, heads, kv heads, head dim, window, grad)
CALLS = {
    # cells 1 and 3 (Mistral 7B: 32 / 8 heads of 128, window 4,096 = the
    # sequence; 4 sequences a step and chip in cell 1, 2 in cell 3)
    "cell1_seq4k_window4k": (4, 4096, 32, 8, 128, 4096, True),
    "seq4k_causal": (4, 4096, 32, 8, 128, None, True),
    "seq4k_window1k": (4, 4096, 32, 8, 128, 1024, True),
    "seq8k_window4k": (2, 8192, 32, 8, 128, 4096, True),
    # the 1b preset's training call (chip_smoke.py)
    "llama1b_seq2k": (8, 2048, 16, 8, 128, None, True),
    # a split step's own-chunk attention: one edge tile a head
    "own_chunk_128": (64, 128, 32, 8, 128, None, False),
}
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def load(path):
    spec = importlib.util.spec_from_file_location("flash_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent-file", default=None)
    ap.add_argument("--calls", default=",".join(CALLS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/bench_flash_tiles.jsonl")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.trace import reduce
    from deepspeed_tpu.ops import flash_attention as this
    if not a.rehearse and jax.default_backend() != "tpu":
        print("no TPU: --rehearse runs the control flow on the CPU",
              file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    out = open(a.out, "a")

    def say(**line):
        line["device"] = jax.devices()[0].device_kind
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")

    def device_ms(fn, *args):
        """{kernel or 'other': device ms a call} of three traced calls on
        device 0; {} where the trace holds no device (the CPU)."""
        jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(3):
                jax.block_until_ready(fn(*args))
            jax.profiler.stop_trace()
            found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                           "*.xplane.pb"))
            trace = reduce.load(found[-1]) if found else {"planes": []}
        for i, plane in reduce.device_planes(trace):
            if i != 0:
                continue
            ms = {}
            for ev, self_ns in reduce.self_times(
                    reduce.line_events(plane, reduce.OPS_LINE)):
                hit = re.search(r"flash_(fwd|bwd_dq|bwd_dkv)",
                                reduce.op_name(ev))
                key = "flash_" + hit.group(1) if hit else "other"
                ms[key] = ms.get(key, 0.0) + self_ns / 3e6
            return ms
        return {}

    parent = load(a.parent_file) if a.parent_file else None
    ranges = this._tile_ranges

    def no_interior(*geometry):
        lo, _, _, hi = ranges(*geometry)
        return lo, hi, hi, hi

    for name in a.calls.split(","):
        b, t, h, kvh, d, window, grad = CALLS[name]
        if a.rehearse:
            b, t, h, kvh, d = 1, 512, 4, 2, 64
            window = window and min(window, t)
        rng = np.random.default_rng(59)
        q, k, v = (jnp.asarray(rng.normal(size=(b, t, n, d)) * 0.5,
                               jnp.bfloat16) for n in (h, kvh, kvh))
        block = min(this.DEFAULT_BLOCK_Q, t) if not a.rehearse else 128
        interior, edge, skipped = this.tile_classes(t, t, block, block, True,
                                                    window)
        heads = b * h
        results = {}
        forms = [("classed", this, ranges), ("masked", this, no_interior)] + \
            ([("parent", parent, ranges)] if parent else [])
        for form, mod, tile_ranges in forms:
            this._tile_ranges = tile_ranges

            def loss(q, k, v, mod=mod):
                return jnp.sum(mod.flash_attention(
                    q, k, v, causal=True, window=window, block_q=block,
                    block_k=block,
                    interpret=a.rehearse).astype(jnp.float32))
            fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)) if grad else loss)
            ms = device_ms(fn, q, k, v)
            results[form] = jax.tree.map(np.asarray, fn(q, k, v))
            this._tile_ranges = ranges
            say(call=name, form=form, heads=heads, tiles_interior=interior,
                tiles_edge=edge, tiles_skipped=skipped, ms_a_call=ms,
                us_a_live_tile={
                    kern: 1e3 * ms[kern] / (heads * (interior + edge))
                    for kern in KERNELS if kern in ms})
        # the classed form's bits are the masked form's (and the parent's)
        for form in results:
            same = all(np.array_equal(x, y) for x, y in zip(
                jax.tree.leaves(results[form]),
                jax.tree.leaves(results["classed"])))
            say(call=name, form=form, bits_equal_classed=bool(same))
    return 0


if __name__ == "__main__":
    sys.exit(main())
