#!/usr/bin/env python3
"""Microbenchmark of the split step's history kernel ALONE, at the serving
cells' shapes: ``paged_attn_lse`` (``ops/paged_attention.py``) over a seeded
arena, page table and batch of rows — ms a call, us a PAGE (one page of one
row walked, all its KV heads) and us a PAGE TURN (a page of ONE KV head: a
loop turn of the kernel before PR 47, which fetched a page a head) by the
rows' live queries.

    chiprun --chips 1 -- python3 tools/bench_paged_hist.py \
        --parent-file .parent_tree/deepspeed_tpu/ops/paged_attention.py --sweep

``--parent-file``: another tree's ``paged_attention.py`` (``git archive
<commit> | tar -x -C .parent_tree``), measured beside this tree's in the
same process, same inputs; a module without ``qcounts`` is called without.
``--sweep``: this tree's kernel at other small tiles (``_paged_call(tile_q=)``)
beside the code's choice; with ``--groups`` at other KV heads a program
(``heads=``: 1 is the walk of before PR 47; one the kernel's VMEM refuses
is a line with ``error``). ``--rehearse``: tiny shapes in interpret mode on
the CPU, control flow only — no time it prints is a device's.

Shapes (PERF.md §4): cell 2 ``mistral7b-l12-serve-chat-closed64`` (64 rows
of chunk 128, 32 / 8 heads of 128, contexts 128–2,500), cell 4
``mimo-v2.5-l7-e16-serve-reason-closed64`` (64 query heads, K 192 padded to
256 lanes beside V 128; a window-128 layer of 8 KV heads, a full layer of
4; contexts to 768), cell 6 ``command-a-plus-l4-e16-serve-rag-closed16`` (16
rows, 128 / 8 heads of 128, window 4,096 and none, contexts 2.5K–10K), cell 7
``nemotron3-nano-l26-e16-serve-chat-closed64``'s three attention layers (32 /
2 heads of 128: 16 queries a KV head, both heads one program at one query a
row; cell 8's Granite layer has cell 2's 32 / 8).
Mixes: ``cell`` = the live queries of the cell's split step (cell 2: 61
decode rows of ONE live query + 3 rows of 128; cell 4: every row one; cell
6: 12 of one + 4 of 128), ``one`` = every row one, ``all`` = every row all
``c``, ``none`` = no row any (what the grid and the q / out / lse blocks
cost with no page walked). One JSON object a line; the lines also go to
``--out``.

``--groups`` (PR 40): what a GROUPED split step calls in place of the row
form's one ``[rows, chunk]`` call, at the same inputs — ``c1``: every row
as a row of ONE query (``[rows, 1]``, the rows of one live token live),
``chunk8``: the chunk group ``[8, chunk]`` (the cell's rows of a whole live
chunk, the rest riding along dead; cells 4 and 5, whose prompts are one
chunk: two fresh rows, no history) — beside ``rows``, the row form's call
at the cell's mix; also cell 5
``gigachat3.1-l5-e16-serve-reason-long-closed64``'s ``mla_decode`` (64
heads over a latent pool 640 lanes wide, contexts 128–4,200); since PR 47
cell 9 ``mistral7b-l12-serve-longprompt-closed8``'s call (8 rows, each a
whole live chunk over 0–3,712 tokens of its own history) and the decode
programs' reader (``paged_attn``, ``[64, 1]`` with the step's own key),
every kernel of ``--parent-file`` / ``--sweep`` on the same inputs.

``--decode`` (PR 61): a typed stack's DECODE read at the six typed cells'
shapes (``DECODE_SHAPES``: the rows of the cell's engine, its page table's
width, contexts of its mix) — ``[rows, 1]`` with ``counts = 1``, the row's
own key in the pool — through the kernel as the decode program calls it
(``paged_attn_decode``: the lse out, every KV head of a row a program, the
walk from the window's first page) beside ``paged_attention_xla``, which
gathers the table's whole width (a window kind: the window's pages) for
every row: ms a call of each, the pages the kernel walks and the pages
the gather copies, the largest difference of the two on out and lse.
``--layers N`` (8) reads follow one another in one executable, each one's
query a function of the read before it, and a call is the Nth part: at 1
each side carries what an executable's launch costs alone (0.1-0.15 ms)."""

import argparse
import importlib.util
import inspect
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import paged_attention as pa_here

BS = 128
#: name -> (rows, chunk, query heads, kv heads, K lanes a head, V lanes a
#: head, true head width (the scale's), pages a row, arena pages, window,
#: context range, rows that carry a whole live chunk in the cell's mix)
SHAPES = {
    "cell2": (64, 128, 32, 8, 128, 128, 128, 32, 512, None, (128, 2500), 3),
    "cell4_window": (64, 128, 64, 8, 256, 128, 192, 8, 512, 128,
                     (16, 768), 0),
    "cell4_full": (64, 128, 64, 4, 256, 128, 192, 8, 512, None,
                   (16, 768), 0),
    "cell6_window": (16, 128, 128, 8, 128, 128, 128, 86, 1376, 4096,
                     (2560, 10240), 4),
    "cell6_full": (16, 128, 128, 8, 128, 128, 128, 86, 1376, None,
                   (2560, 10240), 4),
    "cell9": (8, 128, 32, 8, 128, 128, 128, 32, 512, None, (0, 3712), 8),
    "cell7": (64, 128, 32, 2, 128, 128, 128, 32, 512, None, (128, 2500), 3),
}
#: a typed stack's decode read, ``SHAPES``' fields at chunk 1: cell 4
#: MiMo-V2.5 (contexts to 768 in a table of 8 pages), cell 6 Command A+ (16
#: rows, 86 pages a row, window 4,096), cell 7 Nemotron 3 Nano (2 KV heads),
#: cell 8 Granite 4.0-H Small, cell 10 Jamba2-3B (20 queries on ONE KV head,
#: 86 pages a row), cell 12 LFM2-24B-A2B (8 KV heads of 64, read in pairs)
DECODE_SHAPES = {
    "cell4_window": (64, 1, 64, 8, 256, 128, 192, 8, 512, 128, (16, 767), 0),
    "cell4_full": (64, 1, 64, 4, 256, 128, 192, 8, 512, None, (16, 767), 0),
    "cell6_window": (16, 1, 128, 8, 128, 128, 128, 86, 1376, 4096,
                     (2560, 10240), 0),
    "cell6_full": (16, 1, 128, 8, 128, 128, 128, 86, 1376, None,
                   (2560, 10240), 0),
    "cell7": (64, 1, 32, 2, 128, 128, 128, 32, 512, None, (128, 2500), 0),
    "cell8": (64, 1, 32, 8, 128, 128, 128, 8, 512, None, (16, 767), 0),
    "cell10": (64, 1, 20, 1, 128, 128, 128, 86, 5504, None, (2560, 10240),
               0),
    "cell12": (64, 1, 32, 8, 64, 64, 64, 32, 2048, None, (128, 2500), 0),
}
TINY_DECODE = {
    "tiny_decode": (4, 1, 8, 2, 128, 128, 128, 4, 16, None, (8, 60), 0),
    "tiny_decode_window": (4, 1, 8, 2, 128, 128, 128, 4, 16, 24, (8, 60), 0),
    "tiny_decode_pairs": (4, 1, 8, 4, 64, 64, 64, 4, 16, None, (8, 60), 0)}
TINY = {"tiny": (4, 16, 8, 2, 128, 128, 128, 4, 16, None, (8, 60), 1),
        "tiny_window": (4, 16, 8, 2, 128, 128, 128, 4, 16, 24, (8, 60), 1)}
SWEEP = (1, 2, 4, 8, 16, 32)
#: ``--groups --sweep``: (label, ``_paged_call`` arguments)
HEAD_SWEEP = tuple((f"heads{hp}", {"heads": hp}) for hp in (1, 2, 4, 8))


def load(path):
    spec = importlib.util.spec_from_file_location("pa_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(shape, mix, seed, block=BS):
    n, c, h, kvh, dk, dv, _, mb, pages, _, (lo, hi), chunk_rows = shape
    rng = np.random.default_rng(seed)
    live = {"cell": [c] * chunk_rows + [1] * (n - chunk_rows),
            "one": [1] * n, "all": [c] * n, "none": [0] * n}[mix]
    hi = min(hi, mb * block - c)
    starts = rng.integers(lo, hi + 1, n)
    # a row that feeds a whole chunk has fed whole chunks before it
    starts = np.where(np.array(live) == c, starts // c * c, starts)
    table = np.stack([rng.permutation(pages)[:mb] for _ in range(n)])
    key = jax.random.PRNGKey(seed % (2 ** 31))
    kq, kk, kv = jax.random.split(key, 3)
    bf = jnp.bfloat16
    return (jax.random.normal(kq, (n, c, h, dk), bf),
            jax.random.normal(kk, (pages + 1, block, kvh * dk), bf),
            jax.random.normal(kv, (pages + 1, block, kvh * dv), bf),
            jnp.asarray(table, jnp.int32), jnp.asarray(starts, jnp.int32),
            jnp.asarray(live, jnp.int32))


def pages(shape, starts, live=None, block=BS):
    """Pages one call walks: the visible pages of every row that holds a
    live query (``live`` [n], default all)."""
    window = shape[9]
    starts = np.asarray(starts)
    last = -(-starts // block)
    first = 0 if window is None else np.maximum(starts - (window - 1),
                                                0) // block
    walked = last - first
    return int(walked.sum() if live is None else
               walked[np.asarray(live) > 0].sum())


def timed(fn, args, reps, rounds):
    out = fn(*args)
    jax.block_until_ready(out)
    took = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t0) / reps)
    return statistics.median(took), out


def reader(mod, shape, interpret, **knobs):
    """The history call of ``mod`` at ``shape``; ``knobs``: the sweeps'
    arguments of ``_paged_call`` (``tile_q``, ``heads``)."""
    window, scale = shape[9], shape[6] ** -0.5
    if "qcounts" not in inspect.signature(
            mod.paged_attention_with_lse).parameters:
        return jax.jit(lambda q, ak, av, pt, st, qc:
                       mod.paged_attention_with_lse(
                           q, ak, av, pt, st, jnp.zeros_like(st),
                           interpret=interpret, window=window, scale=scale))
    return jax.jit(lambda q, ak, av, pt, st, qc: mod._paged_call(
        q, ak, av, pt, st, jnp.zeros_like(st), with_lse=True,
        interpret=interpret, window=window, scale=scale, qcounts=qc,
        **knobs))


#: the latent cell: (rows, chunk, heads, pool lanes, value lanes, pages a
#: row, arena pages, context range, softmax scale)
LATENT = {"cell5_latent": (64, 128, 64, 640, 512, 34, 2176, (128, 4200),
                           192 ** -0.5)}
TINY_LATENT = {"tiny_latent": (4, 16, 4, 128, 64, 4, 16, (8, 40), 0.1)}
GROUP_ROWS = 8


def group_calls(q, pt, st, qc, c):
    """(label, q, page table, starts, live queries) of the row form's call
    and of the two a grouped step makes in its place."""
    n = q.shape[0]
    wide = np.flatnonzero(np.asarray(qc) > 1)
    ids = np.zeros(min(GROUP_ROWS, n), np.int32)
    ids[:len(wide)] = wide
    live = jnp.asarray(np.arange(len(ids)) < len(wide))
    if not len(wide):       # a one-chunk prompt: fresh rows, no history
        g_st = jnp.zeros(len(ids), jnp.int32)
        g_qc = jnp.asarray([c, c] + [0] * (len(ids) - 2), jnp.int32)
    else:
        g_st, g_qc = st[ids], jnp.where(live, qc[ids], 0)
    return (("rows", q, pt, st, qc),
            ("c1", q[:, :1], pt, st, (qc == 1).astype(jnp.int32)),
            (f"chunk{len(ids)}", q[ids], pt[ids], g_st, g_qc))


def agree(out, lse, live, base):
    """The live queries' out and lse (an empty history's -1e30 clipped) →
    (that vector, its largest distance from ``base``: the first kernel
    measured on these inputs)."""
    got = np.concatenate(
        [np.asarray(out, np.float32)[live].ravel(),
         np.maximum(np.asarray(lse), -99.0)[live].ravel()])
    base = got if base is None else base
    return base, float(np.abs(got - base).max(initial=0))


def groups_section(a, say, shapes, mods, block):
    heads = {k: v for k, v in shapes.items() if not k.startswith("cell6")}
    for name, shape in heads.items():
        c = shape[1]
        mix = "cell" if shape[11] else "one"
        q, ak, av, pt, st, qc = inputs(shape, mix, a.seed, block)
        calls = group_calls(q, pt, st, qc, c)
        if shape[11] == shape[0]:       # every row a whole chunk: no groups
            calls = calls[:1]
        for label, gq, gpt, gst, gqc in calls:
            walked = pages(shape, gst, gqc, block)
            live = np.arange(gq.shape[1])[None] < np.asarray(gqc)[:, None]
            base = None
            for kernel, mod, knobs in mods:
                if knobs.get("heads", 1) > shape[3] or "tile_q" in knobs:
                    continue
                try:
                    sec, (out, lse) = timed(
                        reader(mod, shape, a.rehearse, **knobs),
                        (gq, ak, av, gpt, gst, gqc), a.reps, a.rounds)
                except Exception as e:              # noqa: BLE001
                    say(shape=name, mix=mix, call=label, kernel=kernel,
                        error=str(e)[:300])
                    continue
                base, diff = agree(out, lse, live, base)
                say(shape=name, mix=mix, call=label, kernel=kernel,
                    rows=list(gq.shape[:2]), ms_a_call=sec * 1e3,
                    pages=walked,
                    us_a_page=sec * 1e6 / walked if walked else None,
                    max_diff_live=diff)
    if not a.rehearse:
        decode_reader(a, say, mods)
    if a.shapes:
        return
    for name, (n, c, h, w, vl, mb, pages_, (lo, hi), scale) in \
            (TINY_LATENT if a.rehearse else LATENT).items():
        rng = np.random.default_rng(a.seed)
        st = jnp.asarray(rng.integers(lo, min(hi, mb * block - c) + 1, n),
                         jnp.int32)
        pt = jnp.asarray(np.stack([rng.permutation(pages_)[:mb]
                                   for _ in range(n)]), jnp.int32)
        kq, kp = jax.random.split(jax.random.PRNGKey(a.seed % (2 ** 31)))
        q = jax.random.normal(kq, (n, c, h, w), jnp.bfloat16)
        pool = jax.random.normal(kp, (pages_ + 1, block, w), jnp.bfloat16)
        fn = jax.jit(lambda q, pool, pt, st, qc: pa_here.mla_decode(
            q, pool, pt, st, jnp.zeros_like(st), qc, v_lanes=vl,
            scale=scale, interpret=a.rehearse))
        for label, gq, gpt, gst, gqc in group_calls(
                q, pt, st, jnp.ones_like(st), c):
            sec, _ = timed(fn, (gq, pool, gpt, gst, gqc), a.reps, a.rounds)
            say(shape=name, mix="one", call=label, rows=list(gq.shape[:2]),
                ms_a_call=sec * 1e3)


def decode_reader(a, say, mods):
    """The decode programs' reader: the same kernel at c = 1 without the
    lse, each row over its history AND the step's own key."""
    shape = SHAPES["cell2"]
    q, ak, av, pt, st, _ = inputs(shape, "one", a.seed)
    one = jnp.ones_like(st)
    walked = int((-(-(np.asarray(st) + 1) // BS)).sum())
    for label, mod, knobs in mods:
        if "tile_q" in knobs:
            continue
        fn = mod.paged_attention if not knobs else \
            (lambda *args, knobs=knobs: mod._paged_call(
                *args, with_lse=False, interpret=False, **knobs)[0])
        sec, _ = timed(jax.jit(fn), (q[:, :1], ak, av, pt, st, one), a.reps,
                       a.rounds)
        say(shape="cell2_decode_c1", kernel=label, ms_a_call=sec * 1e3,
            pages=walked, us_a_page=sec * 1e6 / walked,
            page_turns=walked * shape[3],
            us_a_turn=sec * 1e6 / walked / shape[3])


def decode_section(a, say, shapes, block):
    """``--decode``: the typed decode programs' read, kernel beside gather."""
    for name, shape in shapes.items():
        n, _, _, kvh, dk, dv, true, mb, _, window = shape[:10]
        q, ak, av, pt, st, one = inputs(shape, "one", a.seed, block)
        scale = true ** -0.5
        readers = {
            "kernel": lambda *args: pa_here.paged_attention_with_lse(
                *args, interpret=a.rehearse, window=window, scale=scale,
                qcounts=args[-1], name=pa_here.DECODE_KERNEL),
            "xla": lambda *args: pa_here.paged_attention_xla(
                *args, window=window, scale=scale, with_lse=True)}
        st_np = np.asarray(st)
        first_page = 0 if window is None else \
            np.maximum(st_np - (window - 1), 0) // block
        walked = int((-(-(st_np + 1) // block) - first_page).sum())
        gathered = n * (mb if window is None else
                        pa_here._span_pages(mb, window, block))
        line, base = {}, None
        for label, fn in readers.items():
            def layers(q, ak, av, pt, *rest, fn=fn):
                """``--layers`` reads in ONE executable, each one's query
                a function of the read before it and its pages its own
                (the table shifted: no two gathers alike), as a decode
                program's layers follow one another: the first's (out,
                lse), the last's."""
                first = None
                for l in range(a.layers):
                    last = fn(q, ak, av, (pt + 7 * l) % (ak.shape[0] - 1),
                              *rest)
                    first = first or last
                    q = q + (jnp.mean(last[0].astype(jnp.float32)) *
                             1e-3).astype(q.dtype)
                return first, last

            sec, ((out, lse), _) = timed(
                jax.jit(layers), (q, ak, av, pt, st, one), a.reps, a.rounds)
            base, line["max_diff"] = agree(out, lse, np.ones((n, 1), bool),
                                           base)
            line[f"ms_a_call_{label}"] = sec * 1e3 / a.layers
        say(shape=name, call="decode", layers=a.layers, rows=[n, 1],
            kv_heads=kvh, lanes=[dk, dv], table_pages=mb, window=window,
            pages_walked=walked, pages_gathered=gathered,
            mb_a_page=block * kvh * (dk + dv) * 2 / 1e6,
            us_a_page_kernel=line["ms_a_call_kernel"] * 1e3 / walked, **line)


def turns_section(a, say, shapes, mods, block):
    """PR 38's table: the row form's call by the rows' live queries, every
    kernel of ``mods`` on the same inputs, then the decode programs' reader."""
    for name, shape in shapes.items():
        c, h, kvh = shape[1:4]
        for mix in ("cell", "one", "all", "none"):
            if mix == "cell" and shape[11] in (0, shape[0]):
                continue                    # the cell's mix IS "one" / "all"
            args = inputs(shape, mix, a.seed, block)
            walked = 0 if mix == "none" else \
                pages(shape, np.asarray(args[4]), block=block)
            live = np.arange(c)[None] < np.asarray(args[5])[:, None]
            base = None
            for label, mod, knobs in mods:
                tile_q = knobs.get("tile_q")
                if tile_q and (c % tile_q or mix in ("all", "none")):
                    continue        # those mixes never take the small tile
                if "heads" in knobs:
                    continue                    # --groups' sweep
                try:
                    sec, (out, lse) = timed(
                        reader(mod, shape, a.rehearse, **knobs), args,
                        a.reps, a.rounds)
                except Exception as e:              # noqa: BLE001
                    say(shape=name, mix=mix, kernel=label,
                        error=str(e)[:300])
                    continue
                tile_q = tile_q or pa_here.tile_queries(c, h // kvh)
                base, diff = agree(out, lse, live, base)
                say(shape=name, mix=mix, kernel=label, ms_a_call=sec * 1e3,
                    pages=walked,
                    us_a_page=sec * 1e6 / walked if walked else None,
                    page_turns=walked * kvh,
                    us_a_turn=sec * 1e6 / walked / kvh if walked else None,
                    tile_rows=tile_q * (h // kvh), max_diff_live=diff)
    if not a.rehearse:
        decode_reader(a, say, mods)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent-file")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--groups", action="store_true")
    ap.add_argument("--decode", action="store_true")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=3800000011)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--shapes", default="")
    ap.add_argument("--out", default="chiprun_out/bench_paged_hist.jsonl")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        sys.exit("no TPU here: --rehearse runs the control flow on the CPU")
    if a.decode:
        shapes = TINY_DECODE if a.rehearse else DECODE_SHAPES
    else:
        shapes = TINY if a.rehearse else SHAPES
    if a.shapes:
        shapes = {k: shapes[k] for k in a.shapes.split(",")}
    block = 16 if a.rehearse else BS
    mods = [("change", pa_here, {})]
    if a.parent_file:
        mods.insert(0, ("parent", load(a.parent_file), {}))
    if a.sweep:
        mods += [(f"tile_q{t}", pa_here, {"tile_q": t}) for t in SWEEP]
        mods += [(label, pa_here, knobs) for label, knobs in HEAD_SWEEP]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    lines = []

    def say(**line):
        line["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        lines.append(line)
        print(json.dumps(line), flush=True)

    if a.decode:
        decode_section(a, say, shapes, block)
    elif a.groups:
        groups_section(a, say, shapes, mods, block)
    else:
        turns_section(a, say, shapes, mods, block)
    with open(a.out, "w") as f:
        f.write("".join(json.dumps(l) + "\n" for l in lines))


if __name__ == "__main__":
    main()
