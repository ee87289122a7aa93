#!/usr/bin/env python3
"""Replay a closed-loop serving mix on the CPU, a launch at a time, to see
what a step budget — and the engine's choice of split program — does to the
instances its launches take.

No device and no model: the generator's own cycle of sizes
(``benchmark/lib/traffic.size_cycle``), ``TokenBudgetPolicy.select``'s rule
(one-token rows first, then the oldest prompts' chunks), and a launch priced
by the slots of the program and instance the ENGINE's rules give it
(``engine_v2._pick_form`` on ints: the batch's own row bucket or, since PR
55, the full-row program where that one's ladder holds the batch on fewer
slots; ``--own-rows`` is the rule before it). One line a budget over all 32
starting points of the cycle (what ``--seed`` picks): tokens/s, the spread
between starting points, launches a window, the share at each ``r<program
rows>@<slots>``, the share lifted and the joint histogram of tokens x chunk
rows.

    python3 tools/replay_split_budget.py --mix rag-closed64 \
        --budgets 2048 1024 896 832 --ms 33 53 192
    python3 tools/replay_split_budget.py --mix longprompt-closed8 \
        --budgets 2048 --ms 29 44.5 90

``--ms`` are milliseconds a launch at up to 512 slots, up to 1,024 slots and
over them (the 64-row program's row form, a 16-row one's 2,048), from a
traced chip run (``tools/host_path_probe.py``: ``split_by_rung``). PR 49: at
2,048 it read 385.9 tokens/s, 6.28% of spread and 64.8% of launches in the
row form for the chip's 385.2–386.1, 6.1–6.6% and 64.0%; at 896 44.17
tokens a launch, the chip's number to the digit.
"""

import argparse
import json
import math
import os
import statistics
import sys
import types
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.lib import traffic  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import (  # noqa: E402
    RaggedInferenceEngineTPU, _bucket)


def engine_rules(max_sequences, max_batch_tokens, own_rows):
    """The engine's host rules — which split program a batch is packed for
    and the instance it takes there — over what they read of an engine: an
    engine never built, its ``config`` alone."""
    rules = object.__new__(RaggedInferenceEngineTPU)
    rules.config = types.SimpleNamespace(
        max_sequences=max_sequences, max_batch_tokens=max_batch_tokens)
    if own_rows:
        rules._pick_form = rules._launch_form
    return rules


def replay(mix, cycle, budget, roll, ms, rules, chunk, window,
           ms_history=0.0):
    """One run: (tokens/s, requests ended, Counter of the window's launches
    by (program rows, slots, lifted), Counter by (tokens in bins of 64,
    chunk rows)) over the window that follows the mix's ramp.
    ``ms_history``: milliseconds a launch pays for every thousand tokens a
    chunk row holds already (a history read whose cost grows with the
    history: a chunk's queries walk it whole), a whole chunk's worth."""
    sizes = np.roll(cycle, -roll, axis=0)
    clients = int(mix["arrival"]["clients"])
    ramp = float(mix["ramp_seconds"])
    drawn = 0
    prompt, out, arrived = [], [], []
    held = [0] * clients        # prompt tokens a client's row holds
    for c in range(clients):      # traffic.Arrivals._plan's first requests
        p, o = sizes[drawn % len(sizes)]
        drawn += 1
        prompt.append(min(int(p), traffic.RAMP_FIRST_PROMPT))
        out.append(max(1, math.ceil(int(o) * (c + 0.5) / clients)))
        arrived.append(c)
    t, rr = 0.0, 0
    tokens = ended = 0
    forms, hist = Counter(), Counter()
    while t < ramp + window:
        by_age = sorted(range(clients), key=arrived.__getitem__)
        decodes = [c for c in by_age if prompt[c] == 0]
        if decodes:
            off = rr % len(decodes)
            decodes = decodes[off:] + decodes[:off]
        left, picks = budget, []
        for c in decodes:
            if left < 1:
                rr += len(picks)
                break
            picks.append((c, 1))
            left -= 1
        for c in by_age:
            if left < 1:
                break
            if prompt[c]:
                take = min(prompt[c], chunk, left)
                picks.append((c, take))
                left -= take
        fed = sum(k for _, k in picks)
        chunk_rows = sum(k > 1 for _, k in picks)
        form = rules._pick_form(_bucket(len(picks)), chunk, "split", fed,
                                chunk_rows)
        t += (ms[(form.slots > 512) + (form.slots > 1024)] + ms_history * sum(
            held[c] / 1e3 * k / chunk for c, k in picks if k > 1)) / 1e3
        inside = ramp < t <= ramp + window
        if inside:
            forms[form.nb, form.slots, form.nb > _bucket(len(picks))] += 1
            hist[fed // 64 * 64, chunk_rows] += 1
        for c, k in picks:
            if prompt[c]:
                prompt[c] -= k
                held[c] += k
                if prompt[c]:
                    continue      # more of the prompt to come: no token
            out[c] -= 1
            tokens += inside
            if out[c] <= 0:
                p, o = sizes[drawn % len(sizes)]
                prompt[c], out[c], arrived[c] = int(p), int(o), clients + drawn
                held[c] = 0
                drawn += 1
                ended += inside
    return tokens / window, ended, forms, hist


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mix", default="rag-closed64")
    ap.add_argument("--budgets", type=int, nargs="+", default=[2048, 896])
    ap.add_argument("--ms", type=float, nargs=3, default=[33.0, 53.0, 192.0],
                    metavar=("TO_512", "TO_1024", "OVER"))
    ap.add_argument("--max-sequences", type=int, default=64)
    ap.add_argument("--max-batch-tokens", type=int, default=2048)
    ap.add_argument("--own-rows", action="store_true",
                    help="every batch in its own row bucket's program")
    ap.add_argument("--ms-history", type=float, default=0.0,
                    metavar="PER_KTOKEN", help="ms a launch pays for every "
                    "thousand tokens a chunk row already holds (PR 52: "
                    "0.71 for GLM-5.2's masked history walk)")
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--window", type=float, default=45.0)
    args = ap.parse_args()
    mix = traffic.load_mix(args.mix)
    if mix["arrival"]["process"] != "closed":
        raise SystemExit("a closed-loop mix is what this replays")
    cycle = traffic.size_cycle(mix)
    rules = engine_rules(args.max_sequences, args.max_batch_tokens,
                         args.own_rows)
    for budget in args.budgets:
        runs = [replay(mix, cycle, budget, roll, args.ms, rules, args.chunk,
                       args.window, args.ms_history)
                for roll in range(len(cycle))]
        rates = [r[0] for r in runs]
        q = statistics.quantiles(rates, n=4)
        med = statistics.median(rates)
        forms = sum((r[2] for r in runs), Counter())
        hist = sum((r[3] for r in runs), Counter())
        launches = sum(forms.values())
        full = _bucket(args.max_sequences)
        # the program's top instance: every one of its rows a chunk row
        row_form = [sum(n for (nb, slots, _), n in r[2].items()
                        if nb == full and slots > 1024) for r in runs]

        def pct(n):
            return round(100 * n / launches, 3)
        print(json.dumps({
            "budget": budget,
            "tokens_per_s": {"median": round(med, 2),
                             "min": round(min(rates), 2),
                             "max": round(max(rates), 2)},
            "spread_pct": round(100 * (q[2] - q[0]) / med, 3),
            "launches_a_window": round(launches / len(runs), 1),
            "tokens_a_launch": round(sum(rates) * args.window / launches, 3),
            "row_form_pct": pct(sum(row_form)),
            "row_form_launches_max": max(row_form),
            "requests_ended": round(sum(r[1] for r in runs) / len(runs), 1),
            "lifted_pct": pct(sum(n for (_, _, lifted), n in forms.items()
                                  if lifted)),
            "by_program_pct": {f"r{nb}@{slots}": pct(n) for (nb, slots, _), n
                               in sorted(forms.items())},
            "hist_pct": {f"{t}x{rows}": pct(n)
                         for (t, rows), n in sorted(hist.items())}}))


if __name__ == "__main__":
    main()
