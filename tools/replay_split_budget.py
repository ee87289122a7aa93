#!/usr/bin/env python3
"""Replay a closed-loop serving mix on the CPU, a launch at a time, to see
what a step budget does to a 64-row split program's instances.

No engine and no device: the generator's own cycle of sizes
(``benchmark/lib/traffic.size_cycle``), ``TokenBudgetPolicy.select``'s rule
(one-token rows first, then the oldest prompts' chunks), and a launch priced
by the instance of ``engine_v2._instances`` it would take — ``(512, 4)``,
``(1024, 8)``, else the row form. One line a budget over all 32 starting
points of the cycle (what ``--seed`` picks): tokens/s, the spread between
starting points, launches a window and the share in the row form.

    python3 tools/replay_split_budget.py --mix rag-closed64 \
        --budgets 2048 1024 896 832 --ms 33 53 192

``--ms`` are milliseconds a launch at 512 slots, at 1,024 slots and in the
row form, from a traced chip run (``tools/host_path_probe.py``:
``split_by_rung``). PR 49: at 2,048 it read 385.9 tokens/s, 6.28% of spread
and 64.8% of launches in the row form for the chip's 385.2–386.1, 6.1–6.6%
and 64.0%; at 896 44.17 tokens a launch, the chip's number to the digit.
"""

import argparse
import json
import math
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.lib import traffic  # noqa: E402

LADDER = ((512, 4), (1024, 8))


def replay(mix, cycle, budget, roll, ms, chunk, window, ms_history=0.0):
    """One run: (tokens/s, launches, launches in the row form, requests
    ended) over the window that follows the mix's ramp. ``ms_history``:
    milliseconds a launch pays for every thousand tokens a chunk row holds
    already (a history read whose cost grows with the history: a chunk's
    queries walk it whole), a whole chunk's worth."""
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
    tokens = launches = row_form = ended = 0
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
        rung = next((i for i, (cap, rows) in enumerate(LADDER)
                     if fed <= cap and chunk_rows <= rows), len(LADDER))
        t += (ms[rung] + ms_history * sum(
            held[c] / 1e3 * k / chunk for c, k in picks if k > 1)) / 1e3
        inside = ramp < t <= ramp + window
        launches += inside
        row_form += inside and rung == len(LADDER)
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
    return tokens / window, launches, row_form, ended


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mix", default="rag-closed64")
    ap.add_argument("--budgets", type=int, nargs="+", default=[2048, 896])
    ap.add_argument("--ms", type=float, nargs=3, default=[33.0, 53.0, 192.0],
                    metavar=("AT_512", "AT_1024", "ROW_FORM"))
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
    for budget in args.budgets:
        runs = [replay(mix, cycle, budget, roll, args.ms, args.chunk,
                       args.window, args.ms_history)
                for roll in range(len(cycle))]
        rates = [r[0] for r in runs]
        q = statistics.quantiles(rates, n=4)
        med = statistics.median(rates)
        launches = sum(r[1] for r in runs)
        print(json.dumps({
            "budget": budget,
            "tokens_per_s": {"median": round(med, 2),
                             "min": round(min(rates), 2),
                             "max": round(max(rates), 2)},
            "spread_pct": round(100 * (q[2] - q[0]) / med, 3),
            "launches_a_window": round(launches / len(runs), 1),
            "tokens_a_launch": round(sum(rates) * args.window / launches, 3),
            "row_form_pct": round(100 * sum(r[2] for r in runs) / launches, 3),
            "row_form_launches_max": max(r[2] for r in runs),
            "requests_ended": round(sum(r[3] for r in runs) / len(runs), 1)}))


if __name__ == "__main__":
    main()
