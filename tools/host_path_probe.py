#!/usr/bin/env python3
"""One run of a serving cell THROUGH THE HARNESS, then the engine's two
always-on host-path counters over the measured window alone: every argument
is ``benchmark/run.py``'s, and the last line printed is one more,

    {"phase": "host_counters", "launches", "host_s", "fetch_wait_s",
     "host_ms_per_launch", "fetch_wait_ms_per_launch", "host_share",
     "launches_ahead", "ahead_share", "ahead_rows_dropped",
     "window": {<the WORK counters' growth over the window>}}

``dispatch/host_seconds`` and ``dispatch/fetch_wait_seconds``
(``engine_v2._fetch``) less what they read when the window opened, over the
window's ``dispatch/host_calls``; read when the runner ends the window
(its ``terminate_inflight``, which drops the rows still running and is no
part of it). Since the pump launches step n+1 before it fetches step n
(ISSUE 44) ``host_s`` is what the host did between two fetches WHILE the
device ran the next program, not time the device idled: ``ahead_share``
(``dispatch/launches_ahead`` over the launches, %) says for how many
launches that held — about 100 in a saturated closed loop, 0 on a tree
before the change — and ``ahead_rows_dropped`` counts the continued rows
whose token was thrown away (0 where every request ends by length). The harness has no reader of the two: it
prints per-layer metrics in traced runs only, and there the capture's own
start and stop stall the pump between two launches for seconds that
``dispatch/host_seconds`` takes for host time (PERF.md section 7). Run
with ``--trace 0`` this gives a replica's host share undisturbed; the
cost of the instrumentation is read from runs with the tracer on against
off at one seed (PERF.md section 6, PR 39). ``window`` holds what the
launches of the window alone counted (``dispatch/steps.split``,
``split_grouped_steps``, ``chunk_rows``, ``attn_row_slots``, ``tokens``,
``token_slots``): the share of split launches that took a grouped instance
and what attention worked on (PR 40; a tree without a counter reads 0).

    chiprun --chips 1 -- python3 tools/host_path_probe.py \
        --workload <cell> --seed <n> --trace <0|1>
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import run as bench_run                # noqa: E402

NAMES = ("dispatch/host_seconds", "dispatch/fetch_wait_seconds",
         "dispatch/host_calls", "dispatch/launches_ahead",
         "dispatch/ahead_rows_dropped")
WORK = ("steps.split", "split_grouped_steps", "chunk_rows",
        "attn_row_slots", "tokens", "token_slots")


def counters():
    from deepspeed_tpu.telemetry.registry import registry
    return [registry.counter(n).value
            for n in NAMES + tuple("dispatch/" + w for w in WORK)]


def main() -> int:
    at_open = []
    open_window = bench_run.Context.open_window

    def opened(self):
        at_open[:] = counters()
        return open_window(self)
    bench_run.Context.open_window = opened
    from deepspeed_tpu.serving import ServingFrontend
    at_close = []
    terminate = ServingFrontend.terminate_inflight

    def closed(self, *args, **kwargs):
        at_close[:] = at_close or counters()
        return terminate(self, *args, **kwargs)
    ServingFrontend.terminate_inflight = closed
    rc = bench_run.main()
    if at_open:
        host, wait, calls, ahead, dropped, *work = (
            b - a for a, b in zip(at_open, at_close or counters()))
        print(json.dumps({
            "phase": "host_counters", "launches": int(calls),
            "host_s": host, "fetch_wait_s": wait,
            "host_ms_per_launch": 1e3 * host / max(1, calls),
            "fetch_wait_ms_per_launch": 1e3 * wait / max(1, calls),
            "host_share": 100.0 * host / (host + wait)
            if host + wait else None,
            "launches_ahead": int(ahead),
            "ahead_share": 100.0 * ahead / max(1, calls),
            "ahead_rows_dropped": int(dropped),
            "window": dict(zip(WORK, work))}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
