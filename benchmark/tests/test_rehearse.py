"""``run.py --rehearse`` end to end, one training and one serving mix, and
the refusal without a TPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cells_by_kind():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(REPO, "benchmark", "traffic",
                               w["traffic"] + ".json")) as fh:
            kind = json.load(fh)["kind"]
        if w["chips"] == 1:
            out.setdefault(kind, w["name"])
    return bench, out


def run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("kind,seconds", [("train", "2"), ("serve", "6")])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracts_last_line(kind, seconds, trace):
    bench, cells = cells_by_kind()
    if kind not in cells:
        pytest.skip(f"no one-chip {kind} cell in BENCHMARK.json")
    proc = run("--workload", cells[kind], "--seed", "3000000019",
               "--seconds", seconds, "--trace", trace, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["device"]["platform"] == "cpu"      # never "tpu" here
    assert last["correct"] is True and last["failed"] == 0
    if trace == "0":
        want = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cells[kind] in m["workloads"]}
        assert set(last["metrics"]) == want
        assert all(v["value"] > 0 for v in last["metrics"].values())


def test_refuses_without_a_tpu():
    _bench, cells = cells_by_kind()
    proc = run("--workload", next(iter(cells.values())), "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2 and proc.stdout.strip() == ""


def test_unknown_cell_is_refused():
    proc = run("--workload", "no-such-cell", "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert proc.returncode == 2 and proc.stdout.strip() == ""
