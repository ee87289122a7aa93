"""An architecture the harness has never seen comes as FILES: a
configuration, its published values, its plain reference, and entries in
``BENCHMARK.json`` — no edit to a file that is there.

The proof: copy ``benchmark/`` and ``BENCHMARK.json`` to a scratch
directory, ADD a Mixtral block (``tests/data/``: 4 experts, 2 a token, the
rehearsal's widths with head size 128 so that the flash kernel is the one
selected) and a cell of it on a committed training mix, and run the COPY's
``run.py --rehearse`` against the real program. The cell has to come out
``correct`` at the runner's own ``LOSS_ATOL`` — the trainer in bf16 as in
the committed cells: what bf16 does to the routing moved the first loss by
2.5e-5 to 2.5e-4 against the float32 reference over seven seeds at these
widths, six times under the limit — and the useful work
``mfu`` would read has to be the sparse count: 2 of 4 experts and the
router. The copy's own contract and FLOPs tests must pass for the new
family too. Nothing here is a cell: the repo's ``BENCHMARK.json`` is not
touched. Run by hand with the rest of ``benchmark/tests`` (about a minute)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import flops
from benchmark.runners import train as train_runner

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIG, CELL, MIX = "tiny-mixtral-train", "tiny-mixtral-train-seq4k", \
    "seq4k-batch4"
ADDED = {"tiny-mixtral-train.json": "configs/tiny-mixtral-train.json",
         "tiny-mixtral.published.json": "configs/published/tiny-mixtral.json",
         "sparse_moe_decoder.py": "reference/sparse_moe_decoder.py"}


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """A copy of the yardstick with the new architecture added to it."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src, dst in ADDED.items():
        dst = os.path.join(root, "benchmark", dst)
        assert not os.path.exists(dst)          # added, never overwritten
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copy(os.path.join(DATA, src), dst)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(DATA, "tiny-mixtral-train.json")) as fh:
        conf = json.load(fh)
    twin = next(w["name"] for w in bench["workloads"]
                if w["traffic"] == MIX and w["chips"] == 1)
    bench["configs"].append({
        "name": CONFIG, "source": conf["source"],
        "file": f"benchmark/configs/{CONFIG}.json",
        "reduced": conf["reduced"], "why": "a test's: sparse experts"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": "a test's: the router and the grouped matmul under the "
               "fused step"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if twin in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)
    return root


def in_scratch(root, *argv):
    """A child in the copy, the real checkout's program on its path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run([sys.executable, *argv], cwd=root, env=env,
                          capture_output=True, text=True, timeout=900)


def lines_of(proc) -> dict:
    out = {}
    for text in proc.stdout.strip().splitlines():
        line = json.loads(text)
        out[line.get("phase", "result")] = line
    return out


def hand_count() -> float:
    # one layer: q 256x256, k and v 256x128 each, o 256x256; the router
    # 256x4; TWO of the four experts, three matrices of 256x512 each
    layer = 2 * 256 * 256 + 2 * 256 * 128 + 256 * 4 + 2 * 3 * 256 * 512
    assert layer == 984_064
    params = 2 * layer + 256 * 512              # two layers, the head
    # attention at the rehearsal's sequence of 256, no window: QK^T and PV
    # over 256 x 257 / 2 pairs, 2 layers x 2 heads of 128, three times the
    # forward, a token
    attention = 3 * 2 * 4 * 2 * 128 * flops.causal_pairs(256, None) / 256
    return 6.0 * params + attention


@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_mixtral_cell_runs_and_is_correct_with_no_edit(scratch, trace):
    proc = in_scratch(scratch, os.path.join("benchmark", "run.py"),
                      "--workload", CELL, "--seed", "3000000019",
                      "--seconds", "2", "--trace", trace, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = lines_of(proc)
    assert said["start"]["reference"].endswith(".sparse_moe_decoder")
    assert said["warm"]["tolerance"] == train_runner.LOSS_ATOL
    assert said["warm"]["abs_gap"] <= train_runner.LOSS_ATOL
    assert said["checks"]["loss_matches_reference"] is True
    assert said["checks"]["revisit_matches_reference"] is True
    assert said["initialized"]["flops_per_token"] == hand_count()
    # ... which is NOT the dense count of the same widths (one GLU a layer)
    dense = hand_count() - 6.0 * 2 * (256 * 4 + 3 * 256 * 512)
    assert said["initialized"]["flops_per_token"] > dense
    last = said["result"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"      # never "tpu" here
    assert last["metrics"]


def test_the_copys_own_contract_and_flops_tests_hold_for_the_new_family(
        scratch):
    proc = in_scratch(
        scratch, "-m", "pytest", "-q", "-p", "no:cacheprovider",
        os.path.join("benchmark", "tests", "test_contract.py"),
        os.path.join("benchmark", "tests", "test_flops.py"))
    assert proc.returncode == 0, proc.stdout[-3000:]
