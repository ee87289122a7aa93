"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
explicit rehearsal walks both phases at tiny widths and says ``cpu``."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
#: the rehearsal's compiles go where the environment says — one fixed place
#: beside the suite's own cache, not the entry points' ``.jax_cache``
CACHE = os.path.join(ROOT, ".jax_test_cache", "chip_smoke_rehearsal")


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = CACHE
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_refuses_without_a_tpu():
    """No chip, no option: non-zero exit and NOTHING on stdout — never a
    result line, never ``"platform": "tpu"``."""
    proc = _run()
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_rehearsal_reports_cpu():
    """``--rehearse-cpu``: both phases pass at tiny widths; the last line
    has the contract's shape and reports the platform it ran on."""
    proc = _run("--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert '"platform": "tpu"' not in proc.stdout
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    phases = {ln.get("phase"): ln for ln in lines[:-1]}
    assert {"start", "server", "server/serve", "server/programs",
            "trainer", "done"} <= set(phases)
    assert not [p for p in phases if "megastep" in str(p)]
    assert phases["server/serve"]["compiles_after_warmup"] == 0
    assert phases["server/serve"]["tokens"] == 109
    # (six requests on the default 64-sequence engine: their split batches
    # of one or two chunk rows are packed for the FULL-row program, whose
    # ladder holds them on a quarter of the 8-row form's slots)
    assert set(phases["server/programs"]["programs"]) == {
        "step n=8 c=1 decode", "step n=8 c=256 fresh",
        "step n=64 c=256 split"}
    tr = phases["trainer"]
    assert tr["compiles_after_warmup"] == 0
    assert tr["fused_step_retraces_after_warmup"] == 0
    assert tr["losses"][-1] < tr["losses"][0]
    # the cache went where the environment said, and nowhere else
    assert phases["start"]["compile_cache_from_env"] is True
    assert phases["start"]["compile_cache_dir"] == CACHE
