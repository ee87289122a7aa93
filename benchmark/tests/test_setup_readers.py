"""The seven readers of set-up (PR 54): each is arithmetic on the program's
always-on counters, so each returns None on a registry without them (the
parent of the PR that added them) and the counters' value otherwise. No
device, no trace, no subprocess."""

import contextlib

import pytest

from benchmark import run as bench_run

#: reader -> the counters it reads, with a value for each
READERS = {
    "setup_import_s": {"setup/import_seconds": 0.5},
    "setup_engine_init_s": {"setup/params_seconds": 3.0,
                            "setup/arena_seconds": 0.25,
                            "setup/engine_seconds": 1.0,
                            "setup/frontend_seconds": 0.125},
    "setup_program_trace_s": {"compile/trace_seconds": 7.5},
    "setup_program_lower_s": {"compile/lower_seconds": 11.0},
    "setup_program_load_s": {"compile/load_seconds": 9.0},
    "setup_program_compile_s": {"compile/compile_seconds": 0.0},
    "setup_programs_built": {"compile/programs_built": 21},
}


@contextlib.contextmanager
def bare_registry():
    """The program's registry without a ``setup/`` or ``compile/`` counter,
    put back as it was (no fixture: ``tests/test_benchmark_yardstick.py``
    collects this file's test functions alone)."""
    from deepspeed_tpu.telemetry.registry import registry as reg
    held = {n: reg.get(n) for n in reg.names()
            if n.startswith(("setup/", "compile/"))}
    for name in held:
        reg.unregister(name)
    try:
        yield reg
    finally:
        for name in [n for n in reg.names()
                     if n.startswith(("setup/", "compile/"))]:
            reg.unregister(name)
        for name, metric in held.items():
            reg.register(name, metric)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_setup_reader_reads_its_counters_or_nothing(metric):
    mod = bench_run.load_reader(metric)
    assert mod.MOVES == "setup_s" and mod.LAYER in ("entry", "step programs")
    with bare_registry() as registry:
        assert mod.read(None) is None
        # the import's seconds are no part of any other reading
        registry.counter("setup/import_seconds").inc(100.0)
        for name, value in READERS[metric].items():
            registry.counter(name).inc(value)
        want = sum(READERS[metric].values())
        if metric == "setup_import_s":
            want += 100.0
        assert mod.read(None) == pytest.approx(want)


def test_every_setup_reader_is_in_every_cell():
    import json
    import os
    with open(os.path.join(bench_run.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = [w["name"] for w in bench["workloads"]]
    moved = {m["name"]: m for m in bench["per_layer"]
             if m["moves"] == "setup_s"}
    assert set(moved) == set(READERS)
    for m in moved.values():
        assert m["workloads"] == cells and m["source"] == "program_counter"
