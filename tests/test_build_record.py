"""The build record (PR 54): every build of a registered, named program as
one row of ``compile_monitor.summary()["programs"]`` from the events jax
sends (trace, lowering, backend, the persistent cache's answer), the seven
always-on ``compile/*`` build counters, construction by part
(``setup/<part>_seconds``) and the ``compile/build`` / ``setup/<part>``
spans. All on the CPU, every case a few tiny compiles."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import compile_monitor as monitor
from deepspeed_tpu.telemetry import registry, setup_part, tracer

PHASES = ("trace_s", "lower_s", "backend_s")
BUILD_COUNTERS = ("programs_built", "trace_seconds", "lower_seconds",
                  "load_seconds", "compile_seconds", "cache_hits",
                  "cache_misses")


def counter(name):
    metric = registry.get(name)
    return 0.0 if metric is None else metric.value


def build_counters():
    return {n: counter(f"compile/{n}") for n in BUILD_COUNTERS}


def rows():
    return monitor.summary()["programs"]


def named(name, body):
    """A jitted function of that name, registered as a step program is."""
    body.__name__ = body.__qualname__ = name
    jitted = jax.jit(body)
    monitor.register_program(name, jitted, (jnp.zeros(3),))
    return jitted


@pytest.fixture()
def record():
    """The process-wide monitor installed, its record emptied; what another
    test registered is gone with it (a program registers at its cache miss,
    and every test here makes its own)."""
    monitor.clear()
    monitor.install()
    yield monitor
    monitor.uninstall()
    monitor.clear()


@pytest.fixture()
def traced():
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    yield tracer
    tracer.configure(enabled=was)
    tracer.clear()


def test_two_named_programs_are_two_rows_and_the_counters_their_sums(record):
    before = build_counters()
    named("build_rec_a", lambda x: jnp.tanh(x) * 3)(jnp.zeros(3))
    named("build_rec_b", lambda x: jnp.cos(x) + 1)(jnp.zeros(3))
    got = rows()
    for name in ("build_rec_a", "build_rec_b"):
        row = got[name]
        assert row["builds"] == 1 and len(row["recent"]) == 1
        build = row["recent"][0]
        assert all(build[k] > 0 and build[k] == row[k] for k in PHASES)
        assert build["cache"] in ("hit", "miss", "off") and build["at"] > 0
    grew = {k: v - before[k] for k, v in build_counters().items()}
    both = [got["build_rec_a"], got["build_rec_b"]]
    assert grew["programs_built"] == 2
    assert grew["trace_seconds"] == pytest.approx(
        sum(r["trace_s"] for r in both))
    assert grew["lower_seconds"] == pytest.approx(
        sum(r["lower_s"] for r in both))
    assert grew["load_seconds"] + grew["compile_seconds"] == pytest.approx(
        sum(r["backend_s"] for r in both))
    assert grew["cache_hits"] + grew["cache_misses"] == sum(
        r["recent"][0]["cache"] != "off" for r in both)


@pytest.mark.parametrize("second, builds", [((3,), 1), ((5,), 2)],
                         ids=["same_shape_adds_nothing",
                              "new_shape_is_a_build_under_the_same_name"])
def test_a_second_call(record, second, builds):
    f = named("build_rec_again", lambda x: x * 2 + 1)
    f(jnp.zeros(3))
    built = counter("compile/programs_built")
    f(jnp.ones(second))
    row = rows()["build_rec_again"]
    assert row["builds"] == builds == len(row["recent"])
    assert counter("compile/programs_built") - built == builds - 1


def test_recent_keeps_the_newest_four_builds_and_the_row_every_one(record):
    f = named("build_rec_many", lambda x: x - 1)
    for n in range(1, 7):
        f(jnp.zeros(n))
    row = rows()["build_rec_many"]
    assert row["builds"] == 6 and len(row["recent"]) == 4
    ats = [b["at"] for b in row["recent"]]
    assert ats == sorted(ats)
    assert row["backend_s"] > sum(b["backend_s"] for b in row["recent"])


def test_an_inner_jit_adds_no_row_and_no_seconds_of_its_own(record):
    inner = jax.jit(lambda x: x * 2)
    inner.__wrapped__.__name__ = "build_rec_inner"
    f = named("build_rec_outer", lambda x: inner(x) + jnp.sin(x))
    before = build_counters()
    f(jnp.zeros(3))
    got = rows()
    assert "build_rec_inner" not in got
    assert got["build_rec_outer"]["builds"] == 1
    # the inner function's trace event is `other`'s, and the counters took
    # the outer program's three phases alone
    assert got["other"]["trace_s"] > 0
    grew = {k: v - before[k] for k, v in build_counters().items()}
    assert grew["programs_built"] == 1
    assert grew["trace_seconds"] == pytest.approx(
        got["build_rec_outer"]["trace_s"])


def test_an_unknown_name_is_other_and_in_no_counter(record):
    before = build_counters()
    jax.jit(lambda x: jnp.tanh(x) - 7)(jnp.zeros((3, 5)))
    got = rows()
    assert set(got) == {"other"}
    assert got["other"]["builds"] >= 1 and got["other"]["backend_s"] > 0
    assert build_counters() == before


def test_an_unknown_build_inside_a_part_is_that_parts_row(record):
    before = build_counters()
    with setup_part("unit_outer"):
        with setup_part("unit_built"):
            jax.jit(lambda x: jnp.tanh(x) + 13)(jnp.zeros((5, 3)))
        jax.jit(lambda x: jnp.tanh(x) + 17)(jnp.zeros((5, 3)))
    got = rows()
    assert "other" not in got
    for part in ("setup/unit_built", "setup/unit_outer"):
        assert got[part]["builds"] >= 1 and got[part]["backend_s"] > 0
        assert "recent" not in got[part]
    assert build_counters() == before


def test_the_persistent_cache_answers_the_second_build(record, tmp_path):
    """A first process-local build is a miss whose seconds are
    ``compile/compile_seconds``; after ``jax.clear_caches()`` the same
    program is read back: a hit, in ``compile/load_seconds``."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    for k, v in zip(keys, (str(tmp_path), 0.0, 0)):
        jax.config.update(k, v)
    try:
        f = named("build_rec_cached", lambda x: jnp.exp(x) * 0.5 + 11)
        before = build_counters()
        f(jnp.zeros(3))
        first = rows()["build_rec_cached"]["recent"][-1]
        jax.clear_caches()
        f(jnp.zeros(3))
        row = rows()["build_rec_cached"]
    finally:
        cc.reset_cache()
        for k, v in was.items():
            jax.config.update(k, v)
    if first["cache"] == "off" or not list(tmp_path.iterdir()):
        pytest.skip("this backend wrote no persistent-cache entry")
    grew = {k: v - before[k] for k, v in build_counters().items()}
    second = row["recent"][-1]
    assert (first["cache"], second["cache"]) == ("miss", "hit")
    assert row["builds"] == 2 and second["retrieval_s"] > 0
    assert grew["compile_seconds"] == pytest.approx(first["backend_s"])
    assert grew["load_seconds"] == pytest.approx(second["backend_s"])
    assert (grew["cache_misses"], grew["cache_hits"]) == (1, 1)


def test_the_scope_tables_compile_is_no_build_of_the_program(record):
    f = named("build_rec_tabled", lambda x: jnp.sqrt(x + 2))
    f(jnp.zeros(3))
    built = counter("compile/programs_built")
    assert monitor.scopes("build_rec_tabled")
    assert rows()["build_rec_tabled"]["builds"] == 1
    assert counter("compile/programs_built") == built


def test_a_build_span_contains_its_phase_spans(record, traced):
    named("build_rec_span", lambda x: jnp.log1p(x * x))(jnp.zeros(3))
    events = [e for e in traced.events() if e["ph"] == "X"]
    (build,) = [e for e in events if e["name"] == "compile/build"]
    assert build["args"]["program"] == "build_rec_span"
    assert build["args"]["cache"] in ("hit", "miss", "off")
    eps = 1e-3      # us: the spans share their clock readings
    for phase, key in (("jaxpr_trace_duration", "trace_ms"),
                       ("jaxpr_to_mlir_module_duration", "lower_ms"),
                       ("backend_compile_duration", "backend_ms")):
        inside = [e for e in events if e["name"] == f"compile/{phase}"
                  and e["ts"] >= build["ts"] - eps
                  and e["ts"] + e["dur"] <= build["ts"] + build["dur"] + eps]
        assert inside, phase
        assert max(e["dur"] for e in inside) == pytest.approx(
            1e3 * build["args"][key], rel=1e-6)


def test_setup_parts_do_not_overlap_and_their_spans_nest(traced):
    import time
    before = {p: counter(f"setup/{p}_seconds")
              for p in ("unit_outer", "unit_inner")}

    @setup_part("unit_outer")
    def construct():
        time.sleep(0.02)
        with setup_part("unit_inner"):
            time.sleep(0.03)
    construct()
    outer, inner = (counter(f"setup/{p}_seconds") - before[p]
                    for p in ("unit_outer", "unit_inner"))
    assert inner >= 0.03 and 0.02 <= outer < 0.03 + inner
    spans = {e["name"]: e for e in traced.events()
             if e["name"].startswith("setup/unit_")}
    o, i = spans["setup/unit_outer"], spans["setup/unit_inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert o["dur"] / 1e6 == pytest.approx(outer + inner, rel=1e-6)


def test_a_serving_engine_records_its_programs_and_parts_tracer_off():
    """No ``install()`` here: the engine's constructor does it."""
    from tests.test_scopes import _engine
    assert not tracer.enabled
    monitor.uninstall()
    before = {p: counter(f"setup/{p}_seconds")
              for p in ("params", "arena", "engine")}
    eng = _engine()
    try:
        first = eng._put_tokens([1, 2], [[1, 2, 3], list(range(1, 12))])
        eng._put_tokens([1], [[first[1]]])
        programs = sorted(fn.__name__ for fn in eng._step_fns.values())
        assert programs == ["serve_decode_r1", "serve_fresh_r2_c8",
                            "serve_split_r1_c8"]
        got = rows()
        for name in programs:
            assert got[name]["builds"] >= 1
            assert all(got[name][k] > 0 for k in PHASES)
        for part, was in before.items():
            assert counter(f"setup/{part}_seconds") > was, part
        assert counter("setup/import_seconds") > 0
    finally:
        monitor.uninstall()


def test_the_frontend_and_the_trainer_time_their_parts():
    from deepspeed_tpu.serving import ServingFrontend
    from tests.test_scopes import _engine
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import llama3_config
    parts = ("frontend", "mesh", "params", "optimizer_state", "engine")
    before = {p: counter(f"setup/{p}_seconds") for p in parts}
    fe = ServingFrontend(_engine(), enable_prefix_cache=False)
    fe.close()
    ds.build_mesh(data=1, devices=jax.devices()[:1])
    ds.initialize(
        model=llama3_config("tiny", max_seq_len=32, vocab_size=64),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}})
    telemetry.compile_monitor.uninstall()
    for part, was in before.items():
        assert counter(f"setup/{part}_seconds") > was, part
