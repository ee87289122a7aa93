"""The chip's time under the program's own names (PR 28): the scope parser
on a checked-in excerpt of optimized TPU HLO, the step programs' names and
their registration for the scope table, the host span tree of a traced
train step and a traced serving step with the work each launch did, and the
always-on ``dispatch/*`` work counters. All on the CPU (conftest forces it);
the compiles for a described v5e are in test_tpu_compile.py."""

import importlib
import os

import numpy as np
import pytest
import jax

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry.explain import (SCOPE_VOCABULARY,
                                             scope_of_op_name,
                                             scope_table_from_hlo)

# ``telemetry.compile_monitor`` is the instance; this is its module
monitor_module = importlib.import_module(
    "deepspeed_tpu.telemetry.compile_monitor")
HERE = os.path.dirname(os.path.abspath(__file__))
ENG_CFG = {"dtype": "float32", "num_blocks": 32, "block_size": 8,
           "max_seq_len": 128, "prefill_chunk": 8, "max_batch_tokens": 64,
           "max_sequences": 16}
WORK_COUNTERS = ("dispatch/tokens", "dispatch/token_slots",
                 "dispatch/context_tokens", "dispatch/context_slots",
                 "dispatch/host_calls")


def _engine(**over):
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.parallel.mesh import build_mesh
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return RaggedInferenceEngineTPU(cfg, {**ENG_CFG, **over}, params=params)


@pytest.fixture()
def traced():
    """The process-wide tracer on and empty, put back as it was."""
    tr = telemetry.tracer
    was = tr.enabled
    tr.configure(enabled=True)
    tr.clear()
    yield tr
    tr.configure(enabled=was)
    tr.clear()


def _spans(events, name):
    return sorted((e for e in events
                   if e["name"] == name and e["ph"] == "X"),
                  key=lambda e: e["ts"])


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3


def _counters():
    return {n: telemetry.registry.counter(n).value for n in WORK_COUNTERS}


# -- the parser ---------------------------------------------------------------

#: instruction of tests/data/v5e_hlo_excerpt.txt -> (scope, backward, remat);
#: INHERITED names those whose scope is not a word of their own ``op_name``
EXCERPT = {
    # forward, under a scope of its own inside the layer loop
    "convolution.116": ("mlp", False, False),
    # a Pallas kernel: the custom-call instruction under attn_core
    "flash_fwd.6": ("attn_core", False, False),
    # the forward computed AGAIN for the backward pass
    "convolution.119": ("mlp", True, True),
    "fusion.449": ("mlp", True, True),
    # backward proper: transpose(jvp()) with no rematted_computation
    "convolution.122": ("mlp", True, False),
    # a scope entered directly under the transform: jvp(loss), and its
    # backward transpose(jvp(loss))
    "convolution.33.clone.3": ("loss", False, False),
    "fusion.31.clone.3": ("loss", True, False),
    "fusion.294": ("optimizer", False, False),
    # a kCustom fusion whose root lost its metadata: the scope of the
    # computation it calls
    "fusion.195": ("kv_write", False, False),
    # a relayout fusion with no metadata at all: its user's scope
    "fusion.169": ("attn_history", False, False),
    # nothing to go by: no vocabulary word, no callee, no scoped user
    "custom-call.24": (None, False, False),
    "while.13": (None, False, False),
}


INHERITED = {"fusion.195", "fusion.169"}


@pytest.fixture(scope="module")
def excerpt_table():
    with open(os.path.join(HERE, "data", "v5e_hlo_excerpt.txt")) as fh:
        return scope_table_from_hlo(fh.read())


@pytest.mark.parametrize("instruction", sorted(EXCERPT))
def test_scope_table_on_tpu_hlo_excerpt(instruction, excerpt_table):
    scope, backward, remat = EXCERPT[instruction]
    assert excerpt_table[instruction] == {
        "scope": scope, "backward": backward, "remat": remat,
        "inherited": instruction in INHERITED}


def test_scope_is_the_innermost_vocabulary_word():
    path = "jit(serve_split)/while/body/closed_call/moe/mlp/dot_general"
    assert scope_of_op_name(path)["scope"] == "mlp"
    assert scope_of_op_name("jit(f)/embed/norm/mul")["scope"] == "norm"
    # an einsum's letters and a kernel's name are not the vocabulary
    assert scope_of_op_name(
        "jit(f)/attn_history/td,sd->ts/dot_general")["scope"] == \
        "attn_history"
    assert scope_of_op_name("jit(f)/flash_fwd/pallas_call")["scope"] is None
    assert scope_of_op_name("")["scope"] is None
    assert len(set(SCOPE_VOCABULARY)) == len(SCOPE_VOCABULARY)
    # a latent layer's words, inside and beside the older ones
    assert scope_of_op_name(
        "jit(f)/attn_latent/bthd,lhd->bthl/dot_general")["scope"] == \
        "attn_latent"
    assert scope_of_op_name("jit(f)/moe_shared/sd,dh->sh/dot_general")[
        "scope"] == "moe_shared"
    # a state-space layer's six, the pools' gather inside the loop's body
    assert scope_of_op_name(
        "jit(f)/while/body/ssm_state/gather")["scope"] == "ssm_state"
    assert scope_of_op_name(
        "jit(f)/ssm_scan/mtsgh,msghp->mtghp/dot_general")["scope"] == \
        "ssm_scan"
    # a gated delta rule's two forms, and an attention layer's output gate
    assert scope_of_op_name(
        "jit(f)/delta_rule/mgrts,msgrp->mtgrp/dot_general")["scope"] == \
        "delta_rule"
    assert scope_of_op_name("jit(f)/attn_gate/logistic")["scope"] == \
        "attn_gate"


# -- names and registration -----------------------------------------------------

#: kind -> (_step_fn arguments after the row bucket, the program's name)
STEP_PROGRAMS = {
    "decode": ((1, ("argmax",), False), "serve_decode_r4"),
    "fresh": ((8, ("argmax",), "fresh"), "serve_fresh_r4_c8"),
    "split": ((8, ("argmax",), "split"), "serve_split_r4_c8"),
    # the single paged read: no batch of the engine's selects it, it is
    # the reference of tests/test_paged.py and keeps its name
    "paged": ((8, ("argmax",), False), "serve_paged_r4_c8"),
    "sample_top_k": ((1, ("sample", 5, False), False),
                     "serve_decode_r4_sample_k5"),
    "logits": ((1, None, False), "serve_decode_r4_logits"),
    "sample": ((1, ("sample", 5, True), False),
               "serve_decode_r4_sample_k5_p"),
}


@pytest.mark.parametrize("kind", sorted(STEP_PROGRAMS))
def test_step_program_registers_under_its_own_name(kind, monkeypatch):
    """Every kind of step program is jitted under a stable name of its kind
    and static shape (the module's name in a device trace) and registers
    for the scope table at its cache miss; nothing is lowered until
    ``scopes()`` is asked, and then once."""
    (cb, mode, fresh), name = STEP_PROGRAMS[kind]
    compiles = []
    real = monitor_module._compile_with_current_metadata
    monkeypatch.setattr(
        monitor_module, "_compile_with_current_metadata",
        lambda jitted, args: compiles.append(1) or real(jitted, args))
    eng = _engine()
    jitted = eng._step_fn(4, cb, mode, fresh)
    assert jitted.__name__ == name
    assert name in telemetry.compile_monitor.programs()
    assert eng._step_fn(4, cb, mode, fresh) is jitted
    assert not compiles
    _ref, args = telemetry.compile_monitor._programs[name]
    table = telemetry.compile_monitor.scopes(name)
    assert len(compiles) == 1
    assert telemetry.compile_monitor.scopes(name) is table
    assert len(compiles) == 1
    found = {e["scope"] for e in table.values()} - {None}
    assert found <= set(SCOPE_VOCABULARY)
    want = {"mlp", "attn_qkv", "norm", "kv_write", "lm_head"}
    if mode is not None:
        want.add("sample")
    if kind == "split":
        want |= {"attn_history", "attn_merge"}
    assert want <= found, want - found
    # the module carries the name too
    assert f"jit_{name}" in jitted.lower(*args).as_text()[:200]


def test_the_engine_chooses_no_path_by_the_environment():
    """Which program a batch runs follows from the batch and the config:
    the ragged engine reads no environment variable."""
    import inspect
    import re
    from deepspeed_tpu.inference import engine_v2
    source = inspect.getsource(engine_v2)
    assert not re.search(r"\benviron\b|\bgetenv\b|^\s*import os\b|DSTPU_",
                         source, re.M)
    # ... and the batch alone decides between the three step programs
    eng = _engine()
    first = eng._put_tokens([1, 2], [[1, 2, 3], list(range(1, 12))])
    eng._put_tokens([1], [[first[1]]])
    assert sorted(fn.__name__ for fn in eng._step_fns.values()) == [
        "serve_decode_r1", "serve_fresh_r2_c8", "serve_split_r1_c8"]


def _dropped_engine_after_a_step():
    """(name of the step program it ran, weak references to a serving
    engine, to one of its weight buffers and to the program's jitted
    function), after the engine ran a prefill step and the caller let go of
    it."""
    import gc
    import weakref
    eng = _engine()
    eng._put_tokens([7], [[1, 2, 3]])
    (jitted,) = eng._step_fns.values()
    name = jitted.__name__
    assert name in telemetry.compile_monitor.programs()
    refs = (weakref.ref(eng), weakref.ref(jax.tree.leaves(eng.params)[0]),
            weakref.ref(jitted))
    del eng, jitted
    gc.collect()
    return name, refs


def _alive(refs):
    import gc
    gc.collect()
    return [r() is not None for r in refs]


def test_an_untraced_engine_is_freed_when_its_caller_drops_it():
    """Registration pins nothing: a process that rebuilds an engine (a
    reload, an autotuner, bench.py's two runs) never holds two device
    states because of the scope table."""
    assert not telemetry.tracer.enabled
    name, refs = _dropped_engine_after_a_step()
    assert _alive(refs) == [False, False, False]
    assert name not in telemetry.compile_monitor.programs()
    with pytest.raises(KeyError):
        telemetry.compile_monitor.scopes(name)


def test_a_traced_run_keeps_the_program_until_its_table_was_asked(traced):
    """The table is asked for after the work, when the caller may hold the
    engine no longer (the benchmark's readers run after the runner
    returned): while the tracer is on the monitor keeps the program, and
    lets it go once the table is made."""
    name, refs = _dropped_engine_after_a_step()
    # what is kept is the program: its body closes over the model's statics
    # and no engine, and it is lowered again from the arguments' abstract
    # form, so the device state goes with its caller (ISSUE 44)
    assert _alive(refs) == [False, False, True]
    table = telemetry.compile_monitor.scopes(name)
    assert "mlp" in {e["scope"] for e in table.values()}
    assert _alive(refs) == [False, False, False]
    assert name in telemetry.compile_monitor.programs()
    assert telemetry.compile_monitor.scopes(name) is table


def test_switching_the_tracer_on_holds_what_registered_before_it():
    """The benchmark's order: warm-up with the tracer off (the cache miss,
    the registration), then the tracer on for the window."""
    import gc
    import weakref
    tr = telemetry.tracer
    assert not tr.enabled
    eng = _engine()
    alive = weakref.ref(eng._step_fn(2, 1, ("argmax",), False))
    tr.configure(enabled=True)
    try:
        del eng
        gc.collect()
        assert alive() is not None
        assert "serve_decode_r2" in telemetry.compile_monitor.programs()
    finally:
        tr.configure(enabled=False)
        tr.clear()
    gc.collect()
    assert alive() is None


def test_scope_table_is_of_the_source_as_it_is_now():
    """The hazard, staged: jax leaves metadata out of the persistent
    cache's key, so a program whose scope was renamed hits the entry
    compiled before, and the executable it then holds in memory carries
    the OLD ``op_name``s. The table names the new scope all the same."""
    import jax.numpy as jnp
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def make(scope):
        def scope_probe(x, w):
            with jax.named_scope(scope):
                return jnp.tanh(x @ w).sum()
        return jax.jit(scope_probe)
    x = jnp.ones((48, 48), jnp.float32)
    try:
        make("mlp")(x, x).block_until_ready()       # leaves the entry
        renamed = make("attn_out")
        renamed(x, x).block_until_ready()           # hits it
        held = renamed.lower(x, x).compile().as_text()
        assert "/mlp/" in held and "/attn_out/" not in held, \
            "jax no longer hands back a stale executable: the hazard " \
            "compile_monitor._compile_with_current_metadata exists for"
        telemetry.compile_monitor.register_program(
            "scope_probe", renamed, (x, x))
        table = telemetry.compile_monitor.scopes("scope_probe")
        assert {e["scope"] for e in table.values()} - {None} == {"attn_out"}
        # the key that holds metadata was this thread's, for that compile
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def test_scopes_of_an_unknown_program_is_a_key_error():
    with pytest.raises(KeyError):
        telemetry.compile_monitor.scopes("no_such_program")


def test_span_yields_its_arguments_only_while_tracing(traced):
    with traced.span("probe/late", a=1) as args:
        args["b"] = 2
    assert _spans(traced.events(), "probe/late")[0]["args"] == \
        {"a": 1, "b": 2}
    traced.configure(enabled=False)
    with traced.span("probe/off", a=1) as args:
        assert args is None
    assert not _spans(traced.events(), "probe/off")


# -- the trainer's span tree ------------------------------------------------------

def test_traced_train_step_has_its_span_tree(traced):
    from deepspeed_tpu.models.gpt import gpt2_config
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import initialize
    build_mesh(data=8)
    engine, *_ = initialize(
        model=gpt2_config("tiny", max_seq_len=32, vocab_size=128),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}}},
        rng=jax.random.PRNGKey(0))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, size=(8, 32), dtype=np.int32)}
    first = engine.global_steps
    for _ in range(2):
        engine.train_batch(iter([batch]))
    events = traced.events()
    steps = _spans(events, "train/step")
    assert [s["args"]["step"] for s in steps] == [first, first + 1]
    for name in ("train/batch", "train/dispatch", "train/bookkeeping"):
        kids = _spans(events, name)
        assert len(kids) == 2, name
        assert all(_inside(k, s) for k, s in zip(kids, steps)), name
    # children in order, and no retroactive second envelope
    b, d, k = (_spans(events, n)[0] for n in
               ("train/batch", "train/dispatch", "train/bookkeeping"))
    assert b["ts"] + b["dur"] <= d["ts"] and d["ts"] + d["dur"] <= k["ts"]
    # the fused step registered for the scope table under its module name
    assert "fused_step" in telemetry.compile_monitor.programs()
    # (the CPU compiler keeps far less metadata than the TPU's: what the
    # table holds there is asserted in test_tpu_compile.py)
    table = telemetry.compile_monitor.scopes("fused_step")
    assert "mlp" in {e["scope"] for e in table.values()}
    assert any(e["backward"] for e in table.values())


# -- the server's span tree, the work of a launch, the counters ---------------------

def _serve(steps, prompts, max_new_tokens=4):
    from deepspeed_tpu.serving import ServingFrontend
    eng = _engine()
    fe = ServingFrontend(eng)
    for p in prompts:
        fe.submit(list(p), max_new_tokens=max_new_tokens)
    for _ in range(steps):
        fe.step()
    return eng, fe


def test_traced_serving_step_has_its_span_tree_and_work(traced):
    rng = np.random.default_rng(0)
    before = _counters()
    # 20 and 3 prompt tokens at chunk 8: fresh chunks first, then chunks
    # with history beside decode rows, then decode-only steps
    _serve(6, [rng.integers(1, 255, 20), rng.integers(1, 255, 3)])
    events = traced.events()
    steps = _spans(events, "serving/step")
    assert len(steps) == 6
    # a step launches its program and THEN collects the one before it: the
    # first step holds no fetch and no fan-out
    tree = {"serving/step": ("serving/admit", "serving/engine_step"),
            "serving/engine_step": ("serving/pack", "serving/dispatch")}
    behind = {"serving/step": ("serving/fanout",),
              "serving/engine_step": ("serving/fetch",)}
    for parent in tree:
        parents = _spans(events, parent)
        for name in tree[parent] + behind[parent]:
            spans = _spans(events, name)
            late = name in behind[parent]
            assert len(spans) == 6 - late, name
            assert all(_inside(k, p) for k, p in
                       zip(spans, parents[late:])), name
    launches = _spans(events, "serving/dispatch")
    programs = [e["args"]["program"] for e in launches]
    assert programs[0] == "fresh" and "split" in programs and \
        programs[-1] == "decode"
    assert [e["args"]["program"] for e in
            _spans(events, "serving/engine_step")] == programs
    for e in launches:
        a = e["args"]
        assert a["rows"] <= a["rows_bucket"]
        assert a["slots"] == a["row_slots"] == a["rows_bucket"] * a["chunk"]
        assert 0 < a["tokens"] <= a["slots"]
        assert a["tokens"] <= a["context_tokens"] <= a["context_slots"]
        # the page table of ENG_CFG: 128 / 8 pages of 8 tokens a row
        assert a["context_slots"] == a["rows_bucket"] * 16 * 8
    first = launches[0]["args"]
    assert (first["tokens"], first["context_tokens"]) == (8 + 3, 8 + 3)
    # the always-on counters advanced by the launches' own sums
    after = _counters()
    for counter, key in (("dispatch/tokens", "tokens"),
                         ("dispatch/token_slots", "slots"),
                         ("dispatch/context_tokens", "context_tokens"),
                         ("dispatch/context_slots", "context_slots")):
        assert after[counter] - before[counter] == \
            sum(e["args"][key] for e in launches), counter
    assert after["dispatch/host_calls"] - before["dispatch/host_calls"] == 6
    by_program = {p: programs.count(p) for p in set(programs)}
    assert all(telemetry.registry.counter(f"dispatch/steps.{p}").value >= n
               for p, n in by_program.items())


@pytest.mark.parametrize("reader", ["paged", "gather"])
def test_split_launch_counts_what_its_history_reader_reads(traced,
                                                           monkeypatch,
                                                           reader):
    """``context_slots`` of a split launch: the pages the paged kernel
    walks plus the chunk's own keys when that reader is chosen
    (``use_pallas`` forced; the kernels in interpret mode), the page
    table's padded width under the XLA gather. Every other program keeps
    the padded width."""
    import functools
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.serving import ServingFrontend
    for kernel in ("paged_attention", "paged_attention_with_lse"):
        monkeypatch.setattr(pa, kernel, functools.partial(
            getattr(pa, kernel), interpret=True))
    starts = []
    real = RaggedInferenceEngineTPU._pack
    monkeypatch.setattr(
        RaggedInferenceEngineTPU, "_pack",
        lambda self, batch, nb, cb: starts.append(
            np.array(batch.start_positions)) or real(self, batch, nb, cb))
    fe = ServingFrontend(_engine(use_pallas=reader == "paged"))
    rng = np.random.default_rng(0)
    before = _counters()
    for prompt in (rng.integers(1, 255, 20), rng.integers(1, 255, 3)):
        fe.submit(list(prompt), max_new_tokens=4)
    for _ in range(4):
        fe.step()
    launches = [e["args"] for e in
                _spans(traced.events(), "serving/dispatch")]
    assert [a["program"] for a in launches] == \
        ["fresh", "split", "split", "decode"]
    for a, st in zip(launches, starts):
        padded = a["rows_bucket"] * 16 * 8      # ENG_CFG's table, in tokens
        if a["program"] == "split" and reader == "paged":
            want = int((-(-st // 8) * 8).sum()) + a["rows_bucket"] * 8
            assert want < padded
        else:
            want = padded
        assert a["context_slots"] == want, a
        assert a["tokens"] <= a["context_tokens"] <= a["context_slots"]
    # the first split step: 8 cached tokens of the long prompt (one page),
    # the short prompt's 3 (one page, partly live), two rows of 8 chunk keys
    assert launches[1]["context_slots"] == \
        (8 + 8 + 2 * 8 if reader == "paged" else 2 * 16 * 8)
    after = _counters()
    assert after["dispatch/context_slots"] - \
        before["dispatch/context_slots"] == \
        sum(a["context_slots"] for a in launches)


def test_packed_launch_counts_the_capacity_it_ran_at(traced):
    """``slots`` of a launch whose rows hold more slots than the step's
    token budget is the capacity its token-wise sublayers packed the
    tokens into — ``max_batch_tokens``, or 16 a row when a split step's
    tokens fit that (the program's own rule) — and ``row_slots`` what
    attention still works on; ``dispatch/token_slots`` grows by the
    former. 4 rows x chunk 96 = 384 slots over a budget of 80. The small
    instance holds ONE row at the chunk's width (64 slots are no whole
    chunk), so a step with two rows of more than one token takes the top
    one whatever its tokens."""
    from deepspeed_tpu.serving import ServingFrontend
    eng = _engine(prefill_chunk=96, max_batch_tokens=80, max_sequences=4)
    assert eng._token_capacities(4, 96, "split") == (64, 80)
    assert eng._token_capacities(4, 96, "fresh") == (80,)
    assert eng._token_capacities(2, 96, "split") == (80,)  # 2.4 x the budget
    fe = ServingFrontend(eng)
    rng = np.random.default_rng(0)
    before = _counters()
    for n in (30, 30, 30, 30):
        fe.submit(list(rng.integers(1, 255, n)), max_new_tokens=8)
    for _ in range(4):
        fe.step()
    launches = [e["args"] for e in
                _spans(traced.events(), "serving/dispatch")]
    got = [(a["program"], a["rows_bucket"], a["tokens"], a["slots"],
            a["row_slots"]) for a in launches]
    assert got == [("fresh", 4, 80, 80, 384),     # 30 + 30 + 20 of 30
                   ("split", 4, 2 + 10 + 30, 80, 384),   # two chunk rows
                   ("decode", 4, 4, 4, 4),
                   ("decode", 4, 4, 4, 4)], got
    after = _counters()
    assert after["dispatch/token_slots"] - before["dispatch/token_slots"] \
        == 80 + 80 + 4 + 4
    assert after["dispatch/tokens"] - before["dispatch/tokens"] == \
        80 + 42 + 4 + 4
    assert not [n for n in telemetry.registry.names()
                if n.startswith("dispatch/steps.") and n.split(".")[1] not in
                ("fresh", "split", "decode", "paged")]


#: launch -> (engine overrides, prompt lengths, which launch, what it is:
#: program, tokens, ``kv_write_slots``). The packing engine's 4-row split
#: program holds 64 and 80 slots and writes back in blocks of 64
_PACKING = {"prefill_chunk": 96, "max_batch_tokens": 80, "max_sequences": 4}
_KV_WRITE_LAUNCHES = {
    "decode": ({}, (5,), 1, ("decode", 1, 1)),
    "row_form_split": ({}, (20, 3), 1, ("split", 8 + 1, 2 * 8)),
    "row_form_fresh": ({}, (20, 3), 0, ("fresh", 8 + 3, 2 * 8)),
    "fresh_at_its_capacity": (_PACKING, (30, 30, 30, 30), 0,
                              ("fresh", 80, 80)),
    "split_at_the_small_capacity": (_PACKING, (30, 30, 30, 30), 1,
                                    ("split", 42, 64)),
    "split_at_the_top_capacity": (_PACKING, (100, 30, 70, 40), 1,
                                  ("split", 80, 2 * 64)),
}


@pytest.mark.parametrize("launch", list(_KV_WRITE_LAUNCHES))
def test_launch_counts_the_updates_its_kv_write_performs(traced, launch):
    """``kv_write_slots`` of a launch, and ``dispatch/kv_write_slots``: the
    updates a pool and layer its KV scatter performs — one a row in a
    decode step, rows x chunk where the step keeps the row form, the
    capacity a fresh step packed into, and whole blocks of the small
    capacity until the tokens are written in a split step that packs (so
    ``token_slots`` wherever the top capacity is a multiple of the small
    one, as 1,024 and 2,048 are)."""
    from deepspeed_tpu.serving import ServingFrontend
    over, prompts, index, (program, tokens, slots) = \
        _KV_WRITE_LAUNCHES[launch]
    fe = ServingFrontend(_engine(**over))
    rng = np.random.default_rng(0)
    before = telemetry.registry.counter("dispatch/kv_write_slots").value
    for n in prompts:
        fe.submit(list(rng.integers(1, 255, n)), max_new_tokens=8)
    for _ in range(index + 1):
        fe.step()
    launches = [e["args"] for e in
                _spans(traced.events(), "serving/dispatch")]
    a = launches[index]
    assert (a["program"], a["tokens"], a["kv_write_slots"]) == \
        (program, tokens, slots), a
    assert a["tokens"] <= a["kv_write_slots"] <= a["row_slots"]
    if not over:
        assert a["kv_write_slots"] == a["slots"] == a["row_slots"]
    assert telemetry.registry.counter("dispatch/kv_write_slots").value - \
        before == sum(x["kv_write_slots"] for x in launches)


def test_untraced_serving_step_counts_and_computes_no_argument(monkeypatch):
    """With the tracer off the counters still advance by the packed
    batch's sums, and the span arguments are never unpacked."""
    from deepspeed_tpu.inference import launch_work

    class Untouchable(dict):
        def keys(self):
            raise AssertionError("span arguments built with the tracer off")

    real = launch_work.launch_work
    monkeypatch.setattr(launch_work, "launch_work",
                        lambda *a, **k: Untouchable(real(*a, **k)))
    tr = telemetry.tracer
    was = tr.enabled
    tr.configure(enabled=False)
    tr.clear()
    try:
        before = _counters()
        eng, _fe = _serve(2, [np.arange(1, 6)], max_new_tokens=8)
        after = _counters()
    finally:
        tr.configure(enabled=was)
    assert not tr.events()
    # step 1: the 5 prompt tokens in one fresh chunk of 8 slots; step 2:
    # one decode token that attends 5 cached tokens and itself
    assert after["dispatch/tokens"] - before["dispatch/tokens"] == 5 + 1
    assert after["dispatch/token_slots"] - \
        before["dispatch/token_slots"] == 8 + 1
    assert after["dispatch/context_tokens"] - \
        before["dispatch/context_tokens"] == 5 + 6
    assert after["dispatch/context_slots"] - \
        before["dispatch/context_slots"] == 2 * 16 * 8
    assert eng.last_program == "decode"


# -- the leaves tile the pump (PR 39) -------------------------------------------

FRONT = ("serving/admit", "serving/plan")
BACK = ("serving/bookkeeping", "serving/fanout", "serving/bookkeeping")
#: the leaves of a step that launched AND collected, in order: the launch
#: (whose ``serving/retire`` marks the rows scheduled and continues them),
#: then the fetch of the launch BEFORE it and that one's tokens
LEAVES = {
    "run": FRONT + ("serving/schedule", "serving/pack", "serving/dispatch",
                    "serving/count", "serving/retire", "serving/fetch",
                    "serving/retire") + BACK}
ENGINE_LEAVES = {"serving/schedule", "serving/pack", "serving/dispatch",
                 "serving/count", "serving/fetch", "serving/retire"}
#: a step with nothing in flight before its launch: nothing to fan out
LAUNCH_ONLY = FRONT + ("serving/schedule", "serving/pack",
                       "serving/dispatch", "serving/count",
                       "serving/retire", "serving/bookkeeping")
#: ... and one with nothing left to launch, that collects the last launch
COLLECT_ONLY = FRONT + ("serving/schedule", "serving/fetch",
                        "serving/retire") + BACK
#: what the parent of PR 39 recorded for this batch sequence: (program,
#: tokens, slots, kv_write_slots, context_tokens, context_slots) a launch,
#: the ``dispatch/*`` counters' increase, the greedy tokens
PARENT = {
    "run": {
        "launches": [("fresh", 11, 16, 16, 11, 256),
                     ("split", 9, 16, 16, 20, 256),
                     ("split", 5, 16, 16, 25, 256),
                     ("decode", 2, 2, 2, 27, 256),
                     ("decode", 2, 2, 2, 29, 256),
                     ("decode", 2, 2, 2, 31, 256),
                     ("decode", 1, 1, 1, 24, 128),
                     ("decode", 1, 1, 1, 25, 128)],
        "counters": {"attn_row_slots": 56, "chunk_rows": 4,
                     "context_slots": 1792, "context_tokens": 192,
                     "host_calls": 8, "kv_write_slots": 56,
                     # every launch but the first: made before the one
                     # ahead of it was collected (ISSUE 44)
                     "launches_ahead": 7,
                     # (PR 46: a split launch by the slots it ran over —
                     # here the row form's 2 x 8)
                     "split_steps_at.16": 2,
                     "steps.decode": 5, "steps.fresh": 1, "steps.split": 2,
                     "token_slots": 56, "tokens": 33}},
}
PARENT_TOKENS = [[182, 208, 191, 135, 209, 53], [3, 214, 9, 36, 181, 9]]
HOST_COUNTERS = ("dispatch/host_seconds", "dispatch/fetch_wait_seconds")


def _dispatch_counters():
    return {n[len("dispatch/"):]: telemetry.registry.counter(n).value
            for n in telemetry.registry.names()
            if n.startswith("dispatch/") and n not in HOST_COUNTERS}


def _wrap_term(monkeypatch, name, work):
    """``launch_work.TERMS`` with the term ``name`` computed by ``work``
    and applied to every stack, for the engines built from here on (a site
    picks its terms once)."""
    from deepspeed_tpu.inference import launch_work
    names = [t.work.__name__ for t in launch_work.TERMS]
    assert name in names
    monkeypatch.setattr(launch_work, "TERMS", tuple(
        t._replace(applies=lambda site: True, work=work) if n == name else t
        for n, t in zip(names, launch_work.TERMS)))


def _pump(steps=9, max_new_tokens=6):
    """Eight launches in eight steps; the ninth collects the last."""
    from deepspeed_tpu.serving import ServingFrontend
    fe = ServingFrontend(_engine())
    rng = np.random.default_rng(0)
    reqs = [fe.submit([int(t) for t in p], max_new_tokens=max_new_tokens)
            for p in (rng.integers(1, 255, 20), rng.integers(1, 255, 3))]
    for _ in range(steps):
        fe.step()
    return fe, reqs


@pytest.mark.parametrize("path", sorted(LEAVES))
def test_a_step_that_launched_holds_the_leaves_once_in_order(traced, path):
    """Each ``serving/step`` that launched a program and collected one
    holds every leaf once (``serving/retire`` and ``serving/bookkeeping``
    twice), in the pump's order, one ending before
    the next begins; the engine's lie inside ``serving/engine_step``;
    ``serving/submit`` stands outside every step. The pump that runs ahead
    opens with a step that only launches and closes with one that only
    collects."""
    _pump()
    events = [e for e in traced.events() if e["ph"] == "X"]
    steps = _spans(events, "serving/step")
    assert len(steps) == 9
    names = set(LEAVES["run"])
    seen = set()
    for step in steps:
        inside = sorted((e for e in events if e["name"] in names and
                         _inside(e, step)), key=lambda e: e["ts"])
        order = tuple(e["name"] for e in inside)
        if "serving/dispatch" not in order and "serving/fetch" not in order:
            # the pump ran dry: no program, no fan-out
            assert order == FRONT + ("serving/schedule",
                                     "serving/bookkeeping")
            continue
        if "serving/dispatch" not in order:
            assert step is steps[-1]
            assert order == COLLECT_ONLY
            continue
        if "serving/fetch" not in order:
            assert step is steps[0]
            assert order == LAUNCH_ONLY
            continue
        seen.add(path)
        assert order == LEAVES[path]
        for a, b in zip(inside, inside[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, (a, b)
        (engine,) = (e for e in _spans(events, "serving/engine_step")
                     if _inside(e, step))
        for e in inside:
            assert _inside(e, engine) == (e["name"] in ENGINE_LEAVES), e
    assert seen == {"run"}
    submits = _spans(events, "serving/submit")
    assert len(submits) == 2
    assert not any(_inside(s, step) for s in submits for step in steps)


def test_each_phase_of_the_pump_runs_under_its_leaf(traced, monkeypatch):
    """Tiling, by what runs where: the scheduler's selection under
    ``serving/schedule``, the packing under ``serving/pack``, the
    accounting under ``serving/count`` and AFTER the jitted call, the
    device_get under ``serving/fetch``, ``mark_scheduled`` under
    ``serving/retire``, the frontend's own work under ``serving/plan`` and
    ``serving/bookkeeping``."""
    import jax as jax_module
    from deepspeed_tpu.inference import engine_v2, launch_work, ragged
    from deepspeed_tpu.serving.frontend import ServingFrontend
    calls = []

    def stamped(owner, attr, leaf):
        real = getattr(owner, attr)

        def spy(*a, **k):
            calls.append((leaf, attr, (traced.now() - traced._t0) * 1e6))
            return real(*a, **k)
        monkeypatch.setattr(owner, attr, spy)

    eng = engine_v2.RaggedInferenceEngineTPU
    stamped(ragged.RaggedScheduler, "next_batch", "serving/schedule")
    stamped(ragged.RaggedScheduler, "mark_scheduled", "serving/retire")
    stamped(eng, "_buckets", "serving/pack")
    stamped(eng, "_pack", "serving/pack")
    stamped(eng, "_step_fn", "serving/dispatch")
    for attr in ("launch_work", "count_launch"):
        stamped(launch_work, attr, "serving/count")
    for name in ("kv_window_tokens", "attn_pairs"):
        _wrap_term(monkeypatch, name, lambda site, launch, name=name:
                   calls.append(("serving/count", name,
                                 (traced.now() - traced._t0) * 1e6)) or {})
    stamped(jax_module, "device_get", "serving/fetch")
    stamped(ServingFrontend, "_update_degraded", "serving/bookkeeping")
    stamped(ServingFrontend, "_fan_out", "serving/fanout")
    _pump(steps=4)
    events = traced.events()
    assert {attr for _leaf, attr, _t in calls} >= {
        "next_batch", "mark_scheduled", "_pack", "_step_fn", "attn_pairs",
        "kv_window_tokens", "launch_work", "count_launch", "device_get",
        "_update_degraded", "_fan_out"}
    for leaf, attr, at in calls:
        assert any(s["ts"] <= at <= s["ts"] + s["dur"]
                   for s in _spans(events, leaf)), (attr, leaf)
    # the launch comes first, then its accounting
    order = [attr for _leaf, attr, _t in calls
             if attr in ("_step_fn", "launch_work", "count_launch")]
    assert order == ["_step_fn", "launch_work", "count_launch"] * 4


@pytest.mark.parametrize("path", sorted(PARENT))
def test_launch_arguments_counters_and_tokens_are_the_parents(traced, path):
    """Counting after the launch changed nothing that is counted: for a
    fixed batch sequence the ``serving/dispatch`` spans' arguments, every
    ``dispatch/*`` counter and the greedy tokens are what the tree before
    PR 39 gave (``attn_row_slots`` and ``chunk_rows`` came with PR 40: in
    the row form what attention works on is ``token_slots``)."""
    before = _dispatch_counters()
    _fe, reqs = _pump()
    after = _dispatch_counters()
    launches = [e["args"] for e in
                _spans(traced.events(), "serving/dispatch")]
    assert [(a["program"], a["tokens"], a["slots"], a["kv_write_slots"],
             a["context_tokens"], a["context_slots"])
            for a in launches] == PARENT[path]["launches"]
    assert all(list(a)[0] == "program" for a in launches)
    grew = {n: after[n] - before.get(n, 0) for n in after
            if after[n] != before.get(n, 0)}
    assert grew == PARENT[path]["counters"]
    assert [list(r.tokens_out) for r in reqs] == PARENT_TOKENS


def test_untraced_launch_computes_no_span_argument_and_still_counts(
        monkeypatch):
    """``launch_work.attn_pairs`` is span arguments only: with the tracer
    off it is not called, and the always-on counters advance as they do
    traced."""
    called = []
    _wrap_term(monkeypatch, "attn_pairs",
               lambda site, launch: called.append(1) or {})
    tr = telemetry.tracer
    was = tr.enabled
    grew = {}
    try:
        for on in (False, True):
            tr.configure(enabled=on)
            tr.clear()
            del called[:]
            before = _dispatch_counters()
            _pump()
            after = _dispatch_counters()
            grew[on] = {n: after[n] - before.get(n, 0) for n in after}
            assert len(called) == (8 if on else 0)
    finally:
        tr.configure(enabled=was)
        tr.clear()
    assert grew[False] == grew[True]
    assert grew[False]["tokens"] == 33 and grew[False]["host_calls"] == 8


def test_host_and_wait_seconds_tile_the_time_between_fetches(monkeypatch):
    """``dispatch/host_seconds`` + ``dispatch/fetch_wait_seconds`` is the
    wall time from the first fetch's start to the last one's return of
    launches back to back, on the engine's own clock reads; a pump that
    ran dry drops the stamp, so waiting for work is no host time."""
    import time
    import types
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.serving import ServingFrontend
    reads = []

    def clock():
        reads.append(time.perf_counter())
        return reads[-1]
    monkeypatch.setattr(engine_v2, "time",
                        types.SimpleNamespace(perf_counter=clock))

    def seconds():
        return [telemetry.registry.counter(n).value for n in HOST_COUNTERS]
    fe = ServingFrontend(_engine())
    before = seconds()
    fe.submit(list(range(1, 12)), max_new_tokens=4)
    for _ in range(6):      # 2 chunks, 3 decode steps; the last's collect
        fe.step()
    host, wait = (b - a for a, b in zip(before, seconds()))
    assert len(reads) == 2 * 5              # two clock reads a fetch
    assert host > 0 and wait > 0
    assert host + wait == pytest.approx(reads[-1] - reads[0], abs=1e-9)
    assert wait == pytest.approx(sum(reads[1::2]) - sum(reads[0::2]))
    # dry: nothing to schedule, nothing counted, and the stamp is dropped
    assert fe.step() is False and fe.engine._fetch_returned is None
    assert len(reads) == 10
    time.sleep(0.05)
    fe.submit(list(range(1, 5)), max_new_tokens=2)
    mid = seconds()
    fe.step()               # the launch
    fe.step()               # ... and its fetch: the first since the stamp
    host2, wait2 = (b - a for a, b in zip(mid, seconds()))
    assert host2 == 0.0 and wait2 == pytest.approx(reads[-1] - reads[-2])


def test_a_spans_annotation_opens_first_and_closes_after_the_event_is_kept():
    """Siblings tile their parent in a profiler capture: the annotation
    covers the clock reads AND the recording, a step number makes it a
    step annotation, and a disabled tracer hands out one shared no-op."""
    import types
    from deepspeed_tpu.telemetry.tracer import Tracer
    log = []
    tr = Tracer()

    class Annotation:
        def __init__(self, name, **kw):
            self.what = (name, kw)

        def __enter__(self):
            log.append(("enter", self.what, len(tr.events())))

        def __exit__(self, *exc):
            log.append(("exit", self.what, len(tr.events())))

    tr._jprof = types.SimpleNamespace(TraceAnnotation=Annotation,
                                      StepTraceAnnotation=Annotation)
    assert tr.span("probe/off") is tr.span("probe/off2")    # tracing off
    tr.enabled = tr.jax_annotations = True      # (a tracer of its own:
    with tr.span("probe/a", k=1) as args:       # nothing to put back)
        args["late"] = 2
    with pytest.raises(KeyError):
        with tr.span("probe/b", step=7):
            raise KeyError("the span is kept all the same")
    assert log == [("enter", ("probe/a", {}), 0),
                   ("exit", ("probe/a", {}), 1),
                   ("enter", ("probe/b", {"step_num": 7}), 1),
                   ("exit", ("probe/b", {"step_num": 7}), 2)]
    a, b = tr.events()
    assert a["args"] == {"k": 1, "late": 2} and b["args"] == {"step": 7}


def test_a_span_whose_recording_raises_still_closes_its_annotation():
    """The annotation closes after the event is built, in a ``finally``:
    a request context whose tags raise leaves no annotation open in the
    capture (the error is the caller's to see)."""
    import types
    from deepspeed_tpu.telemetry.tracer import Tracer
    log = []
    tr = Tracer()

    class Annotation:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    class Broken:
        def tags(self):
            raise RuntimeError("no tags today")

    tr._jprof = types.SimpleNamespace(TraceAnnotation=Annotation,
                                      StepTraceAnnotation=Annotation)
    tr.enabled = tr.jax_annotations = True
    with pytest.raises(RuntimeError, match="no tags today"):
        with tr.span("probe/outer"):
            with tr.span("probe/broken", ctx=Broken()):
                pass
    assert log == [("enter", "probe/outer"), ("enter", "probe/broken"),
                   ("exit", "probe/broken"), ("exit", "probe/outer")]
    assert [e["name"] for e in tr.events()] == ["probe/outer"]
