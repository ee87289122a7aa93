"""The serving pump launches step n+1 before it fetches step n (ISSUE 44).

A row's fed-back token stays on the device: every token-mode step program
writes its sampled tokens into the arena's slot buffer (``FED_TOKENS``), the
engine's ``launch`` extends each row that may emit another token by ONE
placeholder, the next launch packs a sentinel for it, and ``collect``
patches the placeholder with the fetched value. What has to stay true, on
the tiny dense, MiMo-typed, latent and hybrid (state-space) stacks the suite
already builds, float32 on the CPU:

(a) the frontend's tokens are a synchronous ``step_with_budget`` loop's,
    greedy and sampled, prefix cache on and off, prompts of one and of
    several chunks;
(b) an end found AFTER a row was continued (eos, cancel, deadline) streams
    no extra token, is counted, and leaves pages and slot fit for reuse;
(c) a length end is known BEFORE the launch: no continuation at all;
(d) a fault at the launch or at the collect costs each request one retry;
(e) everything that ends a pump drains it;
(f) the order of the spans, and the counter that says how often the pump
    ran ahead;
and the program grid a server warms is the grid the pump launches."""

import dataclasses

import jax
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference import RaggedInferenceEngineTPU
from deepspeed_tpu.inference.engine_v2 import FED_SENTINEL, FED_TOKENS
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.resilience.faults import fault_injector
from deepspeed_tpu.serving import ServingFrontend

ENGINE = dict(dtype="float32", max_sequences=4, num_blocks=48, block_size=8,
              max_seq_len=64, max_batch_tokens=64, prefill_chunk=8)
STACKS = ("dense", "mimo", "latent", "hybrid")
_BUILT = {}


def _stack(name):
    """(config, float32 params) of a tiny stack, built once a process from
    the fixtures of the stack's own test file."""
    if name in _BUILT:
        return _BUILT[name]
    from deepspeed_tpu.models import transformer
    if name == "dense":
        from deepspeed_tpu.models.llama import llama3_config
        cfg = dataclasses.replace(
            llama3_config("tiny", max_seq_len=256, vocab_size=128),
            init_std=0.1)
        built = cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))
    elif name == "mimo":
        from tests.test_mimo_v2 import TINY, build
        built = build(TINY)
    elif name == "latent":
        from tests.test_deepseek_v3 import TINY, build
        built = build(TINY)
    else:
        from deepspeed_tpu.models.hf_loader import config_from_hf
        from tests.test_nemotron_h import randomised, small
        import jax.numpy as jnp
        cfg = config_from_hf(small())
        built = cfg, randomised(transformer.init_params(
            cfg, jax.random.PRNGKey(7), jnp.float32))
    _BUILT[name] = built
    return built


def _engine(stack="dense", **over):
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params = _stack(stack)
    return RaggedInferenceEngineTPU(cfg, dict(ENGINE, **over), params=params)


class _WaitingFrontend(ServingFrontend):
    """The pump as it was: each engine step a ``step_with_budget`` call that
    waits for its own program, every token fed back through the host."""

    def _engine_step(self, row_limits):
        out = self.engine.step_with_budget(budget=self.token_budget,
                                           mode=self.mode)
        return out is not None, None if out is None else (out, ())


def _counter(name):
    return telemetry.registry.counter("dispatch/" + name).value


def _launches():
    return sum(telemetry.registry.counter(n).value
               for n in telemetry.registry.names()
               if n.startswith("dispatch/steps."))


def _prompts(vocab, seed=0):
    """Two waves: prompts of one chunk and of several (chunk 8), the second
    wave sharing the first's long prompt as a prefix."""
    rng = np.random.default_rng(seed)
    long = rng.integers(1, vocab, 21).tolist()
    first = [long, rng.integers(1, vocab, 3).tolist(),
             rng.integers(1, vocab, 9).tolist()]
    second = [long + rng.integers(1, vocab, 5).tolist(),
              rng.integers(1, vocab, 2).tolist()]
    return first, second


def _serve(frontend_class, stack, mode, cache):
    """Tokens of two waves of requests (the second submitted once the first
    has drained, so that both pumps admit it into the same state), and the
    frontend."""
    fe = frontend_class(_engine(stack), mode=mode,
                        enable_prefix_cache=cache)
    vocab = fe.engine.model_config.vocab_size
    out = []
    for wave, budgets in zip(_prompts(vocab), ((7, 5, 1), (6, 4))):
        reqs = [fe.submit(p, max_new_tokens=n)
                for p, n in zip(wave, budgets)]
        fe.run_until_idle()
        assert all(r.finish_reason == "length" for r in reqs)
        out += [list(r.tokens_out) for r in reqs]
    return out, fe


# -- (a) the same tokens -------------------------------------------------------

@pytest.mark.parametrize("cache", [True, False], ids=["cache", "nocache"])
@pytest.mark.parametrize("mode", [("argmax",), ("sample", 8, True)],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("stack", STACKS)
def test_the_frontends_tokens_are_the_waiting_loops(devices, stack, mode,
                                                    cache):
    """Same requests, same programs in the same order (the rng is split on
    the device once a program), same tokens: bit-equal greedy, and sampled
    from the engine's own seed. The pump that runs ahead made all but its
    first launch of a wave while another was in flight, and dropped no
    row."""
    want, _ = _serve(_WaitingFrontend, stack, mode, cache)
    before = {n: _counter(n) for n in ("launches_ahead",
                                       "ahead_rows_dropped")}
    launches = _launches()
    got, fe = _serve(ServingFrontend, stack, mode, cache)
    assert got == want
    assert [len(t) for t in got] == [7, 5, 1, 6, 4]
    if cache and stack != "hybrid":
        assert fe.stats()["prefix_tokens_reused"] >= 16
    launches = _launches() - launches
    assert _counter("launches_ahead") - before["launches_ahead"] == \
        launches - 2                      # the first launch of each wave
    assert _counter("ahead_rows_dropped") == before["ahead_rows_dropped"]
    assert fe.engine.in_flight == 0 and not fe.engine.state.seqs


def test_a_fed_back_token_does_not_pass_through_the_host(devices,
                                                          monkeypatch):
    """What the next program reads IS the slot buffer: with every fetched
    token spoiled on its way to the host's copy of the sequence, a row's
    continuation is unchanged, and the buffer holds each row's last token
    by its slot."""
    want, _ = _serve(ServingFrontend, "dense", ("argmax",), False)
    eng = _engine()
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    packed = []
    real_pack = eng._pack

    def spy(batch, nb, cb):
        packed.append(batch.token_ids[:, 0].tolist())
        return real_pack(batch, nb, cb)
    monkeypatch.setattr(eng, "_pack", spy)
    first, _ = _prompts(eng.model_config.vocab_size)
    reqs = [fe.submit(p, max_new_tokens=n)
            for p, n in zip(first, (7, 5, 1))]
    slots = {}
    while any(r.finish_reason is None for r in reqs):
        fe.step()
        slots.update({uid: seq.slot for uid, seq in eng.state.seqs.items()})
    assert [list(r.tokens_out) for r in reqs] == want[:3]
    # decode rows were packed as the sentinel, never as a token's value
    assert any(FED_SENTINEL in row for row in packed)
    fed = np.asarray(eng.arena[FED_TOKENS])
    assert fed.shape == (ENGINE["max_sequences"] + 1,)
    for r in reqs:
        assert fed[slots[r.uid]] == r.tokens_out[-1]


# -- (b) an end found after the row was continued ------------------------------

def _fresh_tokens(stack, prompt, n):
    fe = ServingFrontend(_engine(stack), enable_prefix_cache=False)
    req = fe.submit(prompt, max_new_tokens=n)
    fe.run_until_idle()
    return list(req.tokens_out)


@pytest.mark.parametrize("how", ["eos", "cancel", "deadline"])
@pytest.mark.parametrize("stack", ["dense", "hybrid"])
def test_an_end_found_after_the_continuation_streams_nothing_more(
        devices, stack, how):
    """The row was continued and (eos) sits in the next launch already when
    its end is found: the extra token is dropped at that collect, never
    streamed, counted; the request that takes the freed pages and state
    slot yields what a fresh engine gives."""
    vocab = _stack(stack)[0].vocab_size
    rng = np.random.default_rng(5)
    prompt, other = (rng.integers(1, vocab, n).tolist() for n in (11, 13))
    whole = _fresh_tokens(stack, prompt, 12)
    now = [0.0]
    eng = _engine(stack, max_sequences=1)        # ONE slot: it is reused
    fe = ServingFrontend(eng, enable_prefix_cache=False,
                         clock=lambda: now[0])
    streamed = []
    # an eos that is the fourth token and none before it
    cut = next(i for i, t in enumerate(whole) if i >= 3 and
               t not in whole[:i])
    req = fe.submit(prompt, max_new_tokens=12, stream_cb=streamed.append,
                    eos_token_id=whole[cut] if how == "eos" else None,
                    timeout=10.0 if how == "deadline" else None)
    dropped = _counter("ahead_rows_dropped")
    free = eng.state.allocator.free_blocks
    slot = None
    while req.finish_reason is None:
        fe.step()
        if eng.state.seqs:
            slot = eng.state.seqs[req.uid].slot
        if how != "eos" and len(req.tokens_out) == cut + 1:
            if how == "cancel":
                req.cancel()
            else:
                now[0] = 11.0
    assert req.finish_reason == {"eos": "eos", "cancel": "cancelled",
                                 "deadline": "deadline"}[how]
    assert streamed == list(req.tokens_out) == whole[:cut + 1]
    again = fe.submit(other, max_new_tokens=6)
    fe.run_until_idle()
    assert streamed == whole[:cut + 1]
    assert _counter("ahead_rows_dropped") == dropped + 1
    assert eng.state.seqs == {} and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == free
    assert list(again.tokens_out) == _fresh_tokens(stack, other, 6)
    assert slot is not None and eng.state._slots == [slot]


# -- (c) a length end is not continued -----------------------------------------

@pytest.mark.parametrize("prompt_len,new,max_seq_len", [
    (5, 3, 64),         # an ordinary end
    (5, 4, 64),         # the last FED token fills the page: 5 + 3 = 8
    (8, 1, 64),         # one token after a prompt of a whole page
    (58, 6, 64),        # prompt + new tokens == max_seq_len
], ids=["plain", "page_boundary", "one_token", "max_seq_len"])
def test_a_length_end_is_known_before_the_launch(devices, prompt_len, new,
                                                 max_seq_len):
    """No placeholder past the budget: the sequence never holds more than
    prompt + new - 1 tokens, never a page past those, never a position past
    ``max_seq_len``, and nothing is dropped."""
    eng = _engine(max_seq_len=max_seq_len)
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    prompt = np.random.default_rng(2).integers(1, 128, prompt_len).tolist()
    dropped = _counter("ahead_rows_dropped")
    launches = _launches()
    req = fe.submit(prompt, max_new_tokens=new)
    most_tokens = most_pages = 0
    while req.finish_reason is None:
        fe.step()
        for seq in eng.state.seqs.values():
            most_tokens = max(most_tokens, len(seq.tokens))
            most_pages = max(most_pages, len(seq.blocks))
    assert req.finish_reason == "length" and len(req.tokens_out) == new
    assert most_tokens == prompt_len + new - 1 <= max_seq_len - 1
    assert most_pages == -(-(prompt_len + new - 1) // ENGINE["block_size"])
    assert _counter("ahead_rows_dropped") == dropped
    # a launch a token after the prefill's, and not one more
    assert _launches() - launches == -(-prompt_len // 8) + new - 1
    assert eng.in_flight == 0
    assert list(req.tokens_out) == _fresh_tokens("dense", prompt, new)


def test_a_continuation_without_a_page_is_the_frontends_to_answer(devices):
    """The arena is full when a row's continuation needs a page: the engine
    continues nothing, and the frontend's answer stands (no cache to evict
    from: ``kv_exhausted``), with every token up to there delivered."""
    eng = _engine(num_blocks=2)
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    rng = np.random.default_rng(4)
    b = fe.submit(rng.integers(1, 128, 6).tolist(), max_new_tokens=10)
    fe.step()                                   # admitted: its first page
    eng.state.allocator.allocate(1)             # someone else's page
    fe.run_until_idle()
    # page 1 holds tokens 0..7: the third token's feed-back has no page
    assert b.finish_reason == "kv_exhausted" and len(b.tokens_out) == 3
    assert list(b.tokens_out) == _fresh_tokens("dense", b.prompt, 3)
    assert eng.in_flight == 0 and not eng.state.seqs


# -- (d) faults ----------------------------------------------------------------

@pytest.fixture()
def disarmed():
    fault_injector.disarm()
    fault_injector.last_step = None
    yield
    fault_injector.disarm()
    fault_injector.last_step = None


@pytest.mark.parametrize("where", ["launch", "collect"])
def test_a_fault_costs_each_request_in_flight_one_retry(devices, disarmed,
                                                        monkeypatch, where):
    vocab = 128
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, vocab, n).tolist() for n in (11, 3)]
    want = [_fresh_tokens("dense", p, 6) for p in prompts]
    eng = _engine()
    fe = ServingFrontend(eng, retry_budget=2)
    reqs = [fe.submit(p, max_new_tokens=6) for p in prompts]
    faults = telemetry.registry.counter(
        "resilience/serving_engine_faults").value
    if where == "launch":
        fault_injector.arm("serving_step:4:engine_error", _env=False)
    else:
        real, calls = eng._fetch, []

        def failing(out):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("device_get failed")
            return real(out)
        monkeypatch.setattr(eng, "_fetch", failing)
    for _ in range(4):
        fe.step()
    # the fault found one launch in flight (at the launch) or made while
    # the failed one was collected: it is gone with the rows
    assert eng.in_flight == 0 and not eng.state.seqs
    assert [r.retries for r in reqs] == [1, 1]
    fe.run_until_idle()
    assert telemetry.registry.counter(
        "resilience/serving_engine_faults").value == faults + 1
    assert [r.retries for r in reqs] == [1, 1]
    assert [r.finish_reason for r in reqs] == ["length", "length"]
    assert [list(r.tokens_out) for r in reqs] == want
    alloc = eng.state.allocator
    assert alloc.free_blocks + fe.cache.pages_cached == alloc.num_blocks
    assert eng.in_flight == 0


# -- (e) drains ----------------------------------------------------------------

def _two_prompts():
    rng = np.random.default_rng(8)
    return [rng.integers(1, 128, n).tolist() for n in (5, 7)]


def _two_running(fe, eos_of=None):
    a, b = _two_prompts()
    return (fe.submit(a, max_new_tokens=9, eos_token_id=eos_of),
            fe.submit(b, max_new_tokens=9))


@pytest.mark.parametrize("how", ["run_until_idle", "stream",
                                 "stream_cancel", "terminate_inflight",
                                 "close", "step_reports_work"])
def test_what_ends_a_pump_leaves_nothing_in_flight(devices, how):
    eng = _engine()
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    if how == "run_until_idle":
        # an eos leaves the row's next launch in flight when nothing runs
        whole = _fresh_tokens("dense", _two_prompts()[0], 9)
        eos = next(t for i, t in enumerate(whole) if t not in whole[:i]
                   and i >= 2)
        a, b = _two_running(fe, eos_of=eos)
        fe.run_until_idle()
        assert a.finish_reason == "eos" and b.finish_reason == "length"
        assert list(a.tokens_out) == whole[:whole.index(eos) + 1]
    elif how == "stream":
        a, b = _two_running(fe)
        a.max_new_tokens = 4
        got = list(fe.stream(a))
        assert got == list(a.tokens_out) and len(got) == 4
        # the other request's token in flight was delivered, not lost
        assert b.finish_reason is None and len(b.tokens_out) >= 4
        assert eng.state.seqs[b.uid].pending == 1
        assert FED_SENTINEL not in eng.state.seqs[b.uid].tokens
        fe.run_until_idle()
        assert len(b.tokens_out) == 9
    elif how == "stream_cancel":
        a, b = _two_running(fe)
        it = fe.stream(a)
        got = [next(it) for _ in range(4)]
        fe.cancel(a)
        assert got + list(it) == list(a.tokens_out)  # drains, then stops
        assert a.state.value == "cancelled" and len(a.tokens_out) < 9
        # the flushed row released its slot and pages; the other goes on
        assert list(eng.state.seqs) == [b.uid]
        fe.run_until_idle()
        assert len(b.tokens_out) == 9
        assert eng.state.allocator.free_blocks == ENGINE["num_blocks"]
    elif how == "terminate_inflight":
        a, b = _two_running(fe)
        for _ in range(3):
            fe.step()
        assert eng.in_flight == 1
        assert fe.terminate_inflight("drained") == 2
        assert a.finish_reason == b.finish_reason == "drained"
    elif how == "close":
        a, b = _two_running(fe)
        for _ in range(3):
            fe.step()
        held = len(a.tokens_out)
        assert eng.in_flight == 1
        fe.close()
        assert len(a.tokens_out) == held + 1    # delivered, then closed
    else:
        a, b = _two_running(fe)
        fe.step()
        assert eng.in_flight == 1
        a.cancel()
        b.cancel()
        # nothing runs, nothing is queued: the launch in flight is work
        assert fe.step() is True and not fe._running
        assert fe.step() is False
    assert eng.in_flight == 0
    if how not in ("stream", "close"):
        assert not eng.state.seqs


def test_step_with_budget_waits_for_nothing_but_its_own_launch(devices):
    eng = _engine()
    eng.scheduler.put([1], [[3, 4, 5]])
    assert eng.launch() is True and eng.in_flight == 1
    with pytest.raises(RuntimeError, match="collect"):
        eng.step_with_budget()
    out, continued = eng.collect()
    assert set(out) == {1} and continued == set()    # no row_limits
    assert eng.collect() is None and eng.launch() is False


# -- (f) the order of a step ---------------------------------------------------

@pytest.fixture()
def traced():
    tr = telemetry.tracer
    was = tr.enabled
    tr.configure(enabled=True)
    tr.clear()
    yield tr
    tr.configure(enabled=was)
    tr.clear()


def _named(events, name):
    return sorted((e for e in events if e["name"] == name and
                   e["ph"] == "X"), key=lambda e: e["ts"])


def test_a_launch_begins_before_the_fetch_before_it_ends(devices, traced):
    eng = _engine()
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    rng = np.random.default_rng(9)
    reqs = [fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=12)
            for n in (19, 4, 6)]
    ahead, launches = _counter("launches_ahead"), _launches()
    calls = 0
    while any(r.finish_reason is None for r in reqs):
        fe.step()
        calls += 1
    events = traced.events()
    steps = _named(events, "serving/engine_step")
    assert len(steps) == len(_named(events, "serving/step")) == calls
    dispatches = _named(events, "serving/dispatch")
    fetches = _named(events, "serving/fetch")
    assert len(dispatches) == len(fetches) == calls - 1 == \
        _launches() - launches
    for n in range(len(fetches) - 1):
        # launch n+1's dispatch span starts before launch n's fetch ends
        assert dispatches[n + 1]["ts"] < fetches[n]["ts"] + fetches[n]["dur"]
        assert dispatches[n + 1]["ts"] + dispatches[n + 1]["dur"] <= \
            fetches[n]["ts"] + 1e-3
    # the step's program is the launch made in that call
    assert [s["args"].get("program") for s in steps] == \
        [d["args"]["program"] for d in dispatches] + [None]
    assert all("batch" in s["args"] for s in steps)
    share = (_counter("launches_ahead") - ahead) / len(dispatches)
    assert share == 1 - 1 / len(dispatches) and share > 0.9


def test_generate_runs_ahead_and_put_does_not(devices):
    eng = _engine()
    ahead, launches = _counter("launches_ahead"), _launches()
    out = eng.generate([[5, 6, 7], [9, 10]], max_new_tokens=5)
    assert [len(t) for t in out] == [3 + 5, 2 + 5]
    # one prefill launch and four decode launches: all but the first
    assert _launches() - launches == 5
    assert _counter("launches_ahead") == ahead + 4 and eng.in_flight == 0
    eng._put_tokens([77], [[1, 2, 3]])
    assert _counter("launches_ahead") == ahead + 4 and eng.in_flight == 0


# -- the grid a server warms is the grid the pump launches ---------------------

def _warm_program_grid(eng, mode):
    """``benchmark/runners/serve.py``'s warm-up, shape for shape: every
    step program on a batch of padding rows, through ``_pack`` and
    ``_step_fn`` with the four arguments and three results it relies on."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.ragged import RaggedBatch
    rows, nb = [], 1
    while nb < eng.config.max_sequences:
        rows.append(nb)
        nb *= 2
    rows.append(nb)
    chunk = int(eng.config.prefill_chunk)
    count = 0
    for nb in reversed(rows):
        for cb, fresh in ((chunk, "split"), (chunk, "fresh"), (1, False)):
            z = np.zeros(0, np.int32)
            empty = RaggedBatch(uids=[], token_ids=np.zeros((0, cb), np.int32),
                                token_counts=z, start_positions=z, slots=z)
            packed = jnp.asarray(eng._pack(empty, nb, cb))
            out, eng._rng_dev, eng.arena = eng._step_fn(nb, cb, mode, fresh)(
                eng.params, eng.arena, packed, eng._rng_dev)
            np.asarray(out)
            count += 1
    return count


@pytest.mark.parametrize("stack", ["dense", "hybrid"])
def test_a_pumped_load_adds_no_program_to_the_warmed_grid(devices, stack):
    """After the warm-up a mixed load of fresh, split and decode launches,
    arrivals joining a running batch, traces no step program: the sentinel
    path is data in the programs the grid holds, not a second family."""
    eng = _engine(stack)
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    assert _warm_program_grid(eng, fe.mode) == 9
    assert len(eng._step_fns) == 9
    traces = telemetry.compile_monitor._functions.get("serving/step_fn", 0)
    before = {k: _counter(k) for k in ("steps.fresh", "steps.split",
                                       "steps.decode", "launches_ahead")}
    vocab = eng.model_config.vocab_size
    rng = np.random.default_rng(3)
    reqs = [fe.submit(rng.integers(1, vocab, n).tolist(), max_new_tokens=7)
            for n in (20, 3)]
    for _ in range(4):
        fe.step()
    reqs += [fe.submit(rng.integers(1, vocab, n).tolist(), max_new_tokens=5)
             for n in (12, 2)]
    fe.run_until_idle()
    assert all(r.finish_reason == "length" for r in reqs)
    grew = {k: _counter(k) - v for k, v in before.items()}
    assert grew["steps.fresh"] >= 1 and grew["steps.split"] >= 3 and \
        grew["steps.decode"] >= 3 and grew["launches_ahead"] >= 8
    assert telemetry.compile_monitor._functions.get(
        "serving/step_fn", 0) == traces
    assert len(eng._step_fns) == 9
