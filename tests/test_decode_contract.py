"""One way to decode (ISSUE 48): ``launch`` / ``collect``.

``step_with_budget`` is a launch and its collect back to back and returns
``{uid: token}``; the serving frontend and ``generate()`` are clients of the
pump that runs ahead. What has to stay true, float32 on the CPU:

(a) the default frontend's greedy tokens are the loop's that waits for every
    program and feeds every token back through the host
    (``tests/test_paged._generate_stepwise``), for mixes of rows and budgets,
    with an eos inside a stream, with budgets that retire rows one by one;
(b) ``generate()`` drops exactly the one token a row was continued for when
    its eos is found, frees pages and state slot, continues no row whose
    end is known before the launch, and leaves nothing in flight when it
    raises;
(c) the old contract is gone, not ignored: no ``max_steps``, no option, no
    metric of that name; a configuration that still carries the key is
    warned about by name and served.

(``tests/test_pump_ahead.py`` holds the pump's own contract: continuation,
drains, faults, span order.)"""

import inspect
import logging

import numpy as np
import pytest
import jax

from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
from deepspeed_tpu.models.llama import llama3_config
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.serving import ServingFrontend
from deepspeed_tpu.telemetry.registry import registry
from tests.test_paged import _generate_stepwise
from tests import test_pump_ahead as pump

ENG_CFG = {"dtype": "float32", "num_blocks": 32, "block_size": 8,
           "max_seq_len": 128, "prefill_chunk": 8, "max_batch_tokens": 64,
           "max_sequences": 16}


def _engine(devices, params_key=0, **over):
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(params_key))
    return RaggedInferenceEngineTPU(cfg, {**ENG_CFG, **over}, params=params)


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=6 + i).tolist() for i in range(n)]


def _serve(devices, prompts, max_new, eos=None):
    """One run of the default frontend on a FRESH engine (same params_key →
    identical weights across runs); [(tokens_out, finish_reason), ...]."""
    eng = _engine(devices)
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    max_new = ([max_new] * len(prompts)
               if isinstance(max_new, int) else max_new)
    reqs = [fe.submit(p, max_new_tokens=m, eos_token_id=eos)
            for p, m in zip(prompts, max_new)]
    fe.run_until_idle()
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == ENG_CFG["num_blocks"]
    return [(list(r.tokens_out), r.finish_reason) for r in reqs]


def _stepwise(devices, prompts, max_new):
    """The new tokens of the loop that waits, a row a list."""
    max_new = ([max_new] * len(prompts)
               if isinstance(max_new, int) else max_new)
    outs = _generate_stepwise(_engine(devices), prompts, max_new)
    return [o[len(p):].tolist() for o, p in zip(outs, prompts)]


# -- (a) the frontend against the loop that waits ------------------------------

#: mix -> (rows, budgets): rows that end together, one row alone, ragged
#: budgets down to a single token, and budgets that retire rows one by one
#: while the survivors keep decoding with their KV intact
_MIXES = {"three_rows": (3, 12), "one_row": (1, 20),
          "ragged_budgets": (5, [1, 3, 8, 13, 2]),
          "staggered_retirement": (3, [4, 9, 17])}


@pytest.mark.parametrize("mix", list(_MIXES))
def test_the_frontends_greedy_tokens_are_the_stepwise_loops(devices, mix):
    rows, budgets = _MIXES[mix]
    prompts = _prompts(rows)
    got = _serve(devices, prompts, budgets)
    assert [t for t, _ in got] == _stepwise(devices, prompts, budgets)
    assert all(reason == "length" for _, reason in got)
    assert [len(t) for t, _ in got] == \
        ([budgets] * rows if isinstance(budgets, int) else budgets)


def test_an_eos_ends_its_rows_and_nothing_follows_it(devices):
    prompts = _prompts(3)
    base = _stepwise(devices, prompts, 12)
    # an eos id the FIRST request emits mid-stream: it was continued by
    # then, and the other rows go on
    eos = base[0][2]
    dropped = pump._counter("ahead_rows_dropped")
    got = _serve(devices, prompts, 12, eos=eos)
    ended = 0
    for (tokens, reason), whole in zip(got, base):
        if eos in whole:
            assert tokens == whole[:whole.index(eos) + 1]
            assert reason == "eos"
            ended += tokens != whole      # an eos on the last token: no drop
        else:
            assert tokens == whole and reason == "length"
    assert len(got[0][0]) == 3          # tokens through the eos, no more
    assert pump._counter("ahead_rows_dropped") - dropped == ended >= 1


def test_stream_stall_raises_with_context(devices):
    from deepspeed_tpu.serving.request import Request
    eng = _engine(devices)
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    orphan = Request(prompt=[1, 2, 3])            # never submitted
    it = fe.stream(orphan, poll_interval=0.001, stall_timeout=0.05)
    with pytest.raises(RuntimeError, match="queue_depth=0"):
        list(it)


# -- (b) generate() is a client of the pump ------------------------------------

@pytest.mark.parametrize("stack", ["dense", "hybrid"])
def test_generate_drops_the_one_token_after_an_eos(devices, stack):
    """The row was continued, and sits in the next launch already, when
    its eos is collected: that launch's token is dropped (counted once),
    the output ends with the eos, pages and state slot are free again."""
    vocab = pump._stack(stack)[0].vocab_size
    prompt = np.random.default_rng(5).integers(1, vocab, 11).tolist()
    (whole,) = pump._engine(stack).generate([prompt], max_new_tokens=12)
    whole = whole.tolist()[len(prompt):]
    cut = next(i for i, t in enumerate(whole) if i >= 3 and
               t not in whole[:i])
    eng = pump._engine(stack)
    dropped, launches = pump._counter("ahead_rows_dropped"), pump._launches()
    (out,) = eng.generate([prompt], max_new_tokens=12,
                          eos_token_id=whole[cut])
    assert out.tolist() == prompt + whole[:cut + 1]
    assert pump._counter("ahead_rows_dropped") == dropped + 1
    # two prefill chunks, cut decode steps, and the one that was dropped
    assert pump._launches() - launches == 2 + cut + 1
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == pump.ENGINE["num_blocks"]
    assert sorted(eng.state._slots) == list(range(
        pump.ENGINE["max_sequences"]))
    # the freed pages and slot serve the next call as a fresh engine's do
    other = np.random.default_rng(6).integers(1, vocab, 13).tolist()
    np.testing.assert_array_equal(
        eng.generate([other], max_new_tokens=6)[0],
        pump._engine(stack).generate([other], max_new_tokens=6)[0])


def test_generate_that_raises_leaves_nothing_in_flight(devices, monkeypatch):
    eng = pump._engine()
    real, ahead = eng._fetch, []

    def failing(out):
        ahead.append(eng.in_flight)     # launches made after this one
        if len(ahead) == 3:
            raise RuntimeError("device_get failed")
        return real(out)
    monkeypatch.setattr(eng, "_fetch", failing)
    prompts = [[5, 6, 7], list(range(1, 20))]
    with pytest.raises(RuntimeError, match="device_get failed"):
        eng.generate(prompts, max_new_tokens=8)
    # the launch made ahead of the failed collect went with the rows
    assert ahead == [1, 1, 1]
    assert eng.in_flight == 0 and not eng.state.seqs
    assert eng.state.allocator.free_blocks == pump.ENGINE["num_blocks"]
    monkeypatch.setattr(eng, "_fetch", real)
    for got, want in zip(eng.generate(prompts, max_new_tokens=8),
                         pump._engine().generate(prompts, max_new_tokens=8)):
        np.testing.assert_array_equal(got, want)


#: case -> (prompt lengths, budgets): a row of ONE token beside one that
#: decodes; a row whose prompt and budget add up to ``max_seq_len`` (64)
_PER_ROW_BUDGETS = {"budget_of_one": ((9, 4), (1, 6)),
                    "max_seq_len": ((58, 5), (6, 6))}


@pytest.mark.parametrize("case", list(_PER_ROW_BUDGETS))
def test_generate_continues_no_row_past_what_it_may_emit(devices, case,
                                                         monkeypatch):
    """A length end is known before the launch: a row is continued while
    its budget holds another token and ``max_seq_len`` another position,
    and never after — nothing is dropped, the tokens are the loop's that
    waits, the pages are free."""
    lengths, budgets = _PER_ROW_BUDGETS[case]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, n).tolist() for n in lengths]
    eng = pump._engine()
    real, continued, longest = eng._continue, {}, [0]

    def spy(seq, row_limits):
        at = real(seq, row_limits)
        continued.setdefault(seq.uid, []).append(at)
        longest[0] = max(longest[0], len(seq.tokens))
        return at
    monkeypatch.setattr(eng, "_continue", spy)
    dropped = pump._counter("ahead_rows_dropped")
    outs = eng.generate(prompts, max_new_tokens=list(budgets))
    assert [len(o) for o in outs] == [n + b for n, b in zip(lengths, budgets)]
    for uid, budget in enumerate(budgets):
        # asked once a token, continued for every token but the last
        assert [at is not None for at in continued[uid]] == \
            [True] * (budget - 1) + [False]
    assert longest[0] == max(n + b - 1 for n, b in zip(lengths, budgets)) \
        <= pump.ENGINE["max_seq_len"] - 1
    assert pump._counter("ahead_rows_dropped") == dropped
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == pump.ENGINE["num_blocks"]
    for got, want in zip(outs, _generate_stepwise(pump._engine(), prompts,
                                                  budgets)):
        np.testing.assert_array_equal(got, want)


# -- (c) the old contract is gone ----------------------------------------------

def test_step_with_budget_has_one_contract(devices):
    eng = pump._engine()
    eng.scheduler.put([1], [[3, 4, 5]])
    with pytest.raises(TypeError, match="max_steps"):
        eng.step_with_budget(max_steps=2)
    assert list(inspect.signature(eng.step_with_budget).parameters) == \
        ["budget", "mode"]
    out = eng.step_with_budget()
    assert list(out) == [1] and isinstance(out[1], int)
    assert eng.step_with_budget() is None


def test_a_serving_block_with_an_old_key_is_warned_about_and_served(devices):
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    from deepspeed_tpu.utils.logging import logger
    records = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = records.append
    logger.addHandler(handler)
    try:
        cfg = DeepSpeedTPUConfig(serving={"megastep_tokens": 16})
    finally:
        logger.removeHandler(handler)
    (warned,) = [r.getMessage() for r in records]
    assert "'megastep_tokens'" in warned and "ServingConfig" in warned
    fe = ServingFrontend(pump._engine(), config=cfg)
    req = fe.submit([3, 4, 5], max_new_tokens=4)
    fe.run_until_idle()
    assert req.finish_reason == "length" and len(req.tokens_out) == 4
    with pytest.raises(TypeError, match="megastep_tokens"):
        ServingFrontend(pump._engine(), megastep_tokens=4)


def test_no_option_and_no_metric_names_a_megastep(devices):
    from deepspeed_tpu.config.config import ServingConfig
    assert not [n for n in inspect.signature(ServingFrontend).parameters
                if "megastep" in n]
    assert not [n for n in ServingConfig.model_fields if "megastep" in n]
    fe = ServingFrontend(pump._engine())
    reqs = [fe.submit(p, max_new_tokens=5) for p in _prompts(2)]
    fe.run_until_idle()
    assert all(r.finish_reason == "length" for r in reqs)
    assert "serving/engine_steps" in registry.names()
    assert not [n for n in registry.names() if "megastep" in n]
    assert not [k for k in fe.stats() if "megastep" in k]
