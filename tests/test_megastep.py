"""Decode-megastep semantics (engine_v2._try_megastep + ServingFrontend).

The megastep runs up to K single-token decode iterations in one jitted
device program; these tests pin the contract that makes it safe to turn
on: token streams are EXACTLY the stepwise loop's (argmax parity for
K ∈ {1, 8, 32} — the ISSUE acceptance bar), EOS retires a row mid-window
without trailing garbage, retirement/cancellation happen at megastep
boundaries, and the sampled-mode RNG stream is invariant to how the
window is chunked (the fused scan splits the rng once per scan slot,
dead or not, and megastep scan lengths are pow2 buckets). generate()
is one more client of the same window.

All deterministic under JAX_PLATFORMS=cpu (conftest forces it)."""

import numpy as np
import pytest
import jax

from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
from deepspeed_tpu.models.llama import llama3_config
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.serving import ServingFrontend
from deepspeed_tpu.telemetry.registry import registry

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


def _serve(devices, megastep, prompts, max_new, eos=None, mode=("argmax",),
           adaptive=False, **fe_over):
    """One frontend run on a FRESH engine (same params_key → identical
    weights across runs); returns [(tokens_out, finish_reason), ...]."""
    eng = _engine(devices)
    fe = ServingFrontend(eng, enable_prefix_cache=False, mode=mode,
                         megastep_tokens=megastep,
                         megastep_adaptive=adaptive, **fe_over)
    if mode[0] == "sample":
        eng._temperature = 0.7
    max_new = ([max_new] * len(prompts)
               if isinstance(max_new, int) else max_new)
    reqs = [fe.submit(p, max_new_tokens=m, eos_token_id=eos)
            for p, m in zip(prompts, max_new)]
    fe.run_until_idle()
    return [(list(r.tokens_out), r.finish_reason) for r in reqs]


# ---------------------------------------------------------------------------
# argmax parity (the acceptance bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 8, 32])
def test_megastep_argmax_parity(devices, k):
    prompts = _prompts(3)
    base = _serve(devices, 0, prompts, 12)
    assert all(len(t) == 12 and r == "length" for t, r in base)
    got = _serve(devices, k, prompts, 12)
    assert got == base


def test_megastep_emits_through_counters(devices):
    """The K=32 run must actually take the fused path (parity alone would
    also pass if megasteps silently fell back to stepwise)."""
    launches0 = registry.counter("dispatch/megastep_launches").value
    tokens0 = registry.counter("dispatch/megastep_tokens").value
    _serve(devices, 32, _prompts(3), 12)
    assert registry.counter("dispatch/megastep_launches").value > launches0
    # 3 rows x 12 tokens: 1 from prefill, 11 per row device-resident
    assert registry.counter("dispatch/megastep_tokens").value - tokens0 \
        == 33


# ---------------------------------------------------------------------------
# EOS mid-megastep
# ---------------------------------------------------------------------------

def test_megastep_eos_early_exit(devices):
    prompts = _prompts(3)
    base = _serve(devices, 0, prompts, 12)
    # pick an eos id the FIRST request emits mid-stream so the megastep
    # row dies inside the window, not at its edge
    eos = base[0][0][2]
    b = _serve(devices, 0, prompts, 12, eos=eos)
    m = _serve(devices, 8, prompts, 12, eos=eos)
    assert m == b
    assert m[0][0][-1] == eos and m[0][1] == "eos"
    assert len(m[0][0]) == 3          # tokens through the eos, nothing after


# ---------------------------------------------------------------------------
# retirement / cancellation at megastep boundaries
# ---------------------------------------------------------------------------

def test_megastep_staggered_retirement(devices):
    """Budgets straddling the window size retire at different boundaries;
    survivors keep decoding with their KV intact."""
    prompts = _prompts(3)
    budgets = [4, 9, 17]
    base = _serve(devices, 0, prompts, budgets)
    got = _serve(devices, 8, prompts, budgets)
    assert got == base
    assert [len(t) for t, _ in got] == budgets


def test_megastep_cancel_at_boundary(devices):
    eng = _engine(devices)
    fe = ServingFrontend(eng, enable_prefix_cache=False, megastep_tokens=8,
                         megastep_adaptive=False)
    req = fe.submit(_prompts(1)[0], max_new_tokens=64)
    it = fe.stream(req)
    got = [next(it) for _ in range(10)]
    fe.cancel(req)
    assert list(it) == req.tokens_out[10:]       # drains, then stops
    assert req.state.value == "cancelled"
    assert len(req.tokens_out) < 64
    # the flushed row released its slot and pages
    assert req.uid not in eng.state.seqs
    assert eng.state.allocator.free_blocks == ENG_CFG["num_blocks"]


# ---------------------------------------------------------------------------
# sampled-mode RNG-stream consistency
# ---------------------------------------------------------------------------

def test_megastep_sampled_rng_chunk_invariance(devices):
    """One K=8 window and two K=4 windows must sample the SAME tokens:
    the fused scan splits the rng once per scan slot and megastep scan
    lengths are exact pow2 buckets, so 8 = 4 + 4 splits line up. (Budget
    9 = 1 prefill token + 8 decode tokens keeps every window pow2.)"""
    prompts = _prompts(1)
    a = _serve(devices, 8, prompts, 9, mode=("sample", 0, False))
    b = _serve(devices, 4, prompts, 9, mode=("sample", 0, False))
    assert a == b
    assert len(a[0][0]) == 9
    # ...and both match the fully stepwise sample stream: 1 + 8 splits
    c = _serve(devices, 0, prompts, 9, mode=("sample", 0, False))
    assert a == c


# ---------------------------------------------------------------------------
# config plumbing + K selection
# ---------------------------------------------------------------------------

def test_megastep_config_plumbing(devices):
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    eng = _engine(devices)
    cfg = DeepSpeedTPUConfig(serving={"megastep_tokens": 16,
                                      "megastep_adaptive": False})
    fe = ServingFrontend(eng, config=cfg)
    assert fe.megastep_tokens == 16 and fe.megastep_adaptive is False
    # explicit kwarg wins over the config block
    fe2 = ServingFrontend(eng, config=cfg, megastep_tokens=4)
    assert fe2.megastep_tokens == 4
    fe3 = ServingFrontend(eng, config={"serving": {"megastep_tokens": 2}})
    assert fe3.megastep_tokens == 2
    with pytest.raises(ValueError, match="megastep_tokens"):
        ServingFrontend(eng, megastep_tokens=-1)


def test_pick_megastep_policy(devices):
    """K shrinks toward 1 on pending prefill work and caps at the
    shallowest remaining budget when the queue is non-empty."""
    eng = _engine(devices, max_sequences=2)
    fe = ServingFrontend(eng, enable_prefix_cache=False, megastep_tokens=32,
                         megastep_adaptive=False)
    assert fe._pick_megastep(0.0) == 1            # nothing running
    r1 = fe.submit(_prompts(1)[0], max_new_tokens=20)
    fe.step()                                     # admit + first prefill
    dec, pre = fe.policy.decode_backlog(eng.state)
    if pre:                                       # prompt still prefilling
        assert fe._pick_megastep(fe.clock()) == 1
    while eng.state.seqs[r1.uid].pending != 1:
        fe.step()
    k_free = fe._pick_megastep(fe.clock())
    assert 1 < k_free <= 20 - len(r1.tokens_out)
    # fill both sequence slots, then queue a third request: the megastep
    # must now stop at the shallowest remaining budget (admission point)
    r2 = fe.submit(_prompts(2, seed=1)[1], max_new_tokens=3)
    fe.step()                                     # admit r2, advance
    while eng.state.seqs.get(r2.uid) is None or \
            eng.state.seqs[r2.uid].pending != 1:
        fe.step()
    fe.submit(_prompts(1, seed=2)[0], max_new_tokens=8)   # queued (no slot)
    k_gated = fe._pick_megastep(fe.clock())
    shallowest = min(20 - len(r1.tokens_out), 3 - len(r2.tokens_out))
    assert k_gated <= max(1, shallowest)
    fe.run_until_idle()


# ---------------------------------------------------------------------------
# stream() stall handling (busy-spin fix)
# ---------------------------------------------------------------------------

def test_stream_stall_raises_with_context(devices):
    from deepspeed_tpu.serving.request import Request
    eng = _engine(devices)
    fe = ServingFrontend(eng, enable_prefix_cache=False)
    orphan = Request(prompt=[1, 2, 3])            # never submitted
    it = fe.stream(orphan, poll_interval=0.001, stall_timeout=0.05)
    with pytest.raises(RuntimeError, match="queue_depth=0"):
        list(it)


# ---------------------------------------------------------------------------
# dead-iteration waste surfacing
# ---------------------------------------------------------------------------

def test_dead_steps_counter_and_note(devices):
    from deepspeed_tpu.telemetry import explain
    eng = _engine(devices)
    scan0 = registry.counter("dispatch/scan_steps").value
    dead0 = registry.counter("dispatch/dead_steps").value
    # generate()'s decode window is a megastep, its scan a power of two:
    # 5 decode steps after the first token → 8 slots, 3 dead iterations
    eng.generate([_prompts(1)[0]], max_new_tokens=6)
    scan_d = registry.counter("dispatch/scan_steps").value - scan0
    dead_d = registry.counter("dispatch/dead_steps").value - dead0
    assert scan_d == 8 and dead_d == 3
    w = explain.dispatch_waste()
    assert w is not None and 0.0 < w["dead_fraction"] < 1.0
    # the process-wide fraction includes other tests' launches; the note
    # only fires above 10% waste, and must name the knob when it does
    note = explain.dispatch_note(threshold=0.10)
    if w["dead_fraction"] > 0.10:
        assert note is not None and "megastep_tokens" in note
    assert explain.dispatch_note(threshold=1.0) is None
