#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that deepspeed_tpu still starts on the chip.

Drives the repo's two main paths once, through the entry points a user
calls, at the full width of one model the repo supports (the Llama ``1b``
preset as ``bench.py`` trains it: hidden 2048, 16 layers, 16 query / 8 KV
heads of 128, FFN 8192, vocabulary 128,256, tied embeddings; random weights
from ``--seed``):

- **server** — ``RaggedInferenceEngineTPU`` under ``ServingFrontend``: six
  requests of mixed prompt length streamed to completion through the
  default frontend; every generated token is checked against the plain
  ``models/transformer.py`` forward;
- **trainer** — ``ds.build_mesh`` → ``ds.initialize`` → ``train_batch`` with
  ``bench.dense_train_config`` at sequence 2048, global batch 8, on one
  repeated batch: losses fall from ≈ ln(vocab), state lives on the chip,
  the Pallas flash kernel is in the step program, nothing compiles after
  warm-up.

``--chips 4`` runs the path that exists only across chips and what it is
compared with, and no other phase: the same trainer with ZeRO-3 on a
``data=4`` mesh against the one-chip run of the same global batch and seed.

One JSON object per phase on stdout; the LAST line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check raises: the process exits non-zero and that line is never
printed. Without a TPU the script refuses to start (exit 2, nothing on
stdout). ``--rehearse-cpu`` is the only way to a CPU run: tiny widths,
Pallas flash in interpret mode, and it reports ``"platform": "cpu"``.

Everything runs in this one process: a chip belongs to one process at a
time, so no phase may live in a child.
"""

import argparse
import gc
import json
import math
import os
import re
import sys
import time

#: bf16 tolerances this script holds the chip to (stated, not tuned per run)
#: — a generated token may differ from the reference argmax only when the
#: reference scores it within this many logit units of its maximum
NEAR_TIE_LOGITS = 0.25
#: ... and at least this share of generated tokens are the exact argmax.
#: A sanity floor, not the test: with random weights the reference's top two
#: logits are closer than bf16 rounding for roughly one token in ten (0.899
#: exact on the chip in the first run of PR 25, every miss a near-tie)
MIN_EXACT_ARGMAX = 0.75
#: per-step |loss(4 chips, ZeRO-3) - loss(1 chip)| on the same batch + seed
ZERO3_LOSS_ATOL = 0.05
#: first-step loss of a random-init model: |loss - ln(vocab)| below this
FIRST_LOSS_ATOL = 0.5

#: serving geometry — the same on the chip and in the rehearsal (only the
#: model differs), so the CPU rehearsal walks the very schedule the chip
#: run walks. Prompts of 1, 1, 1, 2, 3 and 3 prefill chunks; budgets chosen
#: so that every request has 16 tokens left when the last prefill chunk is
#: done and they all finish together (fewest distinct programs to compile)
PROMPT_LENS = (24, 60, 130, 300, 520, 700)
NEW_TOKENS = (19, 19, 19, 18, 17, 17)
SERVE_CONFIG = {"dtype": "bfloat16", "max_seq_len": 1024}


class SmokeFailure(RuntimeError):
    """A check did not hold; the run is a failure."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 (default): server + trainer on one chip; 4: "
                         "only the ZeRO-3 data=4 trainer and its one-chip "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny-width rehearsal on the CPU backend (control "
                         "flow only; reports platform cpu, never tpu)")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# bookkeeping: compiles and the persistent cache
# ---------------------------------------------------------------------------

class CompileLedger:
    """Counts what jax compiles: backend compile requests (the repo's
    ``compile_monitor``), persistent-cache requests and hits (jax's own
    monitoring events), and the repo's per-function trace counters."""

    def __init__(self):
        import jax
        from deepspeed_tpu.telemetry import compile_monitor
        self._mon = compile_monitor
        self._mon.install()
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        s = self._mon.summary()
        ev = s["events"].get("backend_compile_duration",
                             {"count": 0, "time_ms": 0.0})
        return {"compiles": int(ev["count"]),
                "compile_s": ev["time_ms"] / 1e3,
                "traces": sum(s["functions"].values()),
                "cache_requests": self.cache_requests,
                "cache_hits": self.cache_hits}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        d = {k: b[k] - a[k] for k in a}
        d["compile_s"] = round(d["compile_s"], 2)
        d["cache_misses"] = d["cache_requests"] - d["cache_hits"]
        return d


def hbm(devices) -> list:
    """Per-device memory stats (None where the backend reports none)."""
    out = []
    for d in devices:
        s = d.memory_stats()
        out.append(None if not s else {
            "bytes_in_use": int(s.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(s.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(s.get("bytes_limit", 0))})
    return out


def kernels_in(text: str, names) -> dict:
    """Which named Pallas kernels a compiled program's text carries, and
    how many Mosaic custom calls it has in all. A kernel's ``name=`` is a
    component of its op_name (``.../flash_fwd/pallas_call``; under autodiff
    ``.../transpose(jvp(flash_bwd_dq))/pallas_call``)."""
    ops = re.findall(r'op_name="([^"]*pallas_call[^"]*)"', text)
    return {"tpu_custom_calls":
            text.count('custom_call_target="tpu_custom_call"'),
            "named": sorted(n for n in names if any(
                re.search(rf"(?<!\w){n}(?!\w)", op) for op in ops))}


FLASH_KERNELS = ("flash_fwd", "flash_fwd_xl", "flash_bwd_dq",
                 "flash_bwd_dkv", "flash_bwd_dq_xl", "flash_bwd_dkv_xl")
PAGED_KERNELS = ("paged_attn", "paged_attn_lse")


def model_for(rehearse: bool, seq: int):
    from deepspeed_tpu.models.llama import llama3_config
    if rehearse:
        # tiny widths, but head_dim 128 so the same kernels are selected
        return llama3_config(
            "tiny", hidden_size=256, num_heads=2, num_kv_heads=1,
            intermediate_size=512, num_layers=2, vocab_size=512,
            max_seq_len=seq, tie_embeddings=True)
    return llama3_config("1b", max_seq_len=seq, tie_embeddings=True)


def widths(model) -> dict:
    return {"hidden": model.hidden_size, "layers": model.num_layers,
            "heads": model.num_heads, "kv_heads": model.kv_heads,
            "head_dim": model.head_dim, "ffn": model.intermediate_size,
            "vocab": model.vocab_size,
            "params_b": round(model.num_params() / 1e9, 3)}


# ---------------------------------------------------------------------------
# server phase
# ---------------------------------------------------------------------------

def reference_gaps(model, params, prompts, outs):
    """Teacher-forced check of every generated token against the repo's
    plain forward (``models/transformer.py``, reference dot-product
    attention, no paged cache): for each generated token (all requests,
    flattened), how far the reference scores it below its own argmax
    (0.0 = it IS the argmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models.transformer import forward_hidden, lm_logits

    n = len(prompts)
    seqs = [list(p) + list(o) for p, o in zip(prompts, outs)]
    t = max(len(s) for s in seqs)
    new = max(len(o) for o in outs)
    toks = np.zeros((n, t), np.int32)
    pos = np.zeros((n, new), np.int32)
    for i, (p, o, s) in enumerate(zip(prompts, outs, seqs)):
        toks[i, :len(s)] = s
        # logits at position len(p)-1+j predict generated token j
        pos[i, :len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))

    def ref(params, toks, pos):
        x, _aux = forward_hidden(model, params, toks)
        xg = jnp.take_along_axis(x, pos[:, :, None], axis=1)
        return lm_logits(model, params, xg)             # [n, new, V] fp32

    logits = np.asarray(jax.jit(ref)(params, toks, pos))
    gaps = []
    for i, o in enumerate(outs):
        rows = logits[i, :len(o)]
        gaps.append(rows.max(axis=-1) - rows[np.arange(len(o)), o])
    return np.concatenate(gaps)


def serve_pass(fe, prompts, label):
    """Submit every prompt, stream to completion, check the counts."""
    streamed = [[] for _ in prompts]
    t0 = time.perf_counter()
    reqs = [fe.submit(p, max_new_tokens=m, stream_cb=streamed[i].append)
            for i, (p, m) in enumerate(zip(prompts, NEW_TOKENS))]
    fe.run_until_idle()
    wall = time.perf_counter() - t0
    for i, (r, m) in enumerate(zip(reqs, NEW_TOKENS)):
        check(r.finish_reason == "length" and r.retries == 0,
              f"{label}: request {i} ended {r.finish_reason!r} after "
              f"{r.retries} retries")
        check(len(r.tokens_out) == m,
              f"{label}: request {i} produced {len(r.tokens_out)} tokens, "
              f"asked for {m}")
        check(streamed[i] == r.tokens_out,
              f"{label}: request {i} streamed tokens differ from its output")
    return [list(r.tokens_out) for r in reqs], wall


def server_phase(args, ledger, device) -> None:
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.serving import ServingFrontend
    from deepspeed_tpu.telemetry.registry import registry

    on_tpu = device.platform == "tpu"
    t_phase = time.perf_counter()
    c0 = ledger.snapshot()
    ds.build_mesh(data=1, devices=[device])
    model = model_for(args.rehearse_cpu, SERVE_CONFIG["max_seq_len"])
    eng = RaggedInferenceEngineTPU(model, dict(SERVE_CONFIG),
                                   rng=jax.random.PRNGKey(args.seed))
    jax.block_until_ready((eng.params, eng.arena))
    init_s = time.perf_counter() - t_phase
    cfg = eng.config
    if on_tpu:
        check(pa.supported(model.head_dim, cfg.block_size),
              "ops/paged_attention.supported is False on the TPU")
        check(eng.use_pallas, "engine.use_pallas is off on the TPU")
    on_dev = {d for leaf in jax.tree.leaves((eng.params, eng.arena))
              for d in leaf.devices()}
    check(on_dev == {device},
          f"serving params/arena live on {on_dev}, expected {device}")

    rng = np.random.default_rng(args.seed)

    def draw():
        return [[int(t) for t in rng.integers(0, model.vocab_size, size=n)]
                for n in PROMPT_LENS]

    emit({"phase": "server", "model": widths(model),
          "engine": {"dtype": cfg.dtype, "num_blocks": cfg.num_blocks,
                     "block_size": cfg.block_size,
                     "max_seq_len": cfg.max_seq_len,
                     "prefill_chunk": cfg.prefill_chunk,
                     "max_batch_tokens": cfg.max_batch_tokens,
                     "arena_bytes": int(sum(
                         a.nbytes for a in eng.arena.values()))},
          "use_pallas": bool(eng.use_pallas),
          "prompt_lens": list(PROMPT_LENS),
          "new_tokens": list(NEW_TOKENS),
          "engine_init_s": round(init_s, 1),
          "engine_init_compile": ledger.delta(c0, ledger.snapshot())})
    # the default frontend: the decode step program selects the Pallas
    # paged kernel, the pump launches one step ahead of its fetch
    fe = ServingFrontend(eng)
    warm_prompts = draw()
    w0 = ledger.snapshot()
    _outs, warm_wall = serve_pass(fe, warm_prompts, "serve/warm-up")
    w1 = ledger.snapshot()
    hc0 = registry.counter("dispatch/host_calls").value
    prompts = draw()                # same lengths → same schedule, no
    outs, wall = serve_pass(fe, prompts, "serve")       # prefix-cache hits
    w2 = ledger.snapshot()
    after = ledger.delta(w1, w2)
    # the reference runs outside the measured window (it compiles)
    flat = reference_gaps(model, eng.params, prompts, outs)
    exact = float((flat == 0.0).mean())
    emit({
        "phase": "server/serve",
        "requests": len(outs), "tokens": sum(len(o) for o in outs),
        "host_calls": int(
            registry.counter("dispatch/host_calls").value - hc0),
        "warmup_wall_s": round(warm_wall, 3),
        "warmup_compile": ledger.delta(w0, w1),
        "wall_s": round(wall, 3),
        "compiles_after_warmup": after["compiles"],
        "traces_after_warmup": after["traces"],
        "exact_argmax_share": round(exact, 4),
        "max_gap_below_ref_argmax": round(float(flat.max()), 5),
        "mean_gap_of_non_argmax": round(float(
            flat[flat > 0].mean()) if (flat > 0).any() else 0.0, 5),
        "near_tie_tolerance": NEAR_TIE_LOGITS,
        "first_tokens": [o[0] for o in outs]})
    check(after["compiles"] == 0 and after["traces"] == 0,
          f"server/serve: {after['compiles']} compile(s), "
          f"{after['traces']} trace(s) after warm-up")
    check(bool(np.isfinite(flat).all()),
          "server/serve: non-finite reference logits")
    check(float(flat.max()) <= NEAR_TIE_LOGITS,
          f"server/serve: a generated token is {flat.max():.4f} "
          f"logits below the reference argmax (tolerance "
          f"{NEAR_TIE_LOGITS})")
    check(exact >= MIN_EXACT_ARGMAX,
          f"server/serve: only {exact:.3f} of generated tokens are "
          f"the reference argmax (need {MIN_EXACT_ARGMAX})")
    fe.close()

    # what each compiled program carries (re-lowered from the live arrays:
    # same module, so with the persistent cache on this is a cache hit)
    programs, missing, kinds = {}, [], set()
    for (nb, cb, mode, fresh), fn in sorted(eng._step_fns.items(), key=str):
        packed = jax.ShapeDtypeStruct(
            (eng._packed_len(nb, cb),), np.int32)
        text = fn.lower(eng.params, eng.arena, packed,
                        eng._rng_dev).compile().as_text()
        kind = "decode" if cb == 1 else str(fresh)
        kinds.add(kind)
        found = kernels_in(text, FLASH_KERNELS + PAGED_KERNELS)
        programs[f"step n={nb} c={cb} {kind}"] = found
        # fresh prefill and the within-chunk half of a continuation run
        # the flash forward; what reads the arena in one pass (stepwise
        # decode) runs the paged kernel
        want = "paged_attn" if kind in ("decode", "False") else "flash_fwd"
        if want not in found["named"]:
            missing.append(f"step n={nb} c={cb} {kind}: no {want}")
    emit({"phase": "server/programs", "programs": programs,
          "compile": ledger.delta(c0, ledger.snapshot()),
          "hbm": hbm([device]),
          "seconds": round(time.perf_counter() - t_phase, 1)})
    check({"fresh", "split", "decode"} <= kinds,
          f"server: expected a fresh-prefill, a continuation and a decode "
          f"step program, compiled {sorted(kinds)}")
    if on_tpu:
        check(not missing,
              f"server: a reference path took a kernel's place: {missing}")


# ---------------------------------------------------------------------------
# trainer phase
# ---------------------------------------------------------------------------

def train_run(args, ledger, devices, label, warmup=2, steps=4) -> dict:
    """``build_mesh`` → ``initialize`` → ``train_batch`` on ``devices``
    (data-parallel over all of them; ZeRO-3 when more than one), one
    repeated batch. Returns the phase record; raises on a failed check."""
    import jax
    import numpy as np

    import bench
    import deepspeed_tpu as ds
    from deepspeed_tpu.telemetry import compile_monitor, explain

    on_tpu = devices[0].platform == "tpu"
    n_dev = len(devices)
    seq, batch = (256, 8) if args.rehearse_cpu else (2048, 8)
    t_phase = time.perf_counter()
    c0 = ledger.snapshot()
    ds.build_mesh(data=n_dev, devices=devices)
    model = model_for(args.rehearse_cpu, seq)
    # the TPU configuration in the rehearsal too (bf16, bf16 state, remat,
    # chunked CE); "auto" would ask the backend, so the rehearsal names the
    # kernel and jax interprets it
    config = bench.dense_train_config(n_dev, batch, seq, on_tpu=True)
    if args.rehearse_cpu:
        config["attention_impl"] = "pallas_flash"
    engine, *_ = ds.initialize(model=model, config=config,
                               rng=jax.random.PRNGKey(args.seed))
    jax.block_until_ready((engine.params, engine.opt_state))
    init_s = time.perf_counter() - t_phase
    gb = int(engine.config.train_batch_size)
    check(gb == batch, f"{label}: global batch {gb}, wanted {batch}")
    data = {"input_ids": np.random.default_rng(args.seed).integers(
        0, model.vocab_size, size=(gb, seq), dtype=np.int32)}

    # where the state lives
    leaves = jax.tree.leaves((engine.params, engine.opt_state))
    platforms = {d.platform for leaf in leaves for d in leaf.devices()}
    check(platforms == {devices[0].platform},
          f"{label}: params/optimizer state live on {platforms}")
    total = sum(leaf.nbytes for leaf in leaves)
    per_dev = {d.id: 0 for d in devices}
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    share = {k: round(v / total, 4) for k, v in per_dev.items()}
    hbm_after_init = hbm(devices)

    losses, step_s = [], []
    t_warm = time.perf_counter()
    for _ in range(warmup):
        losses.append(float(engine.train_batch(iter([data]))))
    warm_s = time.perf_counter() - t_warm
    c1 = ledger.snapshot()
    traces1 = compile_monitor.retrace_count("engine/fused_step")
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(iter([data]))))
        step_s.append(time.perf_counter() - t0)
    c2 = ledger.snapshot()
    after = ledger.delta(c1, c2)
    retraces = compile_monitor.retrace_count("engine/fused_step") - traces1

    # the step program, re-lowered from shapes (same module: a cache hit
    # when the persistent cache is on) — kernels, collectives, memory
    compiled = engine._fused_step.lower(
        *explain._abstract_train_args(engine, data)).compile()
    text = compiled.as_text()
    found = kernels_in(text, FLASH_KERNELS)
    coll = {op: int(s["count"]) for op, s in
            explain.collective_stats_from_hlo(text).items()}
    mem = compiled.memory_analysis()
    record = {
        "phase": label, "model": widths(model), "devices": n_dev,
        "zero_stage": int(engine.zero_stage), "seq": seq,
        "global_batch": gb, "dtype": str(engine.config.compute_dtype),
        "initialize_s": round(init_s, 1),
        "attention_impl": config["attention_impl"],
        "losses": [round(x, 4) for x in losses],
        "warmup_steps": warmup, "warmup_s": round(warm_s, 2),
        "step_s": [round(x, 4) for x in step_s],
        "compiles_after_warmup": after["compiles"],
        "fused_step_retraces_after_warmup": retraces,
        "kernels": found, "collectives": coll,
        "step_program_bytes": {
            "arguments": int(mem.argument_size_in_bytes),
            "outputs": int(mem.output_size_in_bytes),
            "temporaries": int(mem.temp_size_in_bytes),
            "aliased": int(mem.alias_size_in_bytes)},
        "state_bytes": int(total), "state_share_per_device": share,
        "hbm_after_init": hbm_after_init, "hbm": hbm(devices),
        "compile": ledger.delta(c0, ledger.snapshot()),
        "seconds": round(time.perf_counter() - t_phase, 1)}
    emit(record)
    if n_dev > 1:
        check(all(0.2 <= s <= 0.35 for s in share.values()),
              f"{label}: state bytes per device {share} of {total} — "
              f"expected about a quarter on each, the whole on none")
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite loss in {losses}")
    check(abs(losses[0] - math.log(model.vocab_size)) < FIRST_LOSS_ATOL,
          f"{label}: first loss {losses[0]:.4f}, expected about "
          f"ln({model.vocab_size}) = {math.log(model.vocab_size):.4f}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall on a repeated batch: {losses}")
    check(after["compiles"] == 0 and retraces == 0,
          f"{label}: {after['compiles']} compile(s) and {retraces} "
          f"retrace(s) of the fused step after warm-up")

    if on_tpu:
        check({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
              <= set(found["named"]),
              f"{label}: the fused step has no Pallas flash forward + "
              f"backward (found {found}) — a reference attention took "
              f"its place")
        if n_dev > 1:
            check(coll.get("all-gather", 0) > 0 and
                  coll.get("reduce-scatter", 0) > 0,
                  f"{label}: the ZeRO-3 step has collectives {coll}, "
                  f"expected all-gather and reduce-scatter")
    return record


def release(devices) -> None:
    """Drop what the finished phase left on the devices."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    emit({"phase": "release", "hbm": hbm(devices)})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse_cpu:
        # the rehearsal is a CPU run by construction, and says so
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()
    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke.py: jax found no TPU (platform "
              f"{dev0.platform!r}); refusing to run. The CPU rehearsal is "
              f"explicit: --rehearse-cpu", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, jax reports "
          f"{len(devices)}")

    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    check(get_accelerator().device_name() == dev0.platform,
          f"accelerator is {get_accelerator().device_name()!r} on a "
          f"{dev0.platform!r} device")
    cache_dir = enable_compile_cache()
    ledger = CompileLedger()
    t0 = time.perf_counter()
    emit({"phase": "start", "platform": dev0.platform,
          "device_kind": dev0.device_kind, "devices": len(devices),
          "chips_used": args.chips, "jax": jax.__version__,
          "compile_cache_dir": cache_dir,
          "compile_cache_from_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "note": "this preset's head split (16 query heads of 128) is the "
                  "repo's own, not the published Llama-3.2-1B's (32 of "
                  "64); settling that is ROADMAP B1"})

    if args.chips == 1:
        one = devices[:1]
        server_phase(args, ledger, dev0)
        release(one)
        train_run(args, ledger, one, "trainer")
    else:
        four = devices[:4]
        ref = train_run(args, ledger, four[:1], "trainer_1chip")
        release(four)
        z3 = train_run(args, ledger, four, "trainer_zero3_4chip")
        diffs = [abs(a - b) for a, b in zip(ref["losses"], z3["losses"])]
        emit({"phase": "zero3_vs_1chip", "loss_abs_diff":
              [round(d, 4) for d in diffs], "tolerance": ZERO3_LOSS_ATOL})
        check(z3["zero_stage"] == 3 and ref["zero_stage"] == 0,
              "wrong ZeRO stages in the comparison")
        check(max(diffs) <= ZERO3_LOSS_ATOL,
              f"ZeRO-3 on 4 chips and the 1-chip run disagree: per-step "
              f"|Δloss| {diffs} (tolerance {ZERO3_LOSS_ATOL})")

    total = ledger.snapshot()
    emit({"phase": "done", "seconds": round(time.perf_counter() - t0, 1),
          "compiles": total["compiles"],
          "compile_s": round(total["compile_s"], 1),
          "cache_hits": total["cache_hits"],
          "cache_misses": total["cache_requests"] - total["cache_hits"]})
    emit({"ok": True, "device": {"platform": dev0.platform,
                                 "kind": dev0.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
