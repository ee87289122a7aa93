"""The training runner: ``ds.build_mesh`` → ``ds.initialize`` →
``train_batch`` through the entry points a user calls (after
``chip_smoke.train_run``, which ran on the v5e in PR 25), on a pool of
distinct seeded batches, for a window by the host clock.

The window opens after the warm-up steps and closes at the end of the
first step that ends at or after ``seconds``: the rate is all the tokens of
all completed steps over all of that time, never a partial step. The
float32 reference is asked twice, outside the window: before the first
step, and after the last about what the window's updates left."""

import copy
import math
import time

import numpy as np

from benchmark.lib import flops as flops_lib
from benchmark.lib import model as model_lib
from benchmark.lib import traffic
from benchmark.trace import scopes

#: |loss of the first step - the float32 reference's loss on the same batch
#: at the initial parameters|. The trainer computes in bf16 with bf16
#: chunked logits; at random initialisation the per-token error is a few
#: 1e-3 and largely cancels in the mean over >16K tokens: 1e-5 to 4.6e-4
#: over 27 runs of the two training cells on the chip (PR 27), so three
#: times the worst. A lower precision fails it: weights rounded through
#: float8_e4m3 or int8 (per-tensor scale) move the reference's own loss by
#: +0.0038 / +0.0036 (CPU, 4 layers at these widths, 2 x 512 tokens, paired;
#: PR 27) -- which the 0.004 this constant first had would have passed.
LOSS_ATOL = 0.0015
#: the same comparison AFTER the window: one more step on batch 0 against
#: the reference at the parameters the window's updates left. By then the
#: batches are being memorised, logits are large and bf16's error with
#: them: 0.0010 at a loss of 2.50 (four chips, batch 0 seen twice), 1e-5
#: at a loss of 0.003 (one chip, seen four times; 6 runs), then 0.0010 at
#: 8.28 and 0.0001 at 10.37 (four chips, seeds 202 and 505) (PR 27). Ten
#: times the worst of the three four-chip readings. An attention or gather
#: fault that random weights hide (their attention is near uniform) shows
#: here.
REVISIT_ATOL = 0.01
#: ... and the window's updates have to be IN those parameters. The tokens
#: are uniform random, so nothing but a batch's own gradient, applied and
#: kept, predicts it better than chance (ln vocab): some later visit of a
#: batch -- a step of the window past the first cycle, or the step after it
#: -- must report a loss this many nats under ln(vocab). A step that skips
#: the update, or zeroes the gradient, is faster and never gets there. Why
#: the LEAST over all later visits and not a share of batch 0's first loss:
#: lr 1e-4 from the first step moves nothing for 12 steps and then falls
#: seed by seed. On four chips (28 steps, 13 later visits) the least read
#: 7.39 / 6.18 / 1.68 / under 1.2 in seeds 202 / 505 / 2147483659 / 808,
#: while batch 0 alone, 12 steps after its last visit, read 8.28 / 10.373
#: (chance) / 4.40 / 2.50; on one chip 0.003 (PR 27). Losing PART of the
#: gradient passes, as does a gradient that is wrong and still descends:
#: only a reference backward pass would see those (PERF.md, open
#: questions). A run that made under one and a half cycles of its batches
#: (a short --seconds; run_seconds gives 28 and 51 steps over 16 batches)
#: is not judged, and says so.
BEATS_CHANCE_NATS = 0.5
#: the benchmark's own host span around each call into the trainer
SPAN = "benchmark/train_step"
#: a random-initialised model's first loss is within this of ln(vocab): a
#: sanity bound, the reference is the test. At hidden 4096 with init std
#: 0.02 the untied head's logits have a standard deviation near 1.3, which
#: puts the loss 0.82 above ln 32000 (11.19, chip and reference alike,
#: PR 27); ISSUE.md's 0.5 was a guess from the tied Llama preset
FIRST_LOSS_ATOL = 1.0


def trainer_config(conf: dict, mix: dict, n_dev: int, rehearse: bool) -> dict:
    """The configuration file's ``trainer`` block plus the micro-batch the
    mix's global batch gives each chip."""
    cfg = copy.deepcopy(conf["trainer"])
    gb = int(mix["global_batch"])
    if gb % n_dev:
        raise ValueError(f"global batch {gb} does not divide over "
                         f"{n_dev} chips")
    cfg["train_micro_batch_size_per_gpu"] = gb // n_dev
    if rehearse:
        # "auto" would ask the backend; the rehearsal names the kernel and
        # jax interprets it
        cfg["attention_impl"] = "pallas_flash"
    return cfg


def run(ctx) -> dict:
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.telemetry import tracer

    conf, mix, devices = ctx.conf, ctx.mix, ctx.devices
    reference = ctx.reference
    n_dev = len(devices)
    seq = int(mix["seq_len"]) if not ctx.rehearse else 256
    mix = dict(mix, seq_len=seq)
    ds.build_mesh(data=n_dev, devices=devices)
    model = model_lib.build_model(conf, ctx.rehearse)
    engine, *_ = ds.initialize(
        model=model, config=trainer_config(conf, mix, n_dev, ctx.rehearse),
        rng=model_lib.prng_key(ctx.seed))
    jax.block_until_ready((engine.params, engine.opt_state))
    gb = int(engine.config.train_batch_size)
    if gb != int(mix["global_batch"]):
        raise RuntimeError(f"global batch {gb}, the mix says "
                           f"{mix['global_batch']}")
    want_stage = int(conf["trainer"]["zero_optimization"]["stage"])
    if int(engine.zero_stage) != want_stage:
        raise RuntimeError(f"ZeRO stage {engine.zero_stage}, the "
                           f"configuration says {want_stage}")
    batches = traffic.train_batches(mix, ctx.seed, model.vocab_size)
    widths = reference.Widths.from_hf(
        model_lib.published_keys(conf, ctx.rehearse))
    # useful work: the architecture's own matmul count, from the file
    flops_per_token = flops_lib.train_flops_per_token(
        model, seq, reference.matmul_params_per_token(widths))
    ctx.log({"phase": "initialized", "params": int(model.num_params()),
             "devices": n_dev, "zero_stage": int(engine.zero_stage),
             "seq": seq, "global_batch": gb,
             "flops_per_token": flops_per_token,
             "t": round(time.monotonic() - ctx.t_start, 2)})

    # the reference, before the first step changes (and donates) the
    # parameters: its loss on batch 0 at the initial parameters
    ref_loss = reference.loss(widths, engine.params, batches[0], devices[0])

    def step(i: int) -> float:
        data = {"input_ids": batches[i % len(batches)]}
        with ctx.annotate(SPAN):
            # float() fetches the loss: the step has ended on the device
            return float(engine.train_batch(iter([data])))

    losses = [step(i) for i in range(int(mix["warmup_steps"]))]
    first_gap = abs(losses[0] - ref_loss)
    ctx.log({"phase": "warm", "reference_loss": ref_loss,
             "first_loss": losses[0], "abs_gap": first_gap,
             "tolerance": LOSS_ATOL,
             "t": round(time.monotonic() - ctx.t_start, 2)})

    trace_steps = int(mix["trace_steps"])
    if ctx.trace:
        tracer.configure(enabled=True, jax_annotations=True)
        tracer.clear()
    c0 = ctx.ledger.snapshot()
    ends, window_losses = [], []
    i = len(losses)
    t_open = ctx.open_window()
    traced = None
    while True:
        if ctx.trace and len(ends) == 1:
            ctx.start_device_trace()
            traced = [time.monotonic(), None, 0]
        window_losses.append(step(i))
        i += 1
        now = time.monotonic()
        ends.append(now)
        if traced is not None and traced[1] is None:
            traced[2] += 1
            if traced[2] >= trace_steps:
                traced[1] = now
                ctx.stop_device_trace()
        if now - t_open >= ctx.seconds:
            break
    if traced is not None and traced[1] is None:
        traced[1] = time.monotonic()
        ctx.stop_device_trace()
    t_close = ends[-1]
    compiles = ctx.ledger.delta(c0, ctx.ledger.snapshot())
    window_s = t_close - t_open
    steps = len(ends)
    tokens = steps * gb * seq
    rate = tokens / window_s / n_dev
    nonfinite = sum(1 for x in window_losses if not math.isfinite(x))
    step_s = np.diff([t_open] + ends)
    med = float(np.median(step_s))
    ctx.log({"phase": "window", "steps": steps, "window_s": window_s,
             "tokens": tokens, "step_s_median": med,
             "step_s_min": float(step_s.min()),
             "step_s_max": float(step_s.max()),
             # a run that reads far off says here which steps were slow
             "slow_steps": [[int(k), float(x)] for k, x in enumerate(step_s)
                            if x > 1.02 * med][:16],
             "loss_first": window_losses[0], "loss_last": window_losses[-1],
             "compiles_in_window": compiles,
             "samples": {"train_tokens_per_s_per_chip": steps}})

    # after the window: what did its updates leave? Batch 0 again, the
    # reference first (the step donates the parameters)
    visits = len(range(0, i, len(batches)))
    ref_after = reference.loss(widths, engine.params, batches[0], devices[0])
    loss_after = step(0)
    after_gap = abs(loss_after - ref_after)
    # every later visit of a batch (the first len(batches) steps are first
    # visits; batch 0 was trained on before the step after the window)
    later = (losses + window_losses)[len(batches):] + [loss_after]
    chance = math.log(model.vocab_size)
    judged = i >= len(batches) * 3 // 2
    ctx.log({"phase": "revisit", "batch_0_visits_before": visits,
             "reference_loss": ref_after, "loss": loss_after,
             "abs_gap": after_gap, "tolerance": REVISIT_ATOL,
             "losses": [round(x, 4) for x in losses + window_losses],
             "least_later_visit_loss": min(later), "chance": chance,
             "beats_chance_nats": BEATS_CHANCE_NATS, "judged": judged,
             "t": round(time.monotonic() - ctx.t_start, 2)})

    checks = {
        "loss_matches_reference": first_gap <= LOSS_ATOL,
        "first_loss_near_ln_vocab":
            abs(losses[0] - math.log(model.vocab_size)) < FIRST_LOSS_ATOL,
        "all_losses_finite": nonfinite == 0 and
            all(math.isfinite(x) for x in losses + [loss_after]),
        "no_compile_in_window": compiles["compiles"] == 0,
        "revisit_matches_reference": after_gap <= REVISIT_ATOL,
        # the rehearsal's tiny widths learn slowly and its few steps may
        # never come back to a batch: there batch 0's loss only has to fall
        "a_later_visit_beats_chance":
            loss_after < losses[0] if ctx.rehearse
            else not judged or min(later) <= chance - BEATS_CHANCE_NATS,
    }
    ctx.log({"phase": "checks", **checks})
    per_chip_seqs = gb // n_dev
    return {
        "correct": all(checks.values()),
        "attempted": steps, "failed": nonfinite,
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "span_name": SPAN,
        # an idle gap of the device goes to the innermost of these
        "gap_spans": [SPAN] + [n for n in scopes.PROGRAM_SPANS
                               if n.startswith("train/")],
        "facts": {
            "kind": "train", "model": model, "seq_len": seq,
            "global_batch": gb, "chips": n_dev,
            "tokens_per_s_per_chip": rate,
            "flops_per_token": flops_per_token,
            "flash_flops_per_step_per_chip":
                flops_lib.flash_train_flops_per_step(model, seq,
                                                     per_chip_seqs),
            "traced_steps": traced[2] if traced else 0,
            "traced_host_window": tuple(traced[:2]) if traced else None,
        },
    }
