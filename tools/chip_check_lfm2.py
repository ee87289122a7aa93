"""A chip-side check of LFM2-MoE's stack outside the benchmark's cell (run it
through ``chiprun --chips 1 --timeout 2400 -- python3
tools/chip_check_lfm2.py --kernel``; on the CPU add ``--rehearse`` for tiny
widths and the control flow alone). After ``tools/chip_check_jamba.py``.

What the cell's own ``correct`` cannot show is shown here in FLOAT32: the
configuration is the cell's (``benchmark/configs/lfm2-24b-a2b-l40-e8-serve.
json``, the published widths, the share) cut to its first ``--layers``
layers (8: ``conv conv attention conv conv conv attention conv``, two dense
and six sparse — float32 weights at all 40 are 15 GB), the engine block the
cell's but ``dtype float32`` and a quarter of its pages, ``highest`` matmul
precision, so the programs are the timed path's 64-row ones with the paired
Pallas reader in them. First every slot of the convolution pools is DIRTIED
(64 throwaway sequences prefilled and flushed). Then two JUDGED sequences —
a prompt of ``--prompt`` tokens (600: a fresh chunk, then split launches
that carry its tails and read its 64-wide pages through the kernel) and one
of 16, each followed by ``--steps`` teacher-forced random tokens — run
beside 40 background sequences that decode a random token a step. Every
position's LOGITS from the prompt's last on are held against the plain
float32 reference's FULL FORWARD of the same tokens
(``benchmark/reference/lfm2_moe_decoder.py``): float32 on both sides, so no
routing flips and EVERY position is judged, by ``F32_LOGIT_DIFF_LIMIT``.
Then the same walk through programs WRONG in one way each, which must not
pass: a row at position 0 left with what its slot held, the q / k head
norms dropped, bf16 weights. ``--skew`` (PR 57): the selection bias sends
EVERY token to the first held expert too, in the program and in the
reference alike, so a split launch of the long prompt (a chunk of 128
beside the 41 one-token rows) fills that expert's 128 rows and takes a
second round of the many-token dispatch (``parallel/moe.
held_experts_moe_layer``); ``--only none`` runs no control.

``--kernel`` first times the split step's history read at the cell's shapes
— 64 rows of one query and a chunk group ``[8, 128]``, 8 KV heads of 64
unpadded in the pools — through the paired Pallas kernel and through the XLA
reader, and holds the two to each other (``--phases none``: that alone). One
JSON object a line; the last says ``ok``."""

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = "lfm2-24b-a2b-l40-e8-serve"
#: the most any judged position's largest logit difference may be, float32
#: program against float32 reference at the published widths (the logits
#: spread by about 1.0). Between the two readings on the v5e (PERF.md §6,
#: PR 56): the sound program's and the least of the three controls'
F32_LOGIT_DIFF_LIMIT = 2e-3


def emit(obj):
    print(json.dumps(obj), flush=True)


def kernel_check(args):
    """The history read of ONE attention layer at the cell's shapes, paired
    Pallas kernel | XLA reader: ms a call and the largest difference."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(args.seed)
    n, mb, bs, kvh, h, d = (4, 4, 16, 2, 4, 64) if args.rehearse else \
        (64, 32, 128, 8, 32, 64)
    pages = n * mb
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(pages + 1, bs, kvh * d)),
                                  jnp.bfloat16) for _ in range(2))
    table = jnp.asarray(rng.permutation(pages).reshape(n, mb), jnp.int32)
    # contexts as the chat mix holds them: lognormal around 600 tokens
    starts = jnp.asarray(np.clip(rng.lognormal(np.log(600), 0.8, n), 16,
                                 mb * bs - 130).astype(np.int32)
                         if not args.rehearse else [20, 3, 40, 33])
    out = {"phase": "kernel", "rows": n, "kv_heads": kvh, "head_dim": d,
           "context_tokens": int(starts.sum())}
    kw = dict(interpret=True) if args.rehearse else {}
    for name, rows, c in (("one_query", n, 1), ("chunk_group", max(1, n // 8), 128
                                                if not args.rehearse else 8)):
        q = jnp.asarray(rng.normal(size=(rows, c, h, d)), jnp.bfloat16)
        st, tb = starts[:rows], table[:rows]
        live = jnp.full((rows,), c, jnp.int32)
        forms = {
            "pallas_paired": jax.jit(lambda q, k, v: pa.paged_attention_with_lse(
                q, k, v, tb, st, jnp.zeros_like(st), scale=d ** -0.5,
                qcounts=live, **kw)),
            "xla": jax.jit(lambda q, k, v: pa.paged_history_with_lse(
                q, k, v, tb, st, live, kernel=False, scale=d ** -0.5))}
        got = {}
        for form, fn in forms.items():
            res = jax.block_until_ready(fn(q, k_pool, v_pool))
            t0 = time.perf_counter()
            for _ in range(args.repeats):
                res = fn(q, k_pool, v_pool)
            jax.block_until_ready(res)
            out[f"{name}.{form}_ms"] = round(
                1e3 * (time.perf_counter() - t0) / args.repeats, 4)
            got[form] = np.asarray(res[0], np.float32)
        out[f"{name}.max_diff"] = float(
            np.abs(got["pallas_paired"] - got["xla"]).max())
    emit(out)
    return all(v < 0.05 for k, v in out.items() if k.endswith("max_diff"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=5600000101)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=600)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--kernel", action="store_true")
    ap.add_argument("--phases", default="float32")
    ap.add_argument("--only", default="")
    ap.add_argument("--skew", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmark.lib import model as model_lib
    from benchmark.reference import lfm2_moe_decoder as ref
    from deepspeed_tpu.inference import RaggedInferenceEngineTPU
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    emit({"phase": "start", "platform": dev.platform, "kind": dev.device_kind})
    ok = True
    if args.kernel:
        ok = kernel_check(args)
    if args.phases == "none":
        emit({"ok": ok})
        return 0 if ok else 1

    conf = model_lib.load_config(CONFIG)
    hf = model_lib.published_keys(conf, args.rehearse)
    hf["num_hidden_layers"] = min(args.layers, hf["num_hidden_layers"])
    if args.rehearse:
        args.prompt, args.steps = 150, 6
    engine_conf = dict(conf["engine"], dtype="float32",
                       num_blocks=conf["engine"]["num_blocks"] // 4)
    if args.rehearse:
        engine_conf.update(num_blocks=64, block_size=16, max_seq_len=512,
                           max_batch_tokens=256, max_sequences=8)
    from deepspeed_tpu.models.hf_loader import config_from_hf
    cfg = config_from_hf(hf)
    w = ref.Widths.from_hf(hf)
    params = tf.init_params(cfg, model_lib.prng_key(args.seed), jnp.float32)
    # what the init makes vacuous, made to count
    rng = np.random.default_rng(args.seed & 0xFFFF)
    for lp in params["layers"]:
        if "attn" in lp:
            for name in ("q_norm", "k_norm"):
                lp["attn"][name] = {"scale": jnp.asarray(
                    rng.uniform(0.5, 1.5, cfg.head_dim), jnp.float32)}
        if "moe" in lp:
            lp["moe"]["router_bias"] = jnp.asarray(
                rng.normal(0, 0.02, cfg.num_experts), jnp.float32)
            if args.skew:
                lp["moe"]["router_bias"] = lp["moe"]["router_bias"].at[
                    cfg.experts_held[0]].set(10.0)
    vocab = cfg.vocab_size
    judged = {0: rng.integers(0, vocab, args.prompt + args.steps),
              1: rng.integers(0, vocab, 16 + args.steps)}
    prompt_len = {0: args.prompt, 1: 16}
    with jax.default_matmul_precision("highest"):
        want = {u: ref.logits_of(w, params, t, dev) for u, t in judged.items()}

    def walk(cfg, params, spoil=None):
        """Both judged rows through the engine beside the background rows →
        the largest |logit difference| from each prompt's last position."""
        eng = RaggedInferenceEngineTPU(cfg, dict(engine_conf), params=params)
        n_bg = 3 if args.rehearse else 40
        slots = eng.config.max_sequences
        if spoil:
            spoil(eng)
        with jax.default_matmul_precision("highest"):
            # every slot dirtied: throwaway sequences prefilled and flushed
            uids = list(range(100, 100 + slots))
            eng.put(uids, [rng.integers(0, vocab, 24).tolist()
                           for _ in uids])
            for u in uids:
                eng.flush(u)
            bg = list(range(200, 200 + n_bg))
            eng.put(bg, [rng.integers(0, vocab, 40).tolist() for _ in bg])
            worst = {}
            for u, toks in judged.items():
                p = prompt_len[u]
                rows = [np.asarray(eng.put([u], [toks[:p].tolist()])[u],
                                   np.float32)]
                for t in toks[p:-1]:
                    out = eng.put([u] + bg, [[int(t)]] + [
                        [int(x)] for x in rng.integers(0, vocab, n_bg)])
                    rows.append(np.asarray(out[u], np.float32))
                got = np.stack(rows)
                worst[u] = float(np.abs(
                    got - want[u][p - 1:p - 1 + len(rows)]).max())
        del eng
        return worst

    def stale(eng):
        ssm.fresh_rows = lambda starts: starts < 0

    controls = {
        "stale_slot": lambda: walk(cfg, params, stale),
        "head_norms_dropped": lambda: walk(
            dataclasses.replace(cfg, qk_head_norm=False), params),
        "bf16_weights": lambda: walk(cfg, jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)),
    }
    t0 = time.time()
    sound = walk(cfg, params)
    good = max(sound.values()) < F32_LOGIT_DIFF_LIMIT
    emit({"phase": "float32", "layers": hf["num_hidden_layers"],
          "kinds": list(cfg.layer_kinds), "max_logit_diff": sound,
          "limit": F32_LOGIT_DIFF_LIMIT, "ok": good,
          "seconds": round(time.time() - t0, 1)})
    ok = ok and (good or args.rehearse)
    fresh_rows = ssm.fresh_rows
    for name, run in controls.items():
        if args.only and name not in args.only.split(","):
            continue
        try:
            wrong = run()
        finally:
            ssm.fresh_rows = fresh_rows
        caught = max(wrong.values()) > F32_LOGIT_DIFF_LIMIT
        emit({"phase": "control", "control": name, "max_logit_diff": wrong,
              "caught": caught})
        ok = ok and (caught or args.rehearse)
    emit({"ok": ok, "device": {"platform": dev.platform,
                               "kind": dev.device_kind}})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
