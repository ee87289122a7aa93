"""Serve a model with continuous batching (ragged/paged engine — the
FastGen analogue) or the simpler padded v1 engine.

    python examples/serve.py --engine ragged --prompts "hello" "the sky"

``--stream`` routes the ragged engine through the serving frontend
(deepspeed_tpu/serving/): prefix-cached admission, SplitFuse token-budget
scheduling and per-token streaming; ``--concurrency`` caps how many
requests are in flight at once (the rest wait in the admission queue).
"""

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=("ragged", "v1"), default="ragged")
    ap.add_argument("--model-dir", default=None,
                    help="HF checkpoint dir; random tiny llama if unset")
    ap.add_argument("--prompts", nargs="+", default=["1 2 3 4"])
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--weight-quant", choices=("int8", "fp8", "int4"),
                    default=None,
                    help="weight-only quantized serving (half or quarter "
                         "the weight HBM; ops/quantized_linear.py)")
    ap.add_argument("--stream", action="store_true",
                    help="drive the ServingFrontend and print tokens as "
                         "they are produced (ragged engine only)")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="with --stream: max requests in flight at once "
                         "(0 = engine max_sequences)")
    args = ap.parse_args()

    from _common import setup_jax
    jax = setup_jax()
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import llama3_config

    ds.build_mesh(data=1, devices=jax.devices()[:1])
    params = None
    if args.model_dir:
        from deepspeed_tpu.models.hf_loader import load_hf_checkpoint
        cfg, params = load_hf_checkpoint(args.model_dir)
        try:
            from transformers import AutoTokenizer
            tok = AutoTokenizer.from_pretrained(args.model_dir)
        except Exception:
            tok = None
    else:
        cfg, tok = llama3_config("tiny", max_seq_len=512), None

    def encode(p):
        if tok is not None:
            return tok(p)["input_ids"]
        return [int(x) % cfg.vocab_size for x in p.split()]

    prompts = [encode(p) for p in args.prompts]
    eng_cfg = {}
    if args.weight_quant:
        eng_cfg["weight_quant"] = args.weight_quant
    if args.stream:
        from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
        from deepspeed_tpu.serving import ServingFrontend
        eng = RaggedInferenceEngineTPU(cfg, eng_cfg or None, params=params)
        if args.concurrency:
            eng.config.max_sequences = min(eng.config.max_sequences,
                                           args.concurrency)
        fe = ServingFrontend(eng)

        def cb_for(i):
            def cb(t):
                piece = tok.decode([t]) if tok is not None else str(t)
                print(f"[{i}] {piece}", flush=True)
            return cb

        reqs = [fe.submit(p, max_new_tokens=args.max_new_tokens,
                          stream_cb=cb_for(i))
                for i, p in enumerate(prompts)]
        fe.run_until_idle()
        outs = [r.tokens_out for r in reqs]
        stats = fe.stats()
        print(f"# engine_steps={stats['engine_steps']} "
              f"prefix_hit_rate={stats.get('prefix_hit_rate', 0.0):.2f} "
              f"ttft_mean={stats['ttft']['mean']:.4f}s")
    elif args.engine == "ragged":
        from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
        eng = RaggedInferenceEngineTPU(cfg, eng_cfg or None, params=params)
        outs = eng.generate(prompts, max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature)
    else:
        from deepspeed_tpu.inference.engine import InferenceEngineTPU
        eng = InferenceEngineTPU(cfg, eng_cfg or None, params=params)
        outs = eng.generate(prompts, max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature)
    for p, o in zip(args.prompts, outs):
        text = tok.decode(o) if tok is not None else " ".join(map(str, o))
        print(f"> {p}\n{text}\n")


if __name__ == "__main__":
    main()
