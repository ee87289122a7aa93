"""The hyper-connection sublayers ALONE on the chip (``chiprun --chips 1 --
python3 tools/bench_hc_maps.py``; ``--rehearse`` on the CPU): 12 sublayers
chained (a six-layer stack's launch) over a float32 stream of 4 x 3584 a
token slot, at 64 rows (a decode launch) and at 512 / 2,048 slots (a split
launch's rungs), with a trivial branch between a read and its write-back
(``y = 0.5·u``), so what is timed is ``typed_layers.stream_read`` /
``stream_write``: the maps and the mixing. One line a FORM of the Sinkhorn
rounds and a size, ms a launch (the least of ``--reps`` timed calls):

- ``fori_loop``: the program's (``typed_layers._sinkhorn``): one round on
  sixteen ``[slots]`` arrays, sums of four operands, the body of a
  ``lax.fori_loop``;
- ``unrolled``: the same round written out 20 times;
- ``reduce``: ``[slots, 4, 4]`` with ``jnp.sum`` over an axis of 4;
- ``no_rounds``: the rounds left out (NOT the function: what the rest of a
  sublayer costs — the norm, the ``phi`` product, the mixing).

Readings: PERF.md section 5, PR 58."""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--slots", type=int, nargs="*", default=[64, 512, 2048])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.models import typed_layers as tl

    n, c, sublayers = 4, (128 if args.rehearse else 3584), 12
    cfg = tf.DecoderConfig(
        hidden_size=c, num_layers=1, num_heads=2, head_dim_override=48,
        v_head_dim=32, q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, intermediate_size=64, vocab_size=64,
        norm="rmsnorm", activation="silu_glu", pos_emb="rope",
        use_bias=False, tie_embeddings=False, layer_kinds=(2,),
        layer_sparse=(0,), hc_mult=n, hc_sinkhorn_iters=20)

    program = tl._sinkhorn

    def unrolled(m, n, iters, eps):
        for _ in range(iters):
            m = program(m, n, 1, eps)
        return m

    def reduce_form(m, n, iters, eps):
        a = jnp.stack(m, axis=-1).reshape(m[0].shape + (n, n))
        for _ in range(iters):
            a = a / (jnp.sum(a, axis=-2, keepdims=True) + eps)
            a = a / (jnp.sum(a, axis=-1, keepdims=True) + eps)
        return tuple(a[..., i, j] for i in range(n) for j in range(n))

    forms = {"fori_loop": program, "unrolled": unrolled,
             "reduce": reduce_form,
             "no_rounds": lambda m, n, iters, eps: tuple(m)}
    keys = jax.random.split(jax.random.PRNGKey(0), 2 * sublayers + n)
    trees = [{"phi": (0.02 * jax.random.normal(
                  keys[2 * s], (n * c, n * n + 2 * n))).astype(jnp.bfloat16),
              "base": jnp.concatenate([jnp.zeros(2 * n), jax.random.normal(
                  keys[2 * s + 1], (n * n,))]),
              "scale": jnp.ones(3)} for s in range(sublayers)]
    dev = jax.devices()[0]
    for slots in args.slots:
        x = tuple(0.02 * jax.random.normal(k, (slots, c), jnp.float32)
                  for k in keys[-n:])
        for name, form in forms.items():
            def launch(x, trees):
                for hc in trees:
                    u, maps = tl.stream_read(cfg, hc, x)
                    x = tl.stream_write(cfg, maps, x, 0.5 * u)
                return tl.stream_close(cfg, x)

            saved = tl._sinkhorn
            tl._sinkhorn = form
            try:
                fn = jax.jit(launch)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, trees))
                first = time.perf_counter() - t0
            finally:
                tl._sinkhorn = saved
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, trees))
                times.append(time.perf_counter() - t0)
            stream_bytes = sublayers * slots * (2 * n * c + 2 * c) * 4
            print(json.dumps({
                "form": name, "slots": slots, "sublayers": sublayers,
                "ms_a_launch": round(1e3 * min(times), 4),
                "ms_median": round(1e3 * sorted(times)[len(times) // 2], 4),
                "first_call_s": round(first, 2),
                "least_ms_by_the_streams_bytes":
                    round(1e3 * stream_bytes / 819e9, 4),
                "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
