#!/usr/bin/env python3
"""Microbenchmark of the held experts' layer ALONE
(``parallel/moe.held_experts_moe_layer``), at the serving cells' shapes and
the split step's token capacities: ms a LAYER of a jit that chains
``--layers`` of them (each its own weights, so every layer streams its
experts from HBM as a step program's does), beside the two parts no
dispatch can save — ``experts``: ``_held_glu`` on a fixed ``[H, 128, d]``
buffer, the weights' stream; ``router``: ``route_tokens`` — so that
``layer − experts − router`` is what placing the rows and bringing them back
costs (PR 57).

    chiprun --chips 1 -- python3 tools/bench_held_experts.py \
        --file parent=.parent_tree/deepspeed_tpu/parallel/moe.py

``--file LABEL=PATH`` (repeatable): another tree's ``moe.py`` (``git archive
<commit> | tar -x -C .parent_tree``), measured beside this tree's in the same
process on the same inputs. ``--skew``: every token picks held expert 0 too
(a sigmoid router by its selection bias; a softmax router by a column along
a direction every token is given a share of), so the layer takes ``slots /
128`` rounds. Every line holds ``max_diff_to_float32``: the tree's FIRST
layer against the same layer in float32 with no dispatch at all (the
router's picks, every held expert on every token at ``highest``, the
weights upcast), beside that reference's largest value.
``--ops N``: each tree's jit is also run under the profiler and the line
gains ``device_layer_ms`` (the program's device time a layer: no launch in
it) and ``ops_us``: its N heaviest device operations, us a LAYER, under
``<instruction name without its number> <opcode> <result shape>``;
``--hlo DIR`` writes each tree's compiled text there to read them against.
``--shapes`` / ``--slots`` pick; ``--rehearse``: tiny widths on the CPU,
control flow only — no time it prints is a device's. One JSON object a
line; the lines also go to ``--out``.

Shapes (PERF.md §4; E experts routed over, H held, top-k, d, expert width):
``lfm2`` 64 / 8 / 4 / 2,048 / 1,536 gated (cell 12), ``nemotron`` 128 / 16 /
6 / 2,688 / 1,856 relu² (cell 7), ``granite`` 72 / 36 / 10 / 4,096 / 768
gated softmax (cell 8), ``mimo`` 256 / 16 / 8 / 4,096 / 2,048 gated (cells
4, 6, 11 are its like)."""

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: name -> (experts, held, top-k, d, f, gated, scoring, routed_scale)
SHAPES = {
    "lfm2": (64, 8, 4, 2048, 1536, True, "sigmoid", 1.0),
    "nemotron": (128, 16, 6, 2688, 1856, False, "sigmoid", 2.5),
    "granite": (72, 36, 10, 4096, 768, True, "softmax", 1.0),
    "mimo": (256, 16, 8, 4096, 2048, True, "sigmoid", 1.0),
}


def load(label, path):
    spec = importlib.util.spec_from_file_location("moe_" + label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--file", action="append", default=[])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--slots", default="512,1024")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--skew", action="store_true")
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--hlo", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/bench_held_experts.jsonl")
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.parallel import moe as this
    if not a.rehearse and jax.default_backend() != "tpu":
        print("no TPU: --rehearse runs the control flow on the CPU",
              file=sys.stderr)
        return 2
    mods = [tuple(f.split("=", 1)) for f in a.file]
    mods = [(label, load(label, path)) for label, path in mods] + \
        [("this", this)]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    out = open(a.out, "a")

    def say(**line):
        line["device"] = jax.devices()[0].device_kind
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        ms = []
        for _ in range(a.calls):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ms.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(ms)

    def device_ops(fn, *args):
        """(device ms a call, {operation: us a call}) of three traced
        calls, device 0; (None, {}) where the trace holds no device."""
        import glob
        import tempfile
        from benchmark.trace import reduce
        from host_path_probe import op_label      # tools/, beside this file
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(3):
                jax.block_until_ready(fn(*args))
            jax.profiler.stop_trace()
            found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                           "*.xplane.pb"))
            trace = reduce.load(found[-1]) if found else {"planes": []}
        for i, plane in reduce.device_planes(trace):
            if i != 0:
                continue
            by_op, total = {}, 0.0
            for ev, self_ns in reduce.self_times(
                    reduce.line_events(plane, reduce.OPS_LINE)):
                op = op_label(ev)
                by_op[op] = by_op.get(op, 0.0) + self_ns
                total += self_ns
            return total / 3e6, {op: ns / 3e3 for op, ns in by_op.items()}
        return None, {}

    for name in a.shapes.split(","):
        e, h, k, d, f, gated, scoring, scale = SHAPES[name]
        if a.rehearse:
            d, f = 64, 32
        cfg = types.SimpleNamespace(
            num_experts=e, experts_held=(0, h), num_experts_per_tok=k,
            router_scoring=scoring, norm_topk_prob=True, router_groups=1,
            router_groups_kept=1, routed_scale=scale, router_norm_eps=1e-20)
        rng = np.random.default_rng(57)

        def weights(*shape):
            return jnp.asarray(rng.normal(size=shape) * shape[-2] ** -0.5,
                               jnp.bfloat16)
        layers = []
        for _ in range(a.layers):
            p = {"router": jnp.asarray(rng.normal(size=(d, e)) * d ** -0.5,
                                       jnp.float32),
                 "wi": weights(h, d, f), "wo": weights(h, f, d)}
            if gated:
                p["wg"] = weights(h, d, f)
            if scoring == "sigmoid":
                bias = np.zeros(e, np.float32)
                bias[0] = 10.0 if a.skew else 0.0
                p["router_bias"] = jnp.asarray(bias)
            elif a.skew:
                p["router"] = p["router"].at[:, 0].set(d ** -0.5)
            layers.append(p)
        for s in map(int, a.slots.split(",")):
            x = jnp.asarray(rng.normal(size=(1, s, d)), jnp.float32)
            if a.skew and scoring == "softmax":
                x = x + 1.0         # logit 0 = sqrt(d) + N(0, 1): the top
            buf = jnp.asarray(rng.normal(size=(h, this.HELD_ROUND_ROWS, d)),
                              jnp.bfloat16)

            def chain(mod):
                def run(layers, x):
                    for p in layers:
                        x = x + mod.held_experts_moe_layer(cfg, p, x)[0]
                    return x
                return jax.jit(run)

            def experts(layers, buf):
                for p in layers:
                    buf = buf + this._held_glu(p, buf)
                return buf

            def router(layers, x):
                acc = 0.0
                for p in layers:
                    w, i = this.route_tokens(cfg, p, x[0])
                    acc = acc + w.sum() + i.sum()
                return acc
            def dense_float32(p, x):
                topw, topi = this.route_tokens(cfg, p, x[0])
                comb = jnp.sum(jnp.where(
                    (topi < h)[..., None], topw[..., None] * jax.nn.one_hot(
                        topi, h, dtype=jnp.float32), 0.0), axis=1)
                p32 = {n: p[n].astype(jnp.float32)
                       for n in ("wg", "wi", "wo") if n in p}
                with jax.default_matmul_precision("highest"):
                    y = this._held_glu(p32, jnp.broadcast_to(
                        x, (h,) + x.shape[1:]))
                    return jnp.einsum("esd,se->sd", y, comb)
            exact = np.asarray(jax.jit(dense_float32)(layers[0], x))
            floor = {"experts": timed(jax.jit(experts), layers, buf),
                     "router": timed(jax.jit(router), layers, x)}
            ref = None
            for label, mod in mods:
                fn = chain(mod)
                got = np.asarray(fn(layers, x), np.float32)
                ref = got if ref is None else ref
                first = np.asarray(jax.jit(
                    lambda p, x: mod.held_experts_moe_layer(cfg, p, x)[0])(
                        layers[0], x), np.float32)[0]
                ms = timed(fn, layers, x) / a.layers
                more = {}
                if a.ops:
                    device_ms, by_op = device_ops(fn, layers, x)
                    more = {"device_layer_ms": device_ms and
                            device_ms / a.layers,
                            "ops_us": {op: round(us / a.layers, 2)
                                       for op, us in sorted(
                                           by_op.items(),
                                           key=lambda kv: -kv[1])[:a.ops]}}
                if a.hlo:
                    os.makedirs(a.hlo, exist_ok=True)
                    with open(os.path.join(
                            a.hlo, f"{name}_{s}_{label}.txt"), "w") as fh:
                        fh.write(fn.lower(layers, x).compile().as_text())
                say(shape=name, slots=s, tree=label, skew=a.skew, **more,
                    layer_ms=ms,
                    experts_ms=floor["experts"] / a.layers,
                    router_ms=floor["router"] / a.layers,
                    dispatch_ms=ms - (floor["experts"] + floor["router"])
                    / a.layers,
                    max_diff_to_first=float(np.abs(got - ref).max()),
                    max_diff_to_float32=float(np.abs(first - exact).max()),
                    float32_max=float(np.abs(exact).max()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
