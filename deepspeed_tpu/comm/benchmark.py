"""Collective micro-benchmark sweep — the ``ds_bench`` analogue.

Reference: ``bin/ds_bench`` driving ``benchmarks/communication/run_all.py``
(all_reduce/all_gather/all_to_all/pt2pt/broadcast over a size sweep, with
algorithm- and bus-bandwidth columns). The TPU-native version times XLA
collectives (`psum`, `all_gather`, `reduce_scatter`, `all_to_all`,
`ppermute`) inside a jitted ``shard_map`` over the active mesh axis, so
what is measured is exactly what the training engine runs on ICI/DCN.

Bus-bandwidth factors follow the standard ring-collective accounting
(nccl-tests / reference utils.py:max_numel):
  allreduce       busbw = algbw * 2(n-1)/n
  allgather       busbw = algbw * (n-1)/n    (algbw over the FULL tensor)
  reducescatter   busbw = algbw * (n-1)/n
  alltoall        busbw = algbw * (n-1)/n
  ppermute (p2p)  busbw = algbw
"""

import time
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deepspeed_tpu.parallel import mesh as mesh_lib

_OPS = ("allreduce", "allgather", "reducescatter", "alltoall", "ppermute")


def _collective_fn(op: str, axis: str, n: int):
    if op == "allreduce":
        return lambda x: jax.lax.psum(x, axis)
    if op == "allgather":
        return lambda x: jax.lax.all_gather(x, axis, tiled=True)
    if op == "reducescatter":
        return lambda x: jax.lax.psum_scatter(x, axis, tiled=True)
    if op == "alltoall":
        return lambda x: jax.lax.all_to_all(
            x.reshape(n, -1), axis, split_axis=0, concat_axis=0).reshape(-1)
    if op == "ppermute":
        perm = [(i, (i + 1) % n) for i in range(n)]
        return lambda x: jax.lax.ppermute(x, axis, perm)
    raise ValueError(f"unknown op {op!r}; expected one of {_OPS}")


def _busbw_factor(op: str, n: int) -> float:
    if op == "allreduce":
        return 2.0 * (n - 1) / n
    if op in ("allgather", "reducescatter", "alltoall"):
        return (n - 1) / n
    return 1.0  # ppermute: point-to-point


def bench_collective(op: str, numel: int, mesh: Optional[Mesh] = None,
                     axis: str = "data", dtype=jnp.bfloat16,
                     warmup: int = 2, trials: int = 10) -> dict:
    """Time one collective at one size; returns a result row dict.

    ``numel`` is the PER-DEVICE element count of the input shard (the
    reference sweeps per-rank buffer sizes the same way).
    """
    mesh = mesh or mesh_lib.get_mesh()
    n = mesh.shape[axis]
    if op == "alltoall":  # per-device shard reshapes to (n, -1)
        numel = max(n, -(-numel // n) * n)
    fn = _collective_fn(op, axis, n)
    mapped = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False))

    x = jax.device_put(
        jnp.zeros((numel * n,), dtype=dtype),
        jax.sharding.NamedSharding(mesh, P(axis)))
    for _ in range(warmup):
        jax.block_until_ready(mapped(x))
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(mapped(x))
        times.append(time.perf_counter() - t0)
    t = float(np.min(times))  # min over trials: steady-state, no host jitter
    itemsize = jnp.dtype(dtype).itemsize
    # algbw convention (nccl-tests): full logical tensor size / time for
    # gather-type ops, per-shard size for permute
    size_bytes = numel * n * itemsize if op != "ppermute" else numel * itemsize
    algbw = size_bytes / t / 1e9
    return {"op": op, "world": n, "axis": axis,
            "numel_per_device": numel, "dtype": str(jnp.dtype(dtype)),
            "size_mb": size_bytes / 2**20, "time_ms": t * 1e3,
            "algbw_gbps": algbw,
            "busbw_gbps": algbw * _busbw_factor(op, n)}


def run_sweep(ops=_OPS, mesh: Optional[Mesh] = None, axis: str = "data",
              min_numel: int = 1 << 10, max_numel: int = 1 << 24,
              dtype=jnp.bfloat16, trials: int = 10) -> List[dict]:
    """Power-of-two size sweep over the requested collectives."""
    mesh = mesh or mesh_lib.get_mesh()
    rows = []
    for op in ops:
        numel = min_numel
        while numel <= max_numel:
            rows.append(bench_collective(op, numel, mesh=mesh, axis=axis,
                                         dtype=dtype, trials=trials))
            numel <<= 2
    return rows


def format_table(rows: List[dict]) -> str:
    hdr = (f"{'op':<14}{'world':>6}{'size(MB)':>10}{'time(ms)':>10}"
           f"{'algbw(GB/s)':>13}{'busbw(GB/s)':>13}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['op']:<14}{r['world']:>6}{r['size_mb']:>10.2f}"
            f"{r['time_ms']:>10.3f}{r['algbw_gbps']:>13.2f}"
            f"{r['busbw_gbps']:>13.2f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(
        prog="dstpu_bench_comm",
        description="collective bandwidth sweep over the device mesh "
                    "(reference: bin/ds_bench)")
    parser.add_argument("--ops", nargs="+", default=list(_OPS),
                        choices=list(_OPS))
    parser.add_argument("--axis", default="data")
    parser.add_argument("--devices", type=int, default=0,
                        help="mesh size (default: all visible devices)")
    parser.add_argument("--min-mb", type=float, default=0.0625)
    parser.add_argument("--max-mb", type=float, default=64.0)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--trials", type=int, default=10)
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON line per row instead of a table")
    args = parser.parse_args(argv)

    devs = jax.devices()
    n = args.devices or len(devs)
    mesh = mesh_lib.build_mesh(**{args.axis: n}, devices=devs[:n])
    itemsize = jnp.dtype(args.dtype).itemsize
    # interpret --min/max-mb as the full logical tensor size
    min_numel = max(1, int(args.min_mb * 2**20 / itemsize / n))
    max_numel = max(min_numel, int(args.max_mb * 2**20 / itemsize / n))
    rows = run_sweep(ops=args.ops, mesh=mesh, axis=args.axis,
                     min_numel=min_numel, max_numel=max_numel,
                     dtype=jnp.dtype(args.dtype), trials=args.trials)
    if args.json:
        for r in rows:
            print(json.dumps(r))
    else:
        print(format_table(rows))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
