"""Tests: env report CLI and the collective benchmark sweep.

Reference analogues: bin/ds_report (deepspeed/env_report.py) and
bin/ds_bench (benchmarks/communication/run_all.py).
"""

import io
import json

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu import env_report
from deepspeed_tpu.comm import benchmark as comm_bench
from deepspeed_tpu.parallel.mesh import build_mesh


class TestEnvReport:
    def test_version_and_device_report(self, capsys):
        env_report.version_report()
        env_report.device_report()
        env_report.storage_report()
        out = capsys.readouterr().out
        assert "deepspeed_tpu" in out
        assert "jax" in out
        assert "devices" in out

    def test_op_report_lists_native_ops(self):
        buf = io.StringIO()
        env_report.op_report(build=False, file=buf)
        out = buf.getvalue()
        assert "host_adam" in out and "async_io" in out
        assert "toolchain" in out

    def test_cli_main(self, capsys):
        rc = env_report.main(["--no-device"])
        out = capsys.readouterr().out
        assert rc in (0, 1)
        assert "version information" in out


class TestCommBench:
    def test_single_collective_row(self, devices):
        mesh = build_mesh(data=8, devices=devices[:8])
        row = comm_bench.bench_collective(
            "allreduce", numel=1024, mesh=mesh, trials=2, warmup=1)
        assert row["world"] == 8
        assert row["time_ms"] > 0
        assert row["algbw_gbps"] > 0
        # allreduce busbw factor 2(n-1)/n = 1.75 at n=8
        assert row["busbw_gbps"] == pytest.approx(
            row["algbw_gbps"] * 1.75)

    @pytest.mark.parametrize("op", ["allgather", "reducescatter",
                                    "alltoall", "ppermute"])
    def test_each_op_runs(self, op, devices):
        mesh = build_mesh(data=8, devices=devices[:8])
        row = comm_bench.bench_collective(
            op, numel=512, mesh=mesh, trials=1, warmup=1)
        assert row["op"] == op and row["time_ms"] > 0

    def test_sweep_and_table(self, devices):
        mesh = build_mesh(data=8, devices=devices[:8])
        rows = comm_bench.run_sweep(
            ops=("allreduce",), mesh=mesh, min_numel=256, max_numel=1024,
            trials=1)
        assert len(rows) == 2  # 256, 1024 (x4 stride)
        table = comm_bench.format_table(rows)
        assert "busbw" in table and "allreduce" in table
        # rows are json-serializable (the --json CLI path)
        for r in rows:
            json.dumps(r)

    def test_correctness_allreduce_values(self, devices):
        """The timed jitted collective computes the right thing."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = build_mesh(data=8, devices=devices[:8])
        fn = comm_bench._collective_fn("allreduce", "data", 8)
        mapped = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))
        x = jax.device_put(jnp.arange(16, dtype=jnp.float32),
                           NamedSharding(mesh, P("data")))
        out = mapped(x)
        # psum over the data axis: every 2-element shard sums across 8 ranks
        expect = jnp.arange(16, dtype=jnp.float32).reshape(8, 2).sum(0)
        assert jnp.allclose(out.reshape(8, 2)[0], expect)
