"""Environment / op report — the ``ds_report`` analogue.

Reference: ``deepspeed/env_report.py`` (op_report:30, debug_report:84) and
``bin/ds_report``. The reference enumerates CUDA extension builders and
torch/nvcc compatibility; the TPU-native report covers what actually
matters here: the JAX stack (jax/jaxlib/libtpu), the device inventory with
HBM stats, the host C++ toolchain, and the build/load status of each
native op in ``csrc/`` (cached .so signature, trial build on request).
"""

import os
import platform
import shutil
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"
FAIL = f"{RED}[FAIL]{END}"

def _native_ops():
    """Enumerate csrc/*.cpp — one op per source, matching NativeOpBuilder's
    default `name → name.cpp` convention, so new ops appear automatically."""
    from deepspeed_tpu.ops.op_builder import _CSRC
    return sorted(p.stem for p in _CSRC.glob("*.cpp"))


def _version(mod_name):
    try:
        mod = __import__(mod_name)
        return getattr(mod, "__version__", "unknown")
    except ImportError:
        return None


def op_report(build: bool = False, file=None) -> bool:
    """Native (C++) op status table. Returns True if all ops are healthy.

    ``build=True`` trial-compiles any op whose cached .so is missing
    (reference op_report only checks compatibility; here a build IS the
    compatibility check — there is no separate arch matrix on a host CPU).
    """
    from deepspeed_tpu.ops.op_builder import NativeOpBuilder, is_native_available

    print("-" * 58, file=file)
    print("deepspeed_tpu native (C++) op report", file=file)
    print("-" * 58, file=file)
    cxx = os.environ.get("CXX", "g++")
    have_cxx = is_native_available()
    print(f"host toolchain ({cxx}) ".ljust(34, ".") +
          f" {OKAY if have_cxx else FAIL}", file=file)
    ok = have_cxx
    for name in _native_ops():
        builder = NativeOpBuilder(name)
        try:
            cached = builder.so_path().exists()
        except OSError:
            cached = False
        status = f"{GREEN}[CACHED]{END}" if cached else f"{YELLOW}[JIT]{END}"
        if build and not cached and have_cxx:
            try:
                builder.build()
                status = f"{GREEN}[BUILT]{END}"
            except Exception as exc:  # report, don't raise: this is a report
                status = FAIL
                ok = False
                print(f"  build error: {exc}", file=file)
        print(f"op {name} ".ljust(34, ".") + f" {status}", file=file)
    print("NOTE: [JIT] ops compile on first use into "
          f"{os.environ.get('DSTPU_CACHE_DIR', '~/.cache/deepspeed_tpu')}",
          file=file)
    return ok


def device_report(file=None) -> None:
    import jax
    from deepspeed_tpu.accelerator import get_accelerator

    accel = get_accelerator()
    print("-" * 58, file=file)
    print("device inventory", file=file)
    print("-" * 58, file=file)
    print(f"backend ".ljust(24, ".") + f" {jax.default_backend()}", file=file)
    devs = jax.devices()
    print(f"devices ".ljust(24, ".") + f" {len(devs)}", file=file)
    for d in devs[:8]:
        print(f"  [{d.id}] {d.device_kind} (process {d.process_index})",
              file=file)
    if len(devs) > 8:
        print(f"  ... and {len(devs) - 8} more", file=file)
    print(f"process count ".ljust(24, ".") + f" {jax.process_count()}",
          file=file)
    try:
        stats = accel.memory_stats()
        if stats:
            tot = stats.get("bytes_limit", 0)
            used = stats.get("bytes_in_use", 0)
            print(f"HBM in use / limit ".ljust(24, ".") +
                  f" {used / 2**30:.2f} / {tot / 2**30:.2f} GiB", file=file)
    except Exception:
        pass
    print(f"comm backend ".ljust(24, ".") +
          f" {accel.communication_backend_name()}", file=file)


def version_report(file=None) -> None:
    import deepspeed_tpu

    print("-" * 58, file=file)
    print("version information", file=file)
    print("-" * 58, file=file)
    rows = [("deepspeed_tpu", deepspeed_tpu.__version__),
            ("python", platform.python_version()),
            ("platform", platform.platform())]
    for mod in ("jax", "jaxlib", "numpy", "flax", "optax", "orbax",
                "transformers"):
        v = _version(mod)
        if v is not None:
            rows.append((mod, v))
    libtpu = _version("libtpu")
    if libtpu is not None:
        rows.append(("libtpu", libtpu))
    for k, v in rows:
        print(f"{k} ".ljust(24, ".") + f" {v}", file=file)
    flags = os.environ.get("XLA_FLAGS")
    if flags:
        print(f"XLA_FLAGS ".ljust(24, ".") + f" {flags}", file=file)


def storage_report(file=None) -> None:
    """NVMe/disk line for the offload/Infinity configs."""
    print("-" * 58, file=file)
    print("storage (ZeRO-Infinity swap target)", file=file)
    print("-" * 58, file=file)
    paths = dict.fromkeys(
        p for p in ("/tmp", os.environ.get("DSTPU_NVME_PATH", ""))
        if p and os.path.isdir(p))
    for path in paths:
        usage = shutil.disk_usage(path)
        print(f"{path} ".ljust(24, ".") +
              f" {usage.free / 2**30:.1f} GiB free of "
              f"{usage.total / 2**30:.1f} GiB", file=file)


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="dstpu_report",
        description="deepspeed_tpu environment and native-op report")
    parser.add_argument("--build", action="store_true",
                        help="trial-build any native op not yet cached")
    parser.add_argument("--no-device", action="store_true",
                        help="skip device probing (no jax backend init)")
    parser.add_argument("--compare", nargs=2, metavar=("A", "B"),
                        help="regression gate: compare two runs' BENCH "
                             "JSONL or metric-history files (baseline A "
                             "vs candidate B); exit 1 on a regression "
                             "beyond the noise band")
    parser.add_argument("--noise", type=float, default=0.05,
                        help="relative noise band for --compare "
                             "(default %(default)s)")
    parser.add_argument("--json", action="store_true",
                        help="emit the --compare report as JSON")
    args = parser.parse_args(argv)

    if args.compare:
        from deepspeed_tpu.telemetry.compare import main_compare
        return main_compare(args.compare[0], args.compare[1],
                            noise=args.noise, as_json=args.json)

    version_report()
    ok = op_report(build=args.build)
    if not args.no_device:
        device_report()
    storage_report()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
