"""deepspeed_tpu.comm — the communication facade.

TPU-native equivalent of the reference's ``deepspeed.comm`` module
(deepspeed/comm/comm.py: ``init_distributed``:788, ``all_reduce``:641,
``all_gather_into_tensor``:310, ``reduce_scatter_tensor``:293,
``all_to_all_single``:344, ``barrier``:419). Two layers:

1. **Process-level** (multi-host TPU pods): ``init_distributed`` wraps
   ``jax.distributed.initialize`` — the rendezvous that the reference does
   via torch.distributed.init_process_group (comm/torch.py:148). Rank ==
   jax process index; world == process count.

2. **Device-level collectives**: thin wrappers over ``jax.lax`` collectives
   (psum/all_gather/psum_scatter/all_to_all/ppermute) that (a) are valid
   inside ``shard_map`` over a named mesh axis and (b) register themselves
   with the CommsLogger at trace time. Outside shard_map, the eager-mode
   fallbacks operate on global arrays via device_put + resharding so unit
   tests can call them directly.

There is no NCCL analogue to manage: XLA lowers these to ICI/DCN
collectives, choosing algorithms per topology. The Backend abstraction of
the reference (comm/backend.py) collapses to this single XLA backend; a
``compressed`` backend for 1-bit optimizers lives in
deepspeed_tpu/comm/compressed.py.
"""

import os
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.comm.comms_logger import comms_logger
from deepspeed_tpu.utils.logging import log_dist, logger

_INITIALIZED = False


# ---------------------------------------------------------------------------
# Process-level API
# ---------------------------------------------------------------------------

def init_distributed(dist_backend: str = "ici",
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     auto_mpi_discovery: bool = True,
                     timeout: Optional[int] = None,
                     **_: Any) -> None:
    """Initialize multi-host communication (reference comm/comm.py:788).

    Single-host (or already-initialized) is a no-op. Multi-host coordinates
    through ``jax.distributed.initialize``; env-var discovery mirrors the
    reference's MPI/launcher env patching (comm.py:857-949) but reads the
    TPU-VM / launcher variables (COORDINATOR_ADDRESS, NUM_PROCESSES,
    PROCESS_ID) that deepspeed_tpu's launcher exports.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator_address = coordinator_address or os.environ.get("DSTPU_COORDINATOR")
    if num_processes is None and "DSTPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["DSTPU_NUM_PROCESSES"])
    if process_id is None and "DSTPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["DSTPU_PROCESS_ID"])
    if coordinator_address is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
        log_dist(f"jax.distributed initialized: "
                 f"{jax.process_index()}/{jax.process_count()} processes")
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank() -> int:
    """Process rank (reference comm.py:705 — but device-granular ranks only
    exist inside shard_map on TPU; use lax.axis_index there)."""
    return jax.process_index()


def get_world_size() -> int:
    """Total device count (the reference's world == device count since it
    runs one process per GPU)."""
    return jax.device_count()


def get_local_rank() -> int:
    return jax.process_index()


def barrier() -> None:
    """Reference comm.py:419. On jax: round-trip a tiny psum across all
    devices and block."""
    x = jnp.zeros((), jnp.int32)
    jax.block_until_ready(
        jax.pmap(lambda v: lax.psum(v, "i"), axis_name="i")(
            jnp.zeros((jax.local_device_count(),), jnp.int32)))
    del x


# ---------------------------------------------------------------------------
# Device-level collectives (valid inside shard_map; log at trace time)
# ---------------------------------------------------------------------------

AxisName = Union[str, Sequence[str]]


def _timed(op: str, x: jax.Array, axis: AxisName, run) -> jax.Array:
    """Register the collective with the CommsLogger, and — on the
    synchronous path in verbose mode — record its MEASURED wall time (a
    cross-check for the roofline's estimate). Inside shard_map/pmap
    ``x`` is an abstract tracer: timing a trace-time call would clock
    XLA's lowering, not the collective, so those register untimed (the
    roofline remains the estimate there). The timed path blocks on the
    result, which the synchronous eager semantics already imply."""
    try:
        size = x.size * x.dtype.itemsize
    except Exception:
        size = 0
    if not (comms_logger.verbose and comms_logger.should_log(op)) \
            or isinstance(x, jax.core.Tracer):
        comms_logger.append(op, size, axis)
        return run()
    from deepspeed_tpu.telemetry.tracer import tracer
    t0 = tracer.now()
    try:
        out = jax.block_until_ready(run())
    except Exception:
        comms_logger.append(op, size, axis)
        raise
    t1 = tracer.now()
    comms_logger.append(op, size, axis, time_sec=t1 - t0)
    tracer.complete(f"comm/{op}", t0, t1, bytes=size)
    return out


def _log(op: str, x: jax.Array, axis: AxisName) -> None:
    try:
        size = x.size * x.dtype.itemsize
    except Exception:
        size = 0
    comms_logger.append(op, size, axis)


def all_reduce(x: jax.Array, axis_name: AxisName, op: str = "sum") -> jax.Array:
    """Reference comm.py:641 (all_reduce). Inside shard_map/pmap only."""
    if op == "sum":
        return _timed("all_reduce", x, axis_name,
                      lambda: lax.psum(x, axis_name))
    if op == "mean":
        return _timed("all_reduce", x, axis_name,
                      lambda: lax.pmean(x, axis_name))
    if op == "max":
        return _timed("all_reduce", x, axis_name,
                      lambda: lax.pmax(x, axis_name))
    if op == "min":
        return _timed("all_reduce", x, axis_name,
                      lambda: lax.pmin(x, axis_name))
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(x: jax.Array, axis_name: AxisName, axis: int = 0,
               tiled: bool = True) -> jax.Array:
    """Reference comm.py:310 (all_gather_into_tensor)."""
    return _timed("all_gather", x, axis_name,
                  lambda: lax.all_gather(x, axis_name, axis=axis,
                                         tiled=tiled))


def reduce_scatter(x: jax.Array, axis_name: AxisName, axis: int = 0,
                   tiled: bool = True) -> jax.Array:
    """Reference comm.py:293 (reduce_scatter_tensor) — the ZeRO-2 hot path
    (stage_1_and_2.py:average_tensor:1184)."""
    return _timed("reduce_scatter", x, axis_name,
                  lambda: lax.psum_scatter(x, axis_name,
                                           scatter_dimension=axis,
                                           tiled=tiled))


def all_to_all(x: jax.Array, axis_name: AxisName, split_axis: int,
               concat_axis: int, tiled: bool = True) -> jax.Array:
    """Reference comm.py:344 (all_to_all_single) — the Ulysses/MoE hot path
    (sequence/layer.py:single_all_to_all:221, moe/sharded_moe.py:_AllToAll:96)."""
    return _timed("all_to_all", x, axis_name,
                  lambda: lax.all_to_all(x, axis_name, split_axis=split_axis,
                                         concat_axis=concat_axis,
                                         tiled=tiled))


def ppermute(x: jax.Array, axis_name: AxisName, perm) -> jax.Array:
    """Point-to-point ring shift (reference pipe/p2p.py send/recv analogue,
    expressed as a collective permute so XLA can pipeline it on ICI)."""
    return _timed("ppermute", x, axis_name,
                  lambda: lax.ppermute(x, axis_name, perm))


def send_recv_next(x: jax.Array, axis_name: AxisName, world: int) -> jax.Array:
    """Shift activations to the next pipeline stage (reference p2p.py:46,67)."""
    perm = [(i, (i + 1) % world) for i in range(world)]
    return ppermute(x, axis_name, perm)


def send_recv_prev(x: jax.Array, axis_name: AxisName, world: int) -> jax.Array:
    perm = [(i, (i - 1) % world) for i in range(world)]
    return ppermute(x, axis_name, perm)


def axis_index(axis_name: AxisName) -> jax.Array:
    """Device rank along a mesh axis (reference get_rank(group=...))."""
    return lax.axis_index(axis_name)


def log_summary(show_straggler: bool = False) -> None:
    """Reference comm.py:435 (log_summary): ``show_straggler`` gathers
    per-process op timings and prints the cross-rank min/max split into
    transmit vs wait time (utils/comms_logging.py:67). With
    ``show_straggler`` this is a COLLECTIVE under multi-process — every
    process must call it, not just rank 0."""
    comms_logger.log_summary(show_straggler=show_straggler)
