"""Per-host launch agent.

Reference: ``launcher/launch.py:145`` (per-node agent: spawns one process
per local rank, exports RANK/WORLD_SIZE env, ``sigkill_handler`` kills
the tree on failure) + the elastic relaunch path (``--elastic_training``
in runner.py → DSElasticAgent). TPU translation: ONE worker process per
host (jax drives every local chip), so the agent's job is environment
setup, supervision, bounded restarts, and signal forwarding:

- exports the jax distributed rendezvous env
  (DSTPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID — consumed by
  comm.init_distributed);
- runs the training command as a child process group;
- forwards SIGTERM (pod preemption) to the child so the in-process
  DSElasticAgent (elasticity/elastic_agent.py) can checkpoint;
- restarts the child up to ``max_restarts`` on nonzero exit (the
  torchelastic worker-group restart), backing off between attempts;
- exports ``DSTPU_HEARTBEAT_FILE`` so the worker's watchdog
  (telemetry/watchdog.py) stamps per-step heartbeats this host's
  operator — and ``dstpu-doctor`` — can read to name a straggler, and
  stamps agent-level status (started/exited/restarting) into the same
  file while no worker is alive.
"""

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger


class LaunchAgent:
    """Supervise one per-host worker process (reference launch.py main)."""

    def __init__(self, cmd: List[str], env: Optional[Dict[str, str]] = None,
                 max_restarts: int = 0, restart_backoff_s: float = 5.0,
                 max_backoff_s: float = 60.0,
                 restart_window_s: float = 300.0,
                 heartbeat_file: Optional[str] = None):
        self.cmd = cmd
        self.env = {**os.environ, **(env or {})}
        self.max_restarts = max_restarts
        self.restart_backoff_s = restart_backoff_s
        self.max_backoff_s = max_backoff_s
        #: rolling restart budget: only restarts within the last
        #: ``restart_window_s`` seconds count against ``max_restarts`` —
        #: a worker that dies once a day is healthy; one that dies
        #: max_restarts times in five minutes is crash-looping
        self.restart_window_s = restart_window_s
        self._restart_times: List[float] = []
        self.heartbeat_file = heartbeat_file or \
            self.env.get("DSTPU_HEARTBEAT_FILE")
        if self.heartbeat_file:
            # the worker's watchdog picks this up and takes over stamping
            self.env["DSTPU_HEARTBEAT_FILE"] = self.heartbeat_file
        self._child: Optional[subprocess.Popen] = None
        self._terminating = False

    def _beat(self, phase: str, **extra) -> None:
        """Agent-level heartbeat (atomic write, best effort). The worker's
        watchdog overwrites the same file with per-step beats once it is
        up; agent beats cover the gaps (spawn, restart backoff, exit)."""
        if not self.heartbeat_file:
            return
        try:
            doc = {"hostname": socket.gethostname(), "pid": os.getpid(),
                   "agent": True, "phase": phase, "ts": time.time(),
                   **extra}
            parent = os.path.dirname(os.path.abspath(self.heartbeat_file))
            os.makedirs(parent, exist_ok=True)
            tmp = f"{self.heartbeat_file}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(doc, fh)
            os.replace(tmp, self.heartbeat_file)
        except Exception:
            pass

    def _forward(self, signum, _frame) -> None:
        """SIGTERM/SIGINT → forward to the child's process group so the
        worker can checkpoint (reference sigkill_handler — but graceful
        first: preemption gives a drain window)."""
        self._terminating = True
        if self._child and self._child.poll() is None:
            logger.warning(
                f"launch agent: forwarding {signal.Signals(signum).name} "
                f"to worker pid {self._child.pid}")
            try:
                os.killpg(os.getpgid(self._child.pid), signum)
            except ProcessLookupError:
                pass

    def run(self) -> int:
        prev_term = signal.signal(signal.SIGTERM, self._forward)
        prev_int = signal.signal(signal.SIGINT, self._forward)
        try:
            attempt = 0
            while True:
                # chaos hook: lets a fault plan target the supervisor
                # itself (a launcher-scoped hang or preempt)
                from deepspeed_tpu.resilience.faults import fault_injector
                fault_injector.fire("launcher")
                # plain logger, never log_dist: that asks jax for the
                # process index, which initialises the backend — the
                # supervisor would then hold every local chip and its
                # worker could take none
                logger.info(f"launch agent: starting worker "
                            f"(attempt {attempt + 1}): "
                            f"{' '.join(self.cmd)}")
                self._child = subprocess.Popen(
                    self.cmd, env=self.env, start_new_session=True)
                self._beat("worker_started", worker_pid=self._child.pid,
                           attempt=attempt)
                rc = self._child.wait()
                self._beat("worker_exited", rc=rc, attempt=attempt)
                if rc == 0 or self._terminating:
                    return rc
                now = time.monotonic()
                self._restart_times = [
                    t for t in self._restart_times
                    if now - t <= self.restart_window_s]
                if len(self._restart_times) >= self.max_restarts:
                    logger.error(
                        f"launch agent: worker failed (rc={rc}) with "
                        f"{len(self._restart_times)} restarts already in "
                        f"the last {self.restart_window_s:.0f}s "
                        f"(budget {self.max_restarts}); giving up")
                    self._beat("crash_loop", rc=rc,
                               restarts_in_window=len(self._restart_times),
                               attempt=attempt)
                    return rc
                self._restart_times.append(now)
                attempt += 1
                delay = min(
                    self.restart_backoff_s *
                    (2 ** (len(self._restart_times) - 1)),
                    self.max_backoff_s)
                logger.warning(
                    f"launch agent: worker rc={rc}; restart "
                    f"{len(self._restart_times)}/{self.max_restarts} "
                    f"(window {self.restart_window_s:.0f}s) in "
                    f"{delay:.1f}s")
                # doctor reads this phase + count to name a crash-looping
                # host from the heartbeat alone
                self._beat("restart_backoff", rc=rc, backoff_s=delay,
                           restarts_in_window=len(self._restart_times),
                           attempt=attempt)
                time.sleep(delay)
                if self._terminating:
                    # SIGTERM landed during the backoff (preemption):
                    # spawning a fresh worker that never saw the signal
                    # would lose the checkpoint window
                    logger.warning("launch agent: termination requested "
                                   "during backoff; not restarting")
                    return rc
        finally:
            signal.signal(signal.SIGTERM, prev_term)
            signal.signal(signal.SIGINT, prev_int)


def _local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device nodes.
    The supervisor must not ask JAX: a process that has touched JAX holds
    the chips, and its children could then take none."""
    return len(glob.glob("/dev/accel[0-9]*")) or \
        len(glob.glob("/dev/vfio/[0-9]*"))


def _one_chip_env(chip: int) -> Dict[str, str]:
    """Environment that shows a child exactly one local chip (libtpu's
    own variables; two such processes ran side by side on a four-chip
    v5e host in PR 25). Without it every child takes all local chips,
    and on a locally attached TPU the second one dies at backend init
    ("The TPU is already in use by process ...")."""
    return {"TPU_VISIBLE_DEVICES": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{8476 + chip}",
            "TPU_MESH_CONTROLLER_PORT": str(8476 + chip)}


class ReplicaPoolAgent:
    """Spawn and supervise a local pool of N serving-replica processes —
    the multi-process backend for the serving router
    (serving/router.py; docs/serving.md "Router, failover & draining").

    Each child runs ``cmd`` with ``DSTPU_REPLICA_NAME=r<i>`` and, when
    ``base_port > 0``, ``DSTPU_HTTP_PORT=base_port+i`` (the replica's
    /metrics + /healthz endpoint the router's breaker polls). On a host
    with several TPU chips each child is shown ONE chip (the lowest no
    live sibling holds; a restart keeps its own), unless the caller's
    environment already chose (``TPU_VISIBLE_DEVICES``) or runs the
    replicas off the TPU (``JAX_PLATFORMS``). Unlike
    :class:`LaunchAgent` this supervisor is poll-driven and installs no
    signal handlers, so it can run off the main thread or embedded in a
    router process; restarts share one rolling per-replica budget so a
    crash-looping replica gives up instead of flapping its breaker
    forever. ``kill(name)`` has chaos semantics: SIGKILL the process
    group and (optionally) leave it down — the router's failover is
    what keeps the streams alive.
    """

    def __init__(self, cmd: List[str], n: int, base_port: int = 0,
                 env: Optional[Dict[str, str]] = None,
                 max_restarts: int = 2, restart_window_s: float = 300.0,
                 heartbeat_dir: Optional[str] = None):
        if n < 1:
            raise ValueError("pool needs at least one replica")
        self.cmd = cmd
        self.names = [f"r{i}" for i in range(n)]
        self.base_port = base_port
        self.env = {**os.environ, **(env or {})}
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        #: one heartbeat JSON per replica under this dir (doctor input)
        self.heartbeat_dir = heartbeat_dir
        self._children: Dict[str, Optional[subprocess.Popen]] = {
            name: None for name in self.names}
        self._restart_times: Dict[str, List[float]] = {
            name: [] for name in self.names}
        #: replicas deliberately downed (kill/stop): never restarted
        self._downed: set = set()
        #: replicas in graceful scale-down: SIGTERM only lands after the
        #: router has drained them; heartbeats read ``draining`` so
        #: dstpu-top/doctor never mistake an intentional shrink for a
        #: crash loop
        self._draining: set = set()
        self.restarts = 0
        self._next_idx = n
        #: one chip per child on a multi-chip TPU host (0 = leave the
        #: environment alone: no TPU here, or the caller already chose)
        platforms = self.env.get("JAX_PLATFORMS") or "tpu"
        self._chips = _local_tpu_chips() \
            if "tpu" in platforms.split(",") and \
            "TPU_VISIBLE_DEVICES" not in self.env else 0
        self._chip_of: Dict[str, int] = {}

    def _beat(self, name: str, phase: str, **extra) -> None:
        """Per-replica agent heartbeat (atomic write, best effort) —
        the LaunchAgent._beat contract, one file per replica under
        ``heartbeat_dir``."""
        if not self.heartbeat_dir:
            return
        try:
            doc = {"hostname": socket.gethostname(), "pid": os.getpid(),
                   "agent": True, "replica": name, "phase": phase,
                   "ts": time.time(), **extra}
            os.makedirs(self.heartbeat_dir, exist_ok=True)
            path = os.path.join(self.heartbeat_dir, f"{name}.json")
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(doc, fh)
            os.replace(tmp, path)
        except Exception:
            pass

    def _spawn(self, name: str) -> subprocess.Popen:
        i = self.names.index(name)
        env = dict(self.env)
        env["DSTPU_REPLICA_NAME"] = name
        if self.base_port > 0:
            env["DSTPU_HTTP_PORT"] = str(self.base_port + i)
        if self._chips > 1:
            env.update(_one_chip_env(self._claim_chip(name)))
        child = subprocess.Popen(self.cmd, env=env, start_new_session=True)
        self._children[name] = child
        logger.info(f"replica pool: started {name} pid={child.pid}" +
                    (f" port={self.base_port + i}" if self.base_port else "")
                    + (f" chip={self._chip_of[name]}"
                       if name in self._chip_of else ""))
        return child

    def _claim_chip(self, name: str) -> int:
        """The chip ``name`` runs on: its own from before (a restart), else
        the lowest one no live sibling holds."""
        held = {self._chip_of[n] for n, c in self._children.items()
                if n != name and n in self._chip_of
                and c is not None and c.poll() is None}
        chip = self._chip_of.get(name)
        if chip is None or chip in held:
            free = [c for c in range(self._chips) if c not in held]
            if not free:
                raise RuntimeError(
                    f"replica pool: no free chip for {name} — this host "
                    f"has {self._chips}, each held by a live replica")
            chip = self._chip_of[name] = free[0]
        return chip

    def start(self) -> "ReplicaPoolAgent":
        for name in self.names:
            self._spawn(name)
        return self

    def targets(self) -> List[str]:
        """Scrape targets for a Router / dstpu-top over this pool."""
        if self.base_port <= 0:
            return []
        return [f"127.0.0.1:{self.base_port + i}"
                for i in range(len(self.names))]

    def poll(self) -> Dict[str, str]:
        """One supervision sweep: restart dead replicas inside their
        rolling budget; returns per-replica phase (``running`` |
        ``restarting`` | ``down`` | ``crash_loop`` | ``draining``).
        A draining replica is NEVER restarted — it is leaving on
        purpose; if it dies mid-drain (chaos) it is simply down and the
        router's failover owns its streams."""
        phases: Dict[str, str] = {}
        now = time.monotonic()
        for name, child in list(self._children.items()):
            if name in self._draining:
                if child is not None and child.poll() is not None:
                    self._draining.discard(name)
                    self._downed.add(name)
                    phases[name] = "down"
                    self._beat(name, "down", rc=child.returncode)
                else:
                    phases[name] = "draining"
                    self._beat(name, "draining")
                continue
            if name in self._downed:
                phases[name] = "down"
                continue
            if child is not None and child.poll() is None:
                phases[name] = "running"
                continue
            times = self._restart_times[name] = [
                t for t in self._restart_times[name]
                if now - t <= self.restart_window_s]
            if len(times) >= self.max_restarts:
                phases[name] = "crash_loop"
                self._beat(name, "crash_loop",
                           restarts_in_window=len(times))
                continue
            rc = child.returncode if child is not None else None
            logger.warning(f"replica pool: {name} exited rc={rc}; "
                           f"restart {len(times) + 1}/{self.max_restarts}")
            times.append(now)
            self.restarts += 1
            self._spawn(name)
            phases[name] = "restarting"
            self._beat(name, "restarting", rc=rc,
                       restarts_in_window=len(times))
        return phases

    # -- elastic scale-up / scale-down --------------------------------------

    def add_replica(self) -> str:
        """Scale-up: spawn one more replica and return its name (the
        autoscaler's ``spawn_fn`` seam for process pools). Names never
        recycle — ``r<next>`` keeps doctor timelines unambiguous."""
        name = f"r{self._next_idx}"
        if self._chips > 1:
            self._claim_chip(name)      # refuses before anything changes
        self._next_idx += 1
        self.names.append(name)
        self._children[name] = None
        self._restart_times[name] = []
        self._spawn(name)
        self._beat(name, "running")
        return name

    def begin_drain(self, name: str) -> None:
        """Mark ``name`` as gracefully scaling down (the autoscaler's
        ``drain_fn`` seam). The process keeps running — the router is
        still finishing or failing over its streams — but heartbeats
        and :meth:`poll` read ``draining``, and only
        :meth:`finish_drain` / :meth:`stop` send the SIGTERM."""
        if name not in self._children:
            raise KeyError(f"no replica named {name!r}")
        if name in self._downed:
            return
        self._draining.add(name)
        self._beat(name, "draining")

    def finish_drain(self, name: str, grace_s: float = 5.0) -> None:
        """Complete a scale-down: the router drained ``name`` (no
        streams assigned, KV released) — now SIGTERM its process group,
        escalating to SIGKILL past ``grace_s``. The slot stays down."""
        if name not in self._draining:
            raise KeyError(f"{name!r} is not draining")
        self._draining.discard(name)
        self._downed.add(name)
        child = self._children.get(name)
        self._beat(name, "down", drained=True)
        if child is None or child.poll() is not None:
            return
        try:
            os.killpg(os.getpgid(child.pid), signal.SIGTERM)
        except ProcessLookupError:
            return
        try:
            child.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(child.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass
            child.wait()

    def kill(self, name: str, restart: bool = False) -> None:
        """SIGKILL one replica's process group (chaos ``replica_kill``
        at process scope). ``restart=True`` lets the next :meth:`poll`
        bring it back (counts against the rolling budget)."""
        child = self._children.get(name)
        if child is None:
            raise KeyError(f"no replica named {name!r}")
        if not restart:
            self._downed.add(name)
        if child.poll() is None:
            try:
                os.killpg(os.getpgid(child.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass
            child.wait()

    def stop(self, grace_s: float = 5.0,
             drain: Optional[Callable[[str], None]] = None) -> None:
        """Stop the pool with drain-before-SIGTERM ordering: every live
        replica is marked ``draining`` first (heartbeats say so, not
        ``crash_loop``), the ``drain`` callback — typically
        ``router.drain`` — gets each name so in-flight streams finish
        or fail over, and only then does SIGTERM land (SIGKILL for
        stragglers past ``grace_s``)."""
        for name, child in self._children.items():
            if name in self._downed or child is None or \
                    child.poll() is not None:
                continue
            self._draining.add(name)
            self._beat(name, "draining")
            if drain is not None:
                try:
                    drain(name)
                except Exception as e:
                    logger.warning(f"replica pool: drain callback for "
                                   f"{name} failed: {e}")
        self._downed.update(self.names)
        self._draining.clear()
        live = [c for c in self._children.values()
                if c is not None and c.poll() is None]
        for c in live:
            try:
                os.killpg(os.getpgid(c.pid), signal.SIGTERM)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + grace_s
        for c in live:
            try:
                c.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(c.pid), signal.SIGKILL)
                except ProcessLookupError:
                    pass
                c.wait()
        for name in self.names:
            self._beat(name, "down", stopped=True)


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: ``python -m deepspeed_tpu.launcher.agent -- cmd args...``
    with rendezvous env passed through (spawned over ssh by
    launcher/runner.py on each host). ``--pool N`` supervises N serving
    replicas of the command instead (each with DSTPU_REPLICA_NAME and,
    with ``--base-port``, its own DSTPU_HTTP_PORT)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-restarts", type=int,
                    default=int(os.environ.get("DSTPU_MAX_RESTARTS", 0)))
    ap.add_argument("--heartbeat-file", default=None,
                    help="per-host heartbeat JSON for dstpu-doctor "
                         "straggler naming (default: env "
                         "DSTPU_HEARTBEAT_FILE)")
    ap.add_argument("--pool", type=int, default=0, metavar="N",
                    help="supervise N serving-replica copies of the "
                         "command instead of one worker")
    ap.add_argument("--base-port", type=int, default=0,
                    help="with --pool: replica i serves /metrics on "
                         "base_port+i (DSTPU_HTTP_PORT)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("usage: agent.py [--max-restarts N] [--heartbeat-file F] "
              "[--pool N [--base-port P]] -- prog args...",
              file=sys.stderr)
        return 2
    if args.pool:
        pool = ReplicaPoolAgent(
            cmd, args.pool, base_port=args.base_port,
            max_restarts=args.max_restarts or 2).start()
        try:
            while True:
                phases = pool.poll()
                if all(p in ("down", "crash_loop")
                       for p in phases.values()):
                    logger.error(f"replica pool: no replica left "
                                 f"restartable ({phases}); exiting")
                    return 1
                time.sleep(1.0)
        except KeyboardInterrupt:
            return 0
        finally:
            pool.stop()
    return LaunchAgent(cmd, max_restarts=args.max_restarts,
                       heartbeat_file=args.heartbeat_file).run()


if __name__ == "__main__":
    sys.exit(main())
