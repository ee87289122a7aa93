"""The one general traffic generator. A mix is a data file under
``benchmark/traffic/``; everything a cell's load needs is a parameter
there, so a later PR adds a mix as a file and touches no code.

Every seed gets the SAME requests in the SAME cyclic order: the mix alone
fixes a cycle of ``POOL_SIZE`` (prompt, output) sizes and, open loop, of
``GAP_POOL`` inter-arrival gaps; ``--seed`` picks where in the cycle a run
begins and draws the token ids. So two seeds do the same work from another
starting point, and a window that passes through the cycle a few times
does the same amount of it. The sizes are not drawn: size ``i`` of ``n``
is the distribution's quantile ``(i + 0.5) / n``, so a small cycle still
has the distribution's shape; ``cycle_seed`` (part of the mix, like its
lengths) pairs prompts with outputs and orders the cycle.

Mix file, kind ``serve`` (these keys and no others)::

    {"kind": "serve", "why": "...",
     "arrival": {"process": "closed", "clients": 64}
              | {"process": "poisson", "rate": 4.0}
              | {"process": "gamma", "rate": 4.0, "cv": 3.0},
     "prompt_tokens": <dist>, "output_tokens": <dist>,
     "max_total_tokens": 4096, "cycle_seed": 1,
     "ramp_seconds": 10, "trace_seconds": 3}

Closed loop: each client's next request is due the moment its last one
ended. Each client's FIRST request is cut as if the client were already
part-way through it: client ``c`` of ``n`` keeps ``(c + 0.5) / n`` of its
output and at most ``RAMP_FIRST_PROMPT`` prompt tokens, so the loop starts
near its steady state and not with every client prefilling at once. Open
loop: due times are the running sum of the gaps (mean exactly 1/rate over
a cycle), whatever the system does.

``<dist>`` is ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}``, ``{"dist": "uniform", "min": a, "max": b}`` or ``{"dist":
"fixed", "value": v}``; all lengths are whole tokens, clipped to
[min, max].

Mix file, kind ``train``::

    {"kind": "train", "why": "...", "seq_len": 4096, "global_batch": 4,
     "distinct_batches": 16, "warmup_steps": 2, "trace_steps": 3}
"""

import json
import math
import os
import statistics
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: sizes in a mix's cycle; a 45 s window of a saturated server passes
#: through it once or twice
POOL_SIZE = 32
#: open loop: gaps in a mix's cycle
GAP_POOL = 4096
#: closed loop: a client's first prompt is cut to this many tokens (one
#: prefill chunk of the serving configurations)
RAMP_FIRST_PROMPT = 128

KEYS = {
    "serve": {"kind", "why", "arrival", "prompt_tokens", "output_tokens",
              "max_total_tokens", "cycle_seed", "ramp_seconds",
              "trace_seconds"},
    "train": {"kind", "why", "seq_len", "global_batch", "distinct_batches",
              "warmup_steps", "trace_steps"},
}


def check_mix(mix: dict, where: str = "mix") -> dict:
    if mix.get("kind") not in KEYS:
        # a kind is a runner and its mix keys together: a new one takes a
        # `benchmark` issue (README.md), never a file beside these
        raise ValueError(f"{where}: kind must be one of {sorted(KEYS)}")
    unknown = set(mix) - KEYS[mix["kind"]]
    if unknown:
        raise ValueError(f"{where}: unknown keys {sorted(unknown)}")
    return mix


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as fh:
        return check_mix(json.load(fh), path)


def lengths(dist: dict, u: np.ndarray) -> np.ndarray:
    """The distribution's quantiles at ``u`` (0 < u < 1), in whole tokens,
    clipped to [min, max]."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(len(u), int(dist["value"]), np.int64)
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        x = lo + u * (hi + 1 - lo) - 0.5
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(q)) for q in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def size_cycle(mix: dict) -> np.ndarray:
    """[POOL_SIZE, 2] (prompt, output) lengths in the mix's fixed cyclic
    order — a function of the mix alone. prompt + output never exceeds
    ``max_total_tokens`` (the output is cut, never below 1)."""
    n = POOL_SIZE
    grid = (np.arange(n) + 0.5) / n
    seed = int(mix["cycle_seed"])
    prompts = lengths(mix["prompt_tokens"], grid)
    outs = lengths(mix["output_tokens"], grid)[
        np.random.default_rng(seed).permutation(n)]
    cap = int(mix["max_total_tokens"])
    if (prompts >= cap).any():
        raise ValueError("a prompt alone reaches max_total_tokens")
    outs = np.maximum(1, np.minimum(outs, cap - prompts))
    pool = np.stack([prompts, outs], axis=1)
    return pool[np.random.default_rng(seed + 2).permutation(n)]


def gap_cycle(mix: dict) -> Optional[np.ndarray]:
    """Open loop: [GAP_POOL] inter-arrival gaps in seconds with mean
    exactly 1/rate and coefficient of variation ``cv`` (gamma; cv 1 is
    Poisson), in the mix's fixed cyclic order. None for a closed loop."""
    arr = mix["arrival"]
    if arr["process"] == "closed":
        return None
    if arr["process"] not in ("poisson", "gamma"):
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate"])
    cv = 1.0 if arr["process"] == "poisson" else float(arr["cv"])
    rng = np.random.default_rng(int(mix["cycle_seed"]) + 1)
    gaps = rng.gamma(1.0 / (cv * cv), 1.0, size=GAP_POOL)
    return gaps / (gaps.mean() * rate)


@dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    prompt: List[int]
    max_new_tokens: int
    due: float                    # seconds on the runner's clock
    client: Optional[int] = None  # closed loop only


class Arrivals:
    """Hands the runner what is due. Closed loop: ``clients`` requests due
    at ``t0``, then each client's next the moment its last one ended
    (``finished``). Open loop: due times are ``t0`` + the running sum of
    the gaps, whatever the system does."""

    def __init__(self, mix: dict, seed: int, vocab_size: int, t0: float):
        check_mix(mix)
        self.vocab = int(vocab_size)
        self.rng = np.random.default_rng([int(seed), 1])
        cycle = size_cycle(mix)
        self._sizes = np.roll(cycle, -int(self.rng.integers(len(cycle))),
                              axis=0)
        gaps = gap_cycle(mix)
        self.closed = gaps is None
        self.next_index = 0
        self.t0 = float(t0)
        self._first: set = set()      # closed loop: clients yet to start
        if self.closed:
            self._clients = int(mix["arrival"]["clients"])
            self._ready: List[Tuple[float, int]] = [
                (self.t0, c) for c in range(self._clients)]
            self._first = set(range(self._clients))
        else:
            self._gaps = np.roll(gaps, -int(self.rng.integers(len(gaps))))
            self._due = self.t0 + np.cumsum(self._gaps)

    def _plan(self, due: float, client: Optional[int]) -> Planned:
        i = self.next_index
        self.next_index += 1
        p_len, o_len = (int(x) for x in self._sizes[i % len(self._sizes)])
        if client in self._first:
            # a client's first request starts part-way through, so the
            # loop does not begin in lock-step
            self._first.discard(client)
            o_len = max(1, int(math.ceil(
                o_len * (client + 0.5) / self._clients)))
            p_len = min(p_len, RAMP_FIRST_PROMPT)
        prompt = self.rng.integers(0, self.vocab, size=p_len).tolist()
        return Planned(i, prompt, o_len, due, client)

    def due(self, now: float) -> List[Planned]:
        out: List[Planned] = []
        if self.closed:
            keep = []
            for t, c in self._ready:
                if t <= now:
                    out.append(self._plan(t, c))
                else:
                    keep.append((t, c))
            self._ready = keep
            return out
        while self.next_due() <= now:
            out.append(self._plan(float(self._due[self.next_index]), None))
        return out

    def finished(self, planned: Planned, when: float) -> None:
        """Closed loop: the client's next request is due now."""
        if self.closed:
            self._ready.append((float(when), planned.client))

    def next_due(self) -> Optional[float]:
        """When the next request is due, if that is known beforehand."""
        if self.closed:
            return min((t for t, _ in self._ready), default=None)
        while self.next_index >= len(self._due):   # another pass of gaps
            self._due = np.concatenate(
                [self._due, self._due[-1] + np.cumsum(self._gaps)])
        return float(self._due[self.next_index])


def train_batches(mix: dict, seed: int, vocab_size: int) -> np.ndarray:
    """[distinct_batches, global_batch, seq_len] int32 token ids from the
    seed; the runner cycles them."""
    rng = np.random.default_rng([int(seed), 0])
    return rng.integers(
        0, int(vocab_size),
        size=(int(mix["distinct_batches"]), int(mix["global_batch"]),
              int(mix["seq_len"])), dtype=np.int32)
