"""Traffic generation for the chip benchmark: one general generator that
turns a traffic file (`traffic/<mix>.json`) and a seed into a request plan.

The arrival processes below are a copy of `repro.sim.arrivals` (sha256-
seeded streams, so a plan is a pure function of its labels and replays
the same in every process).  The benchmark keeps its own copy so that a
change to the program cannot move the yardstick.

On top of them sit the two plans the harness drives:

  open    requests due at fixed times (`stratified_poisson`): every seed
          gets the same multiset of exponential gaps and of operations,
          in another order, so the offered work does not change with the
          seed and only the interleaving does.
  closed  one queue of requests per client, each client sending its next
          request when the previous one is answered (no think time
          unless the file asks for it).

Operands are drawn uniformly from the integer range of the configuration;
FHE work does not depend on the values, only on the operation.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import random


def seeded_rng(*parts) -> random.Random:
    """A `random.Random` seeded from a stable digest of `parts`.

    `random.Random(tuple)` seeds via `hash()`, which Python randomizes per
    process for strings; hashing the repr through sha256 keeps every stream
    a pure function of its labels."""
    digest = hashlib.sha256(repr(parts).encode()).digest()
    return random.Random(int.from_bytes(digest[:8], "big"))


@dataclasses.dataclass(frozen=True)
class Poisson:
    """Open-loop Poisson arrivals at `rate` requests per second."""
    rate: float

    open_loop = True

    def schedule(self, duration_s: float, seed: int) -> list:
        rng = seeded_rng("poisson", seed, self.rate)
        out, t = [], 0.0
        while True:
            t += rng.expovariate(self.rate)
            if t >= duration_s:
                return out
            out.append(t)


@dataclasses.dataclass(frozen=True)
class MMPP:
    """Markov-modulated Poisson process: the rate steps through `segments`
    — a tuple of (rate_rps, duration_s) — cycling until the duration is
    exhausted."""
    segments: tuple

    open_loop = True

    def schedule(self, duration_s: float, seed: int) -> list:
        rng = seeded_rng("mmpp", seed, self.segments)
        out, t, seg = [], 0.0, 0
        seg_end = self.segments[0][1]
        while t < duration_s:
            rate = self.segments[seg % len(self.segments)][0]
            gap = rng.expovariate(rate) if rate > 0 else float("inf")
            if t + gap >= seg_end:
                t = seg_end
                seg += 1
                seg_end += self.segments[seg % len(self.segments)][1]
                continue
            t += gap
            if t >= duration_s:
                break
            out.append(t)
        return out


@dataclasses.dataclass(frozen=True)
class StratifiedPoisson:
    """Poisson-like open-loop arrivals with a fixed amount of work:
    round(rate * duration) arrivals whose gaps are the exponential
    distribution's quantiles at (i + 1/2) / M, shuffled by the seed.  Two
    seeds offer the same gaps in another order."""
    rate: float

    open_loop = True

    def schedule(self, duration_s: float, seed: int) -> list:
        m = max(1, round(self.rate * duration_s))
        gaps = [-math.log(1.0 - (i + 0.5) / m) / self.rate for i in range(m)]
        # the quantiles sum to slightly more or less than m / rate; scale
        # them so the last arrival lands inside the window
        scale = duration_s * (m - 0.5) / m / sum(gaps)
        seeded_rng("stratified", seed, self.rate).shuffle(gaps)
        out, t = [], 0.0
        for g in gaps:
            t += g * scale
            out.append(t)
        return out


@dataclasses.dataclass(frozen=True)
class ClosedLoop:
    """Closed-loop pacing: each client owns one outstanding request and
    waits `think_s` seconds between them."""
    think_s: float = 0.0

    open_loop = False


ARRIVALS = {"poisson": Poisson, "mmpp": MMPP,
            "stratified_poisson": StratifiedPoisson, "closed": ClosedLoop}


@dataclasses.dataclass
class PlannedRequest:
    """One request of a plan: which operation on which plaintexts, sent by
    which client, due when (seconds after the window opens; None in a
    closed loop, where the client sends it when its previous one is
    answered)."""
    index: int
    client: int
    op: str
    args: list
    due: float | None = None


@dataclasses.dataclass
class Plan:
    open_loop: bool
    clients: int
    requests: list            # PlannedRequest, in due order (open) or
                              # client-major queue order (closed)
    seed: int
    think_s: float = 0.0


def op_sequence(weights: dict, count: int, rng: random.Random) -> list:
    """`count` operations in the proportions of `weights` (whole numbers;
    largest-remainder rounding), the same multiset for every seed, in
    blocks of `weights[op]` of each operation, each block shuffled.  So
    every prefix holds each operation in its proportion to within one
    block: a window that sees the first requests sees the same mix
    whatever the seed."""
    names = sorted(weights)
    if any(int(weights[n]) != weights[n] or weights[n] < 1 for n in names):
        raise ValueError(f"operation weights must be whole numbers: "
                         f"{weights}")
    total = float(sum(weights[n] for n in names))
    exact = [weights[n] / total * count for n in names]
    counts = [int(x) for x in exact]
    order = sorted(range(len(names)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:count - sum(counts)]:
        counts[i] += 1
    left = dict(zip(names, counts))
    seq = []
    while len(seq) < count:
        block = [n for n in names for _ in range(min(int(weights[n]),
                                                     left[n]))]
        for n in block:
            left[n] -= 1
        rng.shuffle(block)
        seq.extend(block)
    return seq


def make_plan(traffic: dict, arity: dict, bits: int, seed: int,
              seconds: float) -> Plan:
    """Expand a traffic file into a request plan.

    traffic  the parsed `traffic/<mix>.json`
    arity    operation name -> number of integer operands
    bits     integer width; operands are uniform in [0, 2^bits)
    """
    arr = dict(traffic["arrivals"])
    kind = arr.pop("kind")
    process = ARRIVALS[kind](**{k: tuple(map(tuple, v)) if k == "segments"
                                else v for k, v in arr.items()})
    clients = int(traffic["clients"])
    weights = traffic["ops"]
    if traffic.get("operands", "uniform") != "uniform":
        raise ValueError(f"unknown operand distribution "
                         f"{traffic['operands']!r}")
    rng_ops = seeded_rng("ops", seed)
    rng_vals = seeded_rng("operands", seed)
    draw = lambda op: [rng_vals.getrandbits(bits) for _ in range(arity[op])]
    if process.open_loop:
        times = process.schedule(seconds, seed)
        ops = op_sequence(weights, len(times), rng_ops)
        reqs = [PlannedRequest(i, i % clients, op, draw(op), due=t)
                for i, (t, op) in enumerate(zip(times, ops))]
        return Plan(True, clients, reqs, seed)
    per_client = int(traffic["pool_per_client"])
    reqs = []
    for c in range(clients):
        for op in op_sequence(weights, per_client, rng_ops):
            reqs.append(PlannedRequest(len(reqs), c, op, draw(op)))
    return Plan(False, clients, reqs, seed, think_s=process.think_s)
