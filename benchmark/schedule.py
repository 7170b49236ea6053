"""The one traffic generator. A traffic mix is a data file under
`benchmark/traffic/`; this module turns it into a schedule.

THE RULE: everything that decides how much work a run does comes from
the traffic file (and the configuration's slot count) and is the same
for every seed: the list of (prompt length, output length) pairs, its
order, which client starts where, what is shared, how many clients.
`--seed` reaches exactly one function here, `token_ids`, and changes
token ids and nothing else.

Parameters a traffic file may set (all optional but the pairs):

  pairs              [[prompt_tokens, output_tokens], ...] written out, or
  pair_grid          {"n", "prompt": [lo, hi], "output": [lo, hi],
                      "output_power", "prompt_stride", "output_stride"}:
                     an even quantile grid, derived by `grid_pairs`
  loop               "closed" (default) or "open"
  clients_per_slot   closed loop: clients = this x the configuration's slots
  clients            closed loop: an absolute client count instead
  rate_rps           open loop: arrivals per second, timed from when due
  burst              open loop: {"size": n, "every_s": t}: n arrivals at
                     once every t seconds (rate_rps is then ignored)
  think_time_s       closed loop: pause between a result and the next call
  shared_prefix_tokens  one prefix shared by every call of the run
  session_turns      turns per session; turn k's prompt is the shared
                     prefix + the whole history + the turn's new tokens
  ramp               "call" (default): until every client completed one
                     call; "session": one whole session per client
  constraint         a ConstraintSpec passed through on every call
  transport          "unary" (default): benchmark/transports is not needed
                     for it; other names are looked up as files
  backend            "model" (default): benchmark/backends/<name>.py
  trace_ms           length of the profiler capture in a traced run
"""

from __future__ import annotations

import dataclasses
import json
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))

# Ids 0..2 are pad, bos and eos of the served tokenizer: never drawn.
FIRST_ID = 3


@dataclasses.dataclass(frozen=True)
class Schedule:
    """What a run does, free of the seed."""

    name: str
    loop: str
    clients: int
    pairs: tuple  # ((prompt_tokens, output_tokens), ...)
    offsets: tuple  # list offset each client starts at
    session_turns: int
    shared_prefix_tokens: int
    think_time_s: float
    rate_rps: float
    burst: tuple  # (size, every_s) or ()
    ramp: str
    constraint: object
    transport: str
    backend: str
    trace_ms: int

    def describe(self) -> dict:
        """The schedule as plain data: what the seed-independence test
        compares byte for byte."""
        return dataclasses.asdict(self)

    def longest_prompt(self) -> int:
        """Upper end of any prompt this schedule sends."""
        if self.session_turns <= 1:
            return self.shared_prefix_tokens + max(p for p, _ in self.pairs)
        worst = 0
        for start in range(0, len(self.pairs), self.session_turns):
            block = session_block(self, start)
            hist = sum(p + o for p, o in block[:-1]) + block[-1][0]
            worst = max(worst, hist)
        return self.shared_prefix_tokens + worst


def grid_pairs(spec: dict) -> list:
    """An even quantile grid of two uniform (or power-skewed) ranges,
    decorrelated and ordered by two strides coprime with n. A pure
    function of the file's numbers."""
    n = int(spec["n"])
    p_lo, p_hi = spec["prompt"]
    o_lo, o_hi = spec["output"]
    power = float(spec.get("output_power", 1.0))
    ps, os_ = int(spec.get("prompt_stride", 1)), int(spec.get("output_stride", 1))
    pairs = []
    for k in range(n):
        qi = ((k * ps) % n + 0.5) / n
        qo = ((k * os_ + n // 3) % n + 0.5) / n
        pairs.append([
            int(round(p_lo + (p_hi - p_lo) * qi)),
            int(round(o_lo + (o_hi - o_lo) * qo ** power)),
        ])
    return pairs


def load(name: str, slots: int, root: str = HERE) -> Schedule:
    """Read `traffic/<name>.json` and fix the schedule for a
    configuration with `slots` decode slots."""
    path = os.path.join(root, "traffic", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    pairs = spec.get("pairs") or grid_pairs(spec["pair_grid"])
    pairs = tuple((int(p), int(o)) for p, o in pairs)
    if not pairs or min(min(p, o) for p, o in pairs) < 1:
        raise ValueError(f"{path}: every pair needs >= 1 prompt and output token")
    loop = spec.get("loop", "closed")
    if loop not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be closed or open")
    turns = int(spec.get("session_turns", 1))
    if len(pairs) % turns:
        raise ValueError(f"{path}: {len(pairs)} pairs are not whole sessions of {turns}")
    if loop == "closed":
        clients = int(spec.get("clients") or spec.get("clients_per_slot", 1) * slots)
    else:
        clients = 0
        if not (spec.get("rate_rps") or spec.get("burst")):
            raise ValueError(f"{path}: an open loop needs rate_rps or burst")
    sessions = len(pairs) // turns
    # Client i starts i whole sessions (or calls) further down the
    # list, spread evenly over it.
    offsets = tuple(
        ((i * sessions) // max(clients, 1)) % sessions * turns
        for i in range(clients)
    )
    burst = spec.get("burst") or {}
    return Schedule(
        name=name, loop=loop, clients=clients, pairs=pairs, offsets=offsets,
        session_turns=turns,
        shared_prefix_tokens=int(spec.get("shared_prefix_tokens", 0)),
        think_time_s=float(spec.get("think_time_s", 0.0)),
        rate_rps=float(spec.get("rate_rps", 0.0)),
        burst=(int(burst["size"]), float(burst["every_s"])) if burst else (),
        ramp=spec.get("ramp", "call"),
        constraint=spec.get("constraint"),
        transport=spec.get("transport", "unary"),
        backend=spec.get("backend", "model"),
        trace_ms=int(spec.get("trace_ms", 3000)),
    )


def session_block(sched: Schedule, start: int) -> list:
    """The pairs of the session that starts at list index `start`."""
    n = len(sched.pairs)
    return [sched.pairs[(start + k) % n] for k in range(sched.session_turns)]


def token_ids(seed: int, vocab: int, n: int, *where) -> list:
    """`n` token ids for one place in the schedule. The only function
    that sees the seed."""
    rng = random.Random(f"{seed}/" + "/".join(str(w) for w in where))
    return [rng.randrange(FIRST_ID, vocab) for _ in range(n)]


def arrivals(sched: Schedule, horizon_s: float) -> list:
    """Open loop: the due time of every call up to `horizon_s`."""
    out = []
    if sched.burst:
        size, every = sched.burst
        t = 0.0
        while t < horizon_s:
            out.extend([t] * size)
            t += every
    else:
        k = 0
        while k / sched.rate_rps < horizon_s:
            out.append(k / sched.rate_rps)
            k += 1
    return out
