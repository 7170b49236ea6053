"""Check `logit_margin_smallthinker`: `logit_margin_dsv32`'s question
and verdict (session prefixes, a client at a time, every turn of a
prefix compared; a limit for each named statistic, all of which must
hold), asked of `benchmark/reference_smallthinker.py`, with one rule
more for the sample: **the first prefix taken is a LONG one**
(more than `long_over` tokens), so that contexts past the window are
compared in every run. `logit_margin_dsv32.sample` walks the clients
from one the run's own token ids name and would, one run in four, spend
its `max_tokens` on short chat sessions alone; this one walks the same
order and skips the prefixes that are not long until it holds one.
Parameters (configuration file, `check`): `max_tokens`, `limits`,
`long_over`, `no_window` (the reference's window layers
attend every visible key: `correct` must then come out false, which
shows that the sample crossed the window).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ME = "check logit_margin_smallthinker"


def _dsv32():
    from benchmark import plugins

    return plugins.load("checks", "logit_margin_dsv32", [os.path.dirname(HERE)])


def sample(calls: list, t0: float, t1: float, max_tokens: int,
           long_over: int = 0) -> list:
    """`logit_margin_dsv32.sample`'s prefixes in its order (every one,
    then cut to `max_tokens` here), the first of more than `long_over`
    tokens moved to the front."""
    every = _dsv32().sample(calls, t0, t1, 1 << 62)
    first = next(
        (k for k, s in enumerate(every) if len(s["ids"]) > long_over), 0)
    every = every[first: first + 1] + every[:first] + every[first + 1:]
    picked, width_sum = [], 0
    for seq in every:
        if picked and width_sum + len(seq["ids"]) > max_tokens:
            break
        picked.append(seq)
        width_sum += len(seq["ids"])
    return picked


def run(ctx: dict) -> dict:
    params = ctx["config"]["check"]
    long_over = int(params.get("long_over", 0))
    seqs = sample(ctx["all_calls"], ctx["t0"], ctx["t1"],
                  int(params.get("max_tokens", 32768)), long_over)
    if not seqs:
        return {"correct": False,
                "lines": [f"{ME}: no call of a session completed inside "
                          "the window, nothing to compare"]}
    job_path = os.path.join(ctx["out_dir"], "reference_job.json")
    with open(job_path, "w") as f:
        json.dump({"config_file": ctx["config_path"], "cpu": ctx["cpu"],
                   "no_window": bool(params.get("no_window")),
                   "sequences": seqs}, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(HERE), "reference_smallthinker.py"),
         job_path],
        cwd=ctx["root"], env=env, capture_output=True, text=True,
        timeout=ctx["check_timeout_s"],
    )
    with open(os.path.join(ctx["out_dir"], "reference.log"), "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    if proc.returncode != 0 or not proc.stdout.strip():
        return {"correct": False,
                "lines": [f"{ME}: the reference child exited "
                          f"{proc.returncode}: {proc.stderr[-600:]}"]}
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    limits = {k: float(v) for k, v in params["limits"].items()}
    over = [k for k, v in limits.items()
            if result.get(k) is None or result[k] > v]
    crossed = len(seqs[0]["ids"]) > long_over
    ok = bool(result["finite"]) and bool(limits) and not over and crossed
    each = result.get("per_sequence", [])
    return {"correct": ok, "result": result, "lines": [
        f"{ME}: {len(seqs)} session prefixes of "
        f"{[s['turns'] for s in seqs]} turns and "
        f"{[len(s['ids']) for s in seqs]} tokens (the first "
        f"{'is' if crossed else 'is NOT'} longer than {long_over}), "
        f"{result['tokens']} returned tokens teacher-forced through the "
        f"float32 reference ({'with' if result.get('window', True) else 'WITHOUT'}"
        f" its window) on {result['platform']} ({result['kind']}) in "
        f"{result['seconds']:.1f} s",
        f"{ME}: " + ", ".join(
            f"{k} = {result.get(k)!r} (limit {v!r}: "
            f"{'OVER' if k in over else 'within'})"
            for k, v in limits.items())
        + f"; max_margin_sigma = {result['max_margin_sigma']!r}, "
        f"mean_sq_margin_sigma a prefix = "
        f"{[round(s['mean_sq_margin_sigma'], 4) for s in each]}, "
        f"flip_share a prefix = {[round(s['flip_share'], 4) for s in each]}",
    ]}
