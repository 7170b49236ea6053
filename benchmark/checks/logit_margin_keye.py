"""Check `logit_margin_keye`: `logit_margin_dsv32`'s question, sample
and verdict (session prefixes, a client at a time, every turn of a
prefix compared; a limit for each named statistic, all of which must
hold), asked of `benchmark/reference_keye.py`, and PAIRED: with
`lower_planes` (a dtype) the reference child also runs with its K, V
and indexer keys rounded to that dtype, on the same tokens, and the
statistic `sq_margin_vs_lower` is the float32 reference's mean square
margin over that one's. One session prefix flips four times the tokens
of another whatever served them, which a ratio on the same tokens
leaves out; served from planes of the lower dtype, the tokens are the
lower reference's more than the float32 one's and the ratio passes its
limit. Parameters (configuration file, `check`): `max_tokens`,
`limits`, `no_selection`, `lower_planes`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ME = "check logit_margin_keye"


def _dsv32():
    from benchmark import plugins

    return plugins.load("checks", "logit_margin_dsv32", [os.path.dirname(HERE)])


def sample(calls: list, t0: float, t1: float, max_tokens: int) -> list:
    return _dsv32().sample(calls, t0, t1, max_tokens)


def run(ctx: dict) -> dict:
    params = ctx["config"]["check"]
    seqs = sample(ctx["all_calls"], ctx["t0"], ctx["t1"],
                  int(params.get("max_tokens", 32768)))
    if not seqs:
        return {"correct": False,
                "lines": [f"{ME}: no call of a session completed inside "
                          "the window, nothing to compare"]}
    job_path = os.path.join(ctx["out_dir"], "reference_job.json")
    with open(job_path, "w") as f:
        json.dump({"config_file": ctx["config_path"], "cpu": ctx["cpu"],
                   "no_selection": bool(params.get("no_selection")),
                   "lower_planes": params.get("lower_planes", ""),
                   "sequences": seqs}, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(HERE), "reference_keye.py"), job_path],
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
    ok = bool(result["finite"]) and bool(limits) and not over
    each = result.get("per_sequence", [])
    return {"correct": ok, "result": result, "lines": [
        f"{ME}: {len(seqs)} session prefixes of "
        f"{[s['turns'] for s in seqs]} turns and "
        f"{[len(s['ids']) for s in seqs]} tokens, {result['tokens']} returned "
        f"tokens teacher-forced through the float32 reference "
        f"({'with' if result.get('selection', True) else 'WITHOUT'} its "
        f"selection) on {result['platform']} ({result['kind']}) in "
        f"{result['seconds']:.1f} s",
        f"{ME}: " + ", ".join(
            f"{k} = {result.get(k)!r} (limit {v!r}: "
            f"{'OVER' if k in over else 'within'})"
            for k, v in limits.items())
        + f"; max_margin_sigma = {result['max_margin_sigma']!r}, "
        f"mean_sq_margin_sigma a prefix = "
        f"{[round(s['mean_sq_margin_sigma'], 4) for s in each]}, "
        f"flip_share a prefix = {[round(s['flip_share'], 4) for s in each]}"
        + (f", sq_margin_vs_lower ({result['lower_planes']} planes) a prefix "
           f"= {[round(s['sq_margin_vs_lower'], 4) for s in each]}"
           if result.get("lower_planes") else ""),
    ]}
