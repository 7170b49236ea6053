"""Check `logit_margin_jamba`: `logit_margin_keye`'s question, sample
and verdict (session prefixes, a client at a time, every turn of a
prefix compared: turns 2.. were admitted through restored state
snapshots; a limit for each named statistic, all of which must hold),
asked of `benchmark/reference_jamba.py`, and one statistic that is no
margin: `state_bytes_short_share`, the share of a row's recurrent
state, counted from the configuration's widths at its stated precisions
(`roofline_jamba.state_bytes_per_row`: `h` float32), that an entry of
the served pool does not hold, read off the batcher's own start-up line
in the stack's log (`row states: <entries> entries x <bytes> B an
entry`, summed over the pool's device arrays). A pool whose `h` is
bfloat16 changes the served tokens by less than the model's own bf16
arithmetic does (the configuration file's `limit_read_from`), so no
margin tells it from a sound run; its bytes do. A stack that never said
reads None, which is over every limit. Parameters (configuration file,
`check`): `max_tokens`, `limits`.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ME = "check logit_margin_jamba"
ROW_STATES = re.compile(r"row states: (\d+) entries x (\d+) B an entry")


def state_bytes_short_share(log_text: str, model: dict):
    """None where the stack's log has no such line; the last one
    counts (a stack started twice)."""
    from benchmark import roofline_jamba

    said = ROW_STATES.findall(log_text)
    if not said:
        return None
    stated = roofline_jamba.state_bytes_per_row(model)
    return max(0.0, 1.0 - int(said[-1][1]) / stated)


def sample(calls: list, t0: float, t1: float, max_tokens: int) -> list:
    from benchmark import plugins

    return plugins.load(
        "checks", "logit_margin_dsv32", [os.path.dirname(HERE)]
    ).sample(calls, t0, t1, max_tokens)


def run(ctx: dict) -> dict:
    params = ctx["config"]["check"]
    seqs = sample(ctx["all_calls"], ctx["t0"], ctx["t1"],
                  int(params.get("max_tokens", 16384)))
    if not seqs:
        return {"correct": False,
                "lines": [f"{ME}: no call of a session completed inside "
                          "the window, nothing to compare"]}
    job_path = os.path.join(ctx["out_dir"], "reference_job.json")
    with open(job_path, "w") as f:
        json.dump({"config_file": ctx["config_path"], "cpu": ctx["cpu"],
                   "sequences": seqs}, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(HERE), "reference_jamba.py"), job_path],
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
    with open(os.path.join(ctx["out_dir"], "stack.log"), errors="replace") as f:
        result["state_bytes_short_share"] = state_bytes_short_share(
            f.read(), ctx["config"])
    limits = {k: float(v) for k, v in params["limits"].items()}
    over = [k for k, v in limits.items()
            if result.get(k) is None or result[k] > v]
    ok = bool(result["finite"]) and bool(limits) and not over
    each = result.get("per_sequence", [])
    return {"correct": ok, "result": result, "lines": [
        f"{ME}: {len(seqs)} session prefixes of "
        f"{[s['turns'] for s in seqs]} turns and "
        f"{[len(s['ids']) for s in seqs]} tokens, {result['tokens']} returned "
        f"tokens teacher-forced through the float32 reference on "
        f"{result['platform']} ({result['kind']}) in {result['seconds']:.1f} s",
        f"{ME}: " + ", ".join(
            f"{k} = {result.get(k)!r} (limit {v!r}: "
            f"{'OVER' if k in over else 'within'})"
            for k, v in limits.items())
        + f"; max_margin_sigma = {result['max_margin_sigma']!r}, "
        f"mean_sq_margin_sigma a prefix = "
        f"{[round(s['mean_sq_margin_sigma'], 4) for s in each]}, "
        f"flip_share a prefix = {[round(s['flip_share'], 4) for s in each]}",
    ]}
