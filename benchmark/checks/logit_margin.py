"""Check `logit_margin`: are the tokens the server returned the ones a
float32 reference of the same model would pick?

A seeded-order sample of the window's sessions is replayed through
`benchmark/reference.py` in a child process, after the stack has let go
of the chip. Teacher-forced along the returned ids, the reference gives
each returned token its margin (reference's largest logit minus its
logit of the chosen token, in standard deviations of that position's
logits). The configuration file names the statistic and its limit; how
the limit was read is in PERF.md. Parameters (configuration file,
`check`): `statistic`, `limit`, `max_tokens` (forward tokens to spend),
`batch_tokens` (tokens per reference call).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def sample(calls: list, t0: float, t1: float, max_tokens: int) -> list:
    """Whole sessions whose last call completed inside the window, in a
    fixed order (client, then session), as reference sequences."""
    sessions: dict = {}
    for c in calls:
        if c.phase != "probe" and c.session >= 0:
            sessions.setdefault((c.client, c.session), []).append(c)
    picked, width_sum = [], 0
    for key in sorted(sessions):
        turns = sorted(sessions[key], key=lambda c: c.turn)
        last = turns[-1]
        whole = [c.turn for c in turns] == list(range(len(turns)))
        if not (whole and all(c.ok for c in turns) and t0 <= last.done < t1):
            continue
        if len(turns) > 1 and any(
            turns[k].prompt != last.prompt[: len(turns[k].prompt)]
            for k in range(len(turns) - 1)
        ):
            continue  # not one growing history (a one-turn mix)
        ids = last.prompt + last.output
        picked.append({
            "ids": ids,
            # a turn's segments are positions in its own prompt + output,
            # and its prompt is the head of the last turn's
            "compare": [seg for c in turns for seg in c.segments],
        })
        width_sum += len(ids)
        if width_sum >= max_tokens:
            break
    return picked


def run(ctx: dict) -> dict:
    params = ctx["config"]["check"]
    max_tokens = int(params.get("max_tokens", 16384))
    batch_tokens = int(params.get("batch_tokens", 4096))
    seqs = sample(ctx["all_calls"], ctx["t0"], ctx["t1"], max_tokens)
    if not seqs:
        return {"correct": False,
                "lines": ["check logit_margin: no whole session completed "
                          "inside the window, nothing to compare"]}
    longest = max(len(s["ids"]) for s in seqs)
    width = max(32, 1 << (longest - 1).bit_length())
    rows = max(1, batch_tokens // width)
    job = {
        "config_file": ctx["config_path"], "cpu": ctx["cpu"],
        "rows": rows, "width": width, "sequences": seqs,
    }
    job_path = os.path.join(ctx["out_dir"], "reference_job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ctx["harness_dir"], "reference.py"),
         job_path],
        cwd=ctx["root"], env=env, capture_output=True, text=True,
        timeout=ctx["check_timeout_s"],
    )
    with open(os.path.join(ctx["out_dir"], "reference.log"), "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    if proc.returncode != 0 or not proc.stdout.strip():
        return {"correct": False,
                "lines": [f"check logit_margin: the reference child exited "
                          f"{proc.returncode}: {proc.stderr[-600:]}"]}
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    stat, limit = params["statistic"], float(params["limit"])
    value = result.get(stat)
    ok = bool(result["finite"]) and value is not None and value <= limit
    lines = [
        f"check logit_margin: {len(seqs)} sessions, {result['tokens']} "
        f"returned tokens teacher-forced through the float32 reference on "
        f"{result['platform']} ({result['kind']}) in {result['seconds']:.1f} s",
        f"check logit_margin: {stat} = {value!r} (limit {limit!r}: "
        f"{'within' if ok else 'OVER'}); flip_share = "
        f"{result['flip_share']!r}, max_margin_sigma = "
        f"{result['max_margin_sigma']!r}, mean_sq_margin_sigma = "
        f"{result['mean_sq_margin_sigma']!r}",
    ]
    return {"correct": ok, "lines": lines, "result": result}
