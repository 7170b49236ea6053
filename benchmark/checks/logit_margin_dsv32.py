"""Check `logit_margin_dsv32`: `logit_margin`'s question (are the
tokens the server returned the ones a float32 reference of the same
model would pick?) asked of `benchmark/reference_dsv32.py`, over
SESSION PREFIXES. `logit_margin.sample` takes whole sessions that end
inside the window; the long-context agent cell's sessions (64 turns)
never end there. So this check samples, a client at a time, the
session's history up to the client's last call completed inside the
window: that call's prompt (the whole history) plus its output, with
every turn of it compared, the document turn's answer from before the
window among them. Parameters (configuration file, `check`):
`max_tokens` (forward tokens to spend), `limits` (a limit for each
named statistic: the run is correct if every one holds, so a lower
precision has to break one of them, not each), `no_selection` (the
reference attends every visible key: `correct` must then come out
false, which shows that the check sees the selection).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def sample(calls: list, t0: float, t1: float, max_tokens: int) -> list:
    """Session prefixes, by client and then session: for each session,
    its turns up to the last one completed inside the window, as one
    reference sequence. `max_tokens` pays for two or three of them, so
    the walk starts at a client the run's own token ids name (the sum
    of the first client's first prompt, which the seed draws): over
    the seeds every slot comes under the reference, and one run's
    sample is still a function of its inputs alone."""
    sessions: dict = {}
    for c in calls:
        if c.phase != "probe" and c.session >= 0:
            sessions.setdefault((c.client, c.session), []).append(c)
    order = sorted(sessions)
    if order:
        clients = sorted({client for client, _ in order})
        first = min(sessions[order[0]], key=lambda c: c.turn)
        start = clients[sum(first.prompt) % len(clients)]
        order = ([k for k in order if k[0] >= start]
                 + [k for k in order if k[0] < start])
    picked, width_sum = [], 0
    for key in order:
        turns = sorted(sessions[key], key=lambda c: c.turn)
        inside = [k for k, c in enumerate(turns) if t0 <= c.done < t1]
        if not inside:
            continue
        turns = turns[: inside[-1] + 1]
        last = turns[-1]
        whole = [c.turn for c in turns] == list(range(len(turns)))
        if not (whole and all(c.ok for c in turns)):
            continue
        if any(c.prompt != last.prompt[: len(c.prompt)] for c in turns):
            continue  # not one growing history
        ids = last.prompt + last.output
        if picked and width_sum + len(ids) > max_tokens:
            break
        picked.append({
            "ids": ids,
            # a turn's segments are positions in its own prompt +
            # output, and its prompt is the head of the last turn's
            "compare": [seg for c in turns for seg in c.segments],
            "turns": len(turns),
        })
        width_sum += len(ids)
    return picked


def run(ctx: dict) -> dict:
    params = ctx["config"]["check"]
    seqs = sample(ctx["all_calls"], ctx["t0"], ctx["t1"],
                  int(params.get("max_tokens", 32768)))
    if not seqs:
        return {"correct": False,
                "lines": ["check logit_margin_dsv32: no call of a session "
                          "completed inside the window, nothing to compare"]}
    job_path = os.path.join(ctx["out_dir"], "reference_job.json")
    with open(job_path, "w") as f:
        json.dump({"config_file": ctx["config_path"], "cpu": ctx["cpu"],
                   "no_selection": bool(params.get("no_selection")),
                   "sequences": seqs}, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "reference_dsv32.py"),
         job_path],
        cwd=ctx["root"], env=env, capture_output=True, text=True,
        timeout=ctx["check_timeout_s"],
    )
    with open(os.path.join(ctx["out_dir"], "reference.log"), "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    if proc.returncode != 0 or not proc.stdout.strip():
        return {"correct": False,
                "lines": [f"check logit_margin_dsv32: the reference child "
                          f"exited {proc.returncode}: {proc.stderr[-600:]}"]}
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    limits = {k: float(v) for k, v in params["limits"].items()}
    over = [k for k, v in limits.items()
            if result.get(k) is None or result[k] > v]
    ok = bool(result["finite"]) and bool(limits) and not over
    each = result.get("per_sequence", [])
    return {"correct": ok, "result": result, "lines": [
        f"check logit_margin_dsv32: {len(seqs)} session prefixes of "
        f"{[s['turns'] for s in seqs]} turns and "
        f"{[len(s['ids']) for s in seqs]} tokens, {result['tokens']} returned "
        f"tokens teacher-forced through the float32 reference "
        f"({'with' if result.get('selection', True) else 'WITHOUT'} its "
        f"selection) on {result['platform']} ({result['kind']}) in "
        f"{result['seconds']:.1f} s",
        "check logit_margin_dsv32: " + ", ".join(
            f"{k} = {result.get(k)!r} (limit {v!r}: "
            f"{'OVER' if k in over else 'within'})"
            for k, v in limits.items())
        + f"; max_margin_sigma = {result['max_margin_sigma']!r}, "
        f"mean_sq_margin_sigma a prefix = "
        f"{[round(s['mean_sq_margin_sigma'], 4) for s in each]}, "
        f"flip_share a prefix = {[round(s['flip_share'], 4) for s in each]}",
    ]}
