"""Check `logit_margin_mla_moe`: `logit_margin`'s question (are the
tokens the server returned the ones a float32 reference of the same
model would pick?) asked of `benchmark/reference_mla_moe.py`, the
reference of the latent-attention + routed-experts family. The sample
of sessions is `logit_margin.sample`'s, the statistics and their
meaning are `reference.py`'s; only the reference child differs, because
`reference.py` knows one layer and one weights recipe. Parameters
(configuration file, `check`): `max_tokens`, and `limits`, a limit for
each named statistic of the reference's result: the run is correct if
every one holds, so a lower precision has to break one of them, not
each.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def run(ctx: dict) -> dict:
    from benchmark import plugins

    params = ctx["config"]["check"]
    sample = plugins.load("checks", "logit_margin", [ctx["harness_dir"]]).sample
    seqs = sample(ctx["all_calls"], ctx["t0"], ctx["t1"],
                  int(params.get("max_tokens", 16384)))
    if not seqs:
        return {"correct": False,
                "lines": ["check logit_margin_mla_moe: no whole session "
                          "completed inside the window, nothing to compare"]}
    job_path = os.path.join(ctx["out_dir"], "reference_job.json")
    with open(job_path, "w") as f:
        json.dump({"config_file": ctx["config_path"], "cpu": ctx["cpu"],
                   "sequences": seqs}, f)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "reference_mla_moe.py"),
         job_path],
        cwd=ctx["root"], env=env, capture_output=True, text=True,
        timeout=ctx["check_timeout_s"],
    )
    with open(os.path.join(ctx["out_dir"], "reference.log"), "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    if proc.returncode != 0 or not proc.stdout.strip():
        return {"correct": False,
                "lines": [f"check logit_margin_mla_moe: the reference child "
                          f"exited {proc.returncode}: {proc.stderr[-600:]}"]}
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    limits = {k: float(v) for k, v in params["limits"].items()}
    over = [k for k, v in limits.items()
            if result.get(k) is None or result[k] > v]
    ok = bool(result["finite"]) and bool(limits) and not over
    each = [s["mean_sq_margin_sigma"] for s in result.get("per_sequence", [])]
    return {"correct": ok, "result": result, "lines": [
        f"check logit_margin_mla_moe: {len(seqs)} sessions, "
        f"{result['tokens']} returned tokens teacher-forced through the "
        f"float32 reference on {result['platform']} ({result['kind']}) in "
        f"{result['seconds']:.1f} s",
        "check logit_margin_mla_moe: " + ", ".join(
            f"{k} = {result.get(k)!r} (limit {v!r}: "
            f"{'OVER' if k in over else 'within'})"
            for k, v in limits.items())
        + f"; max_margin_sigma = {result['max_margin_sigma']!r}, "
        f"mean_sq_margin_sigma a session = "
        f"{[round(x, 4) for x in each]}, flip_share a session = "
        f"{[round(s['flip_share'], 4) for s in result.get('per_sequence', [])]}",
    ]}
