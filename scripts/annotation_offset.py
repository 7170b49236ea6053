#!/usr/bin/env python3
"""Check the program's tick clock against the profiler's.

While a profile capture runs, every executor work item of the batcher
is a `jax.profiler.TraceAnnotation` (`ggrmcp.tick.dispatch`,
`ggrmcp.tick.collect`, `ggrmcp.admit`) tagged with the `seq` of its
tick or admission record. This script serves a model, captures a trace
under load, fetches the tick and admission rings (`/debug/ticks`), and
reports, for each `seq` found in both, the offset between the
annotation's start on the profiler's axis and the record's `t_wall`:

    python3 scripts/annotation_offset.py            # on the TPU
    python3 scripts/annotation_offset.py --cpu      # rehearsal, tiny-mistral

It prints one JSON line and keeps the trace and the rings under
`chiprun_out/annotation_offset/`. The parent never touches the device:
the stack is a child (the benchmark's launcher), and the trace is read
after it has stopped.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import random
import shutil
import statistics
import sys
import warnings

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import client  # noqa: E402
from benchmark.backends import model  # noqa: E402

GENERATE = "ggrmcp_tpu_generateservice_generate"
ANNOTATIONS = ("ggrmcp.tick.dispatch", "ggrmcp.tick.collect", "ggrmcp.admit")


async def drive(port: int, args) -> tuple:
    """Closed-loop clients; once each has completed a call (every shape
    compiled), one capture, then the rings."""
    rng = random.Random(7)
    done = [0] * args.clients
    stop = False

    async def loop(i: int) -> None:
        http = client.Http("127.0.0.1", port, 600.0)
        try:
            while not stop:
                prompt = [rng.randrange(3, args.vocab) for _ in range(args.prompt)]
                await http.tool(GENERATE, client.generate_arguments(prompt, args.new))
                done[i] += 1
        finally:
            http.close()

    tasks = [asyncio.ensure_future(loop(i)) for i in range(args.clients)]
    ctl = client.Http("127.0.0.1", port, 600.0)
    try:
        while min(done) < 2:
            for t in tasks:
                if t.done():
                    t.result()
            await asyncio.sleep(0.05)
        body = json.loads(await ctl.request(
            "POST", f"/debug/profile?duration_ms={args.capture_ms}&label=offset"))
        path = body["backends"][0].get("outputPath", "")
        rings = json.loads(await ctl.request("GET", "/debug/ticks?n=2048"))
    finally:
        stop = True
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        ctl.close()
    return path, rings["backends"][0]


def annotation_starts(xplane: str) -> dict:
    """{annotation name: {seq: start in epoch seconds}} from the trace's
    host planes. Event times in the file count from the capture's
    start, which the "Task Environment" plane gives on the wall clock
    (`profile_start_time`, epoch ns)."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(xplane).planes)
    t0_ns = next(
        int(dict(p.stats)["profile_start_time"])
        for p in planes if p.name == "Task Environment")
    found: dict = {name: {} for name in ANNOTATIONS}
    for plane in planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in found:
                    stats = dict(event.stats)
                    if "seq" in stats:
                        found[event.name][int(stats["seq"])] = (
                            t0_ns + event.start_ns) / 1e9
    return found


def summary(offsets_ms: list) -> dict:
    if not offsets_ms:
        return {"n": 0}
    return {"n": len(offsets_ms), "median_ms": statistics.median(offsets_ms),
            "min_ms": min(offsets_ms), "max_ms": max(offsets_ms)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU at tiny-mistral; NOT a chip result")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=100)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--capture-ms", type=int, default=1500)
    args = ap.parse_args()
    name = ("tests/benchmark/rehearsal/benchmark/configs/tiny-mistral-cpu.json"
            if args.cpu else "benchmark/configs/mistral-7b-int8-1chip.json")
    with open(os.path.join(ROOT, name)) as f:
        config = json.load(f)
    args.vocab = int(config["vocab_size"])
    out_dir = os.path.join(ROOT, "chiprun_out", "annotation_offset")
    stack = model.launch(ROOT, out_dir, config["stack"], args.cpu, 1000.0)
    try:
        path, rings = asyncio.run(drive(stack.port, args))
    except BaseException:
        sys.stderr.write(stack.log_tail())
        raise
    finally:
        stack.stop()
    files = glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        print(f"no trace under {path}", file=sys.stderr)
        return 1
    kept = os.path.join(out_dir, "trace.xplane.pb")
    shutil.copy(max(files, key=os.path.getmtime), kept)
    shutil.rmtree(path, ignore_errors=True)
    with open(os.path.join(out_dir, "rings.json"), "w") as f:
        json.dump(rings, f)
    warnings.filterwarnings("ignore", category=DeprecationWarning)
    starts = annotation_starts(kept)
    ticks = {int(t["seq"]): float(t["tWall"]) for t in rings.get("ticks", [])}
    admissions = {
        int(a["seq"]): float(a["tWall"]) for a in rings.get("admissions", [])}
    line = {
        "rehearsal": args.cpu,
        "events": {name: len(seqs) for name, seqs in starts.items()},
        # annotation start minus the record's wall stamp, same seq
        "dispatch_minus_tick_t_wall": summary([
            (t - ticks[seq]) * 1000.0
            for seq, t in starts["ggrmcp.tick.dispatch"].items() if seq in ticks]),
        "admit_minus_admission_t_wall": summary([
            (t - admissions[seq]) * 1000.0
            for seq, t in starts["ggrmcp.admit"].items() if seq in admissions]),
        "trace": os.path.relpath(kept, ROOT),
    }
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
