#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = start the served stack as a child (the cell's backend file),
probe every admission shape the cell's traffic reaches, ramp, measure
`--seconds`, stop the child, check the outputs against the float32
reference in a second child, print one JSON line. This parent never
imports JAX: the chip belongs to one child at a time. Without a TPU the
run fails and prints no result. `--cpu-rehearsal` walks the same control
flow on the CPU for the tests; its line names the CPU and carries no
device metric.

Everything a cell is made of is data found by name: BENCHMARK.json,
`configs/<config>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.py`, `backends/<backend>.py`,
`checks/<check>.py`. See benchmark/README.md.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import copy  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HARNESS_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import client, plugins, schedule, stats, trace, xplane  # noqa: E402

MODEL_INFO = "ggrmcp_tpu_modelinfoservice_getmodelinfo"
STATS = "ggrmcp_tpu_modelinfoservice_getservingstats"
# Warm starts are about 90 s; the first run of a cell in a checkout
# compiles some thirty 7B programs.
READY_S, RAMP_S, CALL_S = 1000.0, 240.0, 600.0


def say(msg: str) -> None:
    print(msg, flush=True)


def numbers(d: dict) -> dict:
    """proto3 JSON: int64 arrives as a string; make scalars numbers."""
    out = {}
    for k, v in d.items():
        if isinstance(v, str):
            try:
                out[k] = float(v) if "." in v else int(v)
            except ValueError:
                out[k] = v
        else:
            out[k] = v
    return out


def deep_merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = deep_merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else copy.deepcopy(v)
    return out


def bucket(n: int, minimum: int = 32) -> int:
    """The power-of-two admission width a length of n falls into."""
    return max(minimum, 1 << (n - 1).bit_length())


def probe_lengths(sched: schedule.Schedule, page: int) -> list:
    """The longest fresh (not yet cached) token count of every
    power-of-two admission width the schedule reaches: a cold prompt's
    length, or, where a session's history is cached page by page, what
    follows the last whole page of the previous turn's prompt."""
    fresh = set()
    turns = sched.session_turns
    for start in range(0, len(sched.pairs), turns):
        cached = length = sched.shared_prefix_tokens
        for k, (p_len, o_len) in enumerate(schedule.session_block(sched, start)):
            length += p_len
            fresh.add(length - (cached // page) * page if (
                k or sched.shared_prefix_tokens) else length)
            cached = length
            length += o_len
    by_width: dict = {}
    for n in fresh:
        by_width[bucket(n)] = max(by_width.get(bucket(n), 0), n)
    return [by_width[w] for w in sorted(by_width)]


async def run_probes(load: client.Load, slots: int, page: int) -> dict:
    """Off the clock: send every admission shape once, so that nothing
    compiles inside the window. For each width, bursts of 1, 2, 3, 4 and
    `slots` simultaneous calls: the batcher admits a burst whole or as
    the first arrival alone and then the rest, and either way these
    sizes reach its row buckets 1, 2, 4 and the full pool (2 or 1+2
    rows, 4 or 1+3, 8 or 1+7). Also the twice-the-same check."""
    sched, seed, vocab = load.sched, load.seed, load.vocab
    load.phase = "probe"
    http = [client.Http(load.host, load.port, load.timeout_s)
            for _ in range(max(slots, 1))]
    n_calls = 0
    try:
        if sched.shared_prefix_tokens:
            # The shared prefix, cold: a chunked admission of its own.
            call = await load.one(
                http[0], 0, load.prefix + schedule.token_ids(
                    seed, vocab, 40, "probe", "prefix"), 2)
            if not call.ok:
                raise RuntimeError(f"probe failed: {call.error}")
            n_calls += 1
        for fresh in probe_lengths(sched, page):
            for burst in sorted({min(n, slots) for n in (1, 2, 3, 4, slots)}):
                calls = await asyncio.gather(*(
                    load.one(http[j], j, load.prefix + schedule.token_ids(
                        seed, vocab, fresh, "probe", fresh, burst, j), 2)
                    for j in range(burst)))
                bad = [c.error for c in calls if not c.ok]
                if bad:
                    raise RuntimeError(f"probe failed: {bad[0]}")
                n_calls += burst
        # The same short greedy request twice in a row, along one path
        # (shorter than a page, so the second finds no page to reuse).
        ids = schedule.token_ids(seed, vocab, min(12, page - 1), "probe", "twice")
        a = await load.one(http[0], 0, ids, 16)
        b = await load.one(http[0], 0, ids, 16)
        n_calls += 2
    finally:
        for h in http:
            h.close()
    return {"calls": n_calls, "twice_same": a.ok and b.ok and a.output == b.output,
            "twice_mismatches": sum(x != y for x, y in zip(a.output, b.output))
            + abs(len(a.output) - len(b.output))}


async def drive(args, cell, config, sched, stack) -> dict:
    serving = config["stack"]["serving"]
    slots = int(serving["batching"]["max_batch_size"])
    page = int(serving["batching"].get("paged_kv_page_size", 16))
    ctl = client.Http("127.0.0.1", stack.port, CALL_S)
    info = await ctl.tool(MODEL_INFO, {})
    device = {"platform": info.get("platform", ""),
              "kind": info.get("deviceKind", ""),
              "count": int(info.get("numDevices", 0))}
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if device["platform"] != want:
        raise RuntimeError(f"the stack runs on {device['platform']!r}, not {want!r}")
    if not args.cpu_rehearsal and device["count"] != int(cell["chips"]):
        raise RuntimeError(
            f"the stack holds {device['count']} device(s), the cell asks "
            f"for {cell['chips']}")
    vocab = int(config["vocab_size"])
    load = client.Load(sched, args.seed, vocab, "127.0.0.1", stack.port, CALL_S)

    t = time.monotonic()
    probes = await run_probes(load, slots, page)
    say(f"probes: {probes['calls']} calls in {time.monotonic() - t:.1f} s; the "
        f"same greedy request twice differs in {probes['twice_mismatches']} "
        f"ids (limit 0)")

    # Ramp: the clients start here and run past the end of the window.
    load.phase = "ramp"
    t = time.monotonic()
    if sched.loop == "closed":
        tasks = [asyncio.ensure_future(load.client_loop(i))
                 for i in range(sched.clients)]
    else:
        tasks = [asyncio.ensure_future(
            load.open_loop(RAMP_S + args.seconds + 5.0))]
    try:
        while not load.ramped():
            if time.monotonic() - t > RAMP_S:
                raise RuntimeError(f"the ramp did not finish in {RAMP_S:.0f} s")
            for task in tasks:
                if task.done():
                    task.result()  # a client died: say why
            await asyncio.sleep(0.02)
        say(f"ramp: {len([c for c in load.calls if c.phase == 'ramp'])} calls "
            f"in {time.monotonic() - t:.1f} s")

        # The window.
        stats0 = numbers(await ctl.tool(STATS, {}))
        load.phase = "run"
        t0, wall0 = time.monotonic(), time.time()
        setup_s = t0 - T_PROCESS_START
        t1 = t0 + args.seconds
        samples, mem_samples, captured = [], [], {}

        async def memory() -> dict:
            body = json.loads(await ctl2.request("GET", "/debug/memory?reconcile=0"))
            report = numbers(body["backends"][0]) if body.get("backends") else {}
            mem_samples.append(max(
                [int(x) for x in report.get("deviceBytesInUse", [])] or [0]))
            return report

        async def sampler() -> None:
            while time.monotonic() < t1 - 1.0:
                await asyncio.sleep(1.0)
                samples.append(numbers(await ctl2.tool(STATS, {})))
                await memory()

        async def capture() -> None:
            await asyncio.sleep(0.25 * args.seconds)
            ms = int(min(sched.trace_ms, 0.4 * args.seconds * 1000))
            http = client.Http("127.0.0.1", stack.port, CALL_S)
            try:
                body = json.loads(await http.request(
                    "POST", f"/debug/profile?duration_ms={ms}&label=bench"))
            finally:
                http.close()
            captured["path"] = body["backends"][0].get("outputPath", "")
            captured["ms"] = ms

        ctl2 = client.Http("127.0.0.1", stack.port, CALL_S)
        side = [asyncio.ensure_future(memory())]
        if args.trace:
            side += [asyncio.ensure_future(sampler()),
                     asyncio.ensure_future(capture())]
        await asyncio.sleep(max(0.0, t1 - time.monotonic()))
        stats1 = numbers(await ctl.tool(STATS, {}))
        t1 = time.monotonic()  # the window ends when its counters are read
        await asyncio.gather(*side)
        mem = await memory()
        ctl2.close()
    finally:
        load.stopping = True
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        ctl.close()
    wall1 = wall0 + (t1 - t0)
    late = [c for c in mem.get("compiles", []) if c.get("postWarmup")
            and wall0 <= float(c.get("tWall", 0)) <= wall1]
    return {
        "device": device, "load": load, "t0": t0, "t1": t1, "setup_s": setup_s,
        "stats0": stats0, "stats1": stats1, "samples": samples, "memory": mem,
        "memory_peak_bytes": max(mem_samples), "captured": captured,
        "probes": probes, "late_compiles": late,
    }


def read_trace(captured: dict, out_dir: str, keep: bool):
    path = captured.get("path")
    if not path:
        return None
    files = glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    newest = max(files, key=os.path.getmtime)
    t = time.monotonic()
    reduced = trace.reduce(xplane.load(newest))
    say(f"trace: {os.path.getsize(newest)} bytes reduced in "
        f"{time.monotonic() - t:.1f} s")
    if keep:
        shutil.copy(newest, os.path.join(out_dir, "trace.xplane.pb"))
    shutil.rmtree(path, ignore_errors=True)
    return reduced


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="walk the control flow on the CPU; NOT a chip result")
    ap.add_argument("--bench-root", default=ROOT,
                    help="directory that holds BENCHMARK.json and its paths")
    ap.add_argument("--control", default="",
                    help="switch on a lower-precision path the configuration "
                         "file lists under `controls`; `correct` must then "
                         "come out false")
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()
    # A terminated run still stops its children (the finally blocks).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    with open(os.path.join(args.bench_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    data_roots = [os.path.join(args.bench_root, p) for p in bench["paths"]]
    roots = data_roots + [HARNESS_DIR]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config_path = os.path.join(args.bench_root, config_entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    if args.control:
        config = deep_merge(config, config["controls"][args.control])
    serving = config["stack"]["serving"]
    slots = int(serving["batching"]["max_batch_size"])
    traffic_root = next(
        r for r in roots
        if os.path.exists(os.path.join(r, "traffic", cell["traffic"] + ".json")))
    sched = schedule.load(cell["traffic"], slots, traffic_root)
    need = sched.longest_prompt() + max(o for _, o in sched.pairs) + 24
    if need > int(serving["batching"]["kv_cache_max_seq"]):
        print(f"traffic {sched.name} needs {need} positions a slot, the "
              f"configuration has {serving['batching']['kv_cache_max_seq']}",
              file=sys.stderr)
        return 2

    out_dir = os.path.join(ROOT, "benchmark_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    if args.cpu_rehearsal:
        say("CPU REHEARSAL: control flow on the CPU. NOT a chip result.")
    backend = plugins.load("backends", sched.backend, roots)
    result = None
    try:
        stack = backend.launch(ROOT, out_dir, config["stack"], args.cpu_rehearsal,
                               READY_S)
    except RuntimeError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 1
    try:
        say(f"stack ready {time.monotonic() - T_PROCESS_START:.1f} s after start "
            f"(config and log under {out_dir})")
        result = asyncio.run(drive(args, cell, config, sched, stack))
    except BaseException:
        sys.stderr.write(stack.log_tail())
        raise
    finally:
        stack.stop()

    load, t0, t1 = result["load"], result["t0"], result["t1"]
    window = stats.window_metrics(load.calls, t0, t1)
    inside = stats.in_window(load.calls, t0, t1)
    stops = sum(c.stops for c in load.calls)
    say(f"window: {t1 - t0:.3f} s, {window['attempted']} calls completed, "
        f"{window['failed']} failed; {stops} early stop(s) at the "
        f"end-of-sequence id in the whole run, each continued; device "
        f"{result['device']}")
    for c in [c for c in inside if not c.ok][:5]:
        say(f"  failed call: {c.error}")
    for c in result["late_compiles"]:
        say(f"  compiled inside the window: {c.get('fnName')} "
            f"{float(c.get('durationMs', 0)):.0f} ms")
    if sched.loop == "open" and load.late_s:
        say(f"open loop: generator lateness p95 "
            f"{stats.percentile(load.late_s, 95) * 1000:.2f} ms")

    check = plugins.load("checks", config["check"]["name"], roots).run({
        "config": config, "config_path": config_path, "cpu": args.cpu_rehearsal,
        "all_calls": load.calls, "t0": t0, "t1": t1, "out_dir": out_dir,
        "root": ROOT, "harness_dir": HARNESS_DIR, "check_timeout_s": 900.0,
    })
    for line in check["lines"]:
        say(line)
    correct = bool(check["correct"]) and result["probes"]["twice_same"]

    device = dict(result["device"])
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    metrics: dict = {}
    line = {"correct": correct, "attempted": window["attempted"],
            "failed": window["failed"], "metrics": metrics, "device": device}
    if args.cpu_rehearsal:
        line["rehearsal"] = True
    if not args.trace:
        values = dict(window, setup_s=result["setup_s"])
        for m in bench["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        reduced = read_trace(result["captured"], out_dir, args.keep_trace)
        if reduced is None and not args.cpu_rehearsal:
            say("trace: no operation ran on a device in the capture")
        ctx = {
            "stats0": result["stats0"], "stats1": result["stats1"],
            "samples": result["samples"], "memory": result["memory"],
            "memory_peak_bytes": result["memory_peak_bytes"],
            "trace": None if args.cpu_rehearsal else reduced,
            "calls": inside, "window_s": t1 - t0, "config": config,
            "device": result["device"], "cell": cell, "sched": sched,
            "reader_roots": roots,
        }
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            if args.cpu_rehearsal and m["source"] == "device_trace":
                continue  # never a device metric from a CPU run
            reader = plugins.load("layer_metrics", m["name"], roots)
            if reader.UNIT != m["unit"]:
                raise RuntimeError(f"{m['name']}: the reader's unit "
                                   f"{reader.UNIT!r} is not {m['unit']!r}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None and not args.cpu_rehearsal:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
