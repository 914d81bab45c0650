#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload har_tree.search_p128_g500 --seed 7 \\
        --seconds 51 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; `workloads.RUNNERS` turns them into work. The run loads and
warms up (``setup_s``), does work until ``--seconds`` have passed and the
unit running then has finished, checks what the window produced against
the plain reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` with ``--trace 1``) and,
last, ``checks``: every number compared beside its limit. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from a profiler trace of the same window.

It runs on the TPU it is started on and nowhere else: without one, or with
fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import types

import spec
import trace_reduce
from compile_clock import CompileClock
from workloads import RUNNERS

EXIT_NO_CHIP = 3


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: error: {msg}", file=sys.stderr)
    raise SystemExit(code)


def chips(n: int):
    """The first ``n`` TPU devices, or exit without a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax found {devs[0].platform} devices; the benchmark "
             f"does not fall back to them", EXIT_NO_CHIP)
    if len(devs) < n:
        fail(f"the cell needs {n} chips, jax found {len(devs)}", EXIT_NO_CHIP)
    return devs[:n]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, so that only a cell's first
    run there compiles. Every program is cached, however small or quick to
    compile, and nothing is evicted: a size limit would make the cache track
    access times, and entries written without them then fail every write."""
    import jax

    for key, value in (
            ("jax_enable_compilation_cache", True),
            ("jax_compilation_cache_dir", str(spec.ROOT / ".jax_cache")),
            ("jax_persistent_cache_min_entry_size_bytes", -1),
            ("jax_persistent_cache_min_compile_time_secs", 0),
            ("jax_compilation_cache_max_size", -1)):
        jax.config.update(key, value)


def reduce_trace(log_dir: str, devices) -> types.SimpleNamespace:
    tr = trace_reduce.load(log_dir)
    lo, hi = tr.window("window")
    ids = [d.id for d in devices]
    missing = [i for i in ids if i not in tr.devices]
    if missing:
        raise RuntimeError(f"trace has no ops for devices {missing}")
    ops = {i: trace_reduce.clip(tr.devices[i], lo, hi) for i in ids}
    busy = [trace_reduce.busy_seconds(ops[i], lo, hi) for i in ids]
    all_ops = [o for i in ids for o in ops[i]]
    n = len(ids)
    gaps = {}
    for i in ids:
        for k, v in trace_reduce.idle_gaps(ops[i], tr.spans, lo, hi):
            gaps[k] = gaps.get(k, 0.0) + v / n
    return types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, ops=ops, window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / n,
        breakdown={
            "device_ops": [[k, v / n] for k, v in
                           trace_reduce.op_totals(all_ops)[:10]],
            "idle_gaps": [[k, v] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]})


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, devices,
             out_dir: str) -> dict:
    """Set up, measure and check one cell on ``devices``; the result line."""
    import jax

    clock = CompileClock(jax.monitoring)
    runner = RUNNERS[traffic["kind"]](config, traffic, seed, out_dir)
    runner.setup()

    log_dir = out_dir + "_trace"
    annotate = lambda name: jax.profiler.TraceAnnotation(  # noqa: E731
        trace_reduce.SPAN_PREFIX + name)
    if trace:
        # a traffic whose trace would take too long to write and read may
        # trace a shorter window: its per-layer metrics are ratios
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        shutil.rmtree(log_dir, ignore_errors=True)
        runner.instrument(annotate)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans come from annotations
        jax.profiler.start_trace(log_dir, profiler_options=options)
    before = clock.snapshot()
    setup_s = process_age_s()
    units = k = 0
    with annotate("window"):
        t0 = time.perf_counter()
        while True:
            with annotate("step"):
                units += runner.step(k)
            k += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    after = clock.snapshot()
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace written in {time.perf_counter() - t0:.3f} s",
              file=sys.stderr)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"window: {k} steps, {units} units in {window_s:.3f} s; "
          f"compiles in window: {after['compiles'] - before['compiles']} "
          f"(trace+lower {after['trace_s'] - before['trace_s']:.3f} s, "
          f"cache hits {after['cache_hits'] - before['cache_hits']}); "
          f"setup {setup_s:.3f} s", file=sys.stderr, flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {}
    if trace:
        t0 = time.perf_counter()
        red = reduce_trace(log_dir, devices)
        print(f"trace reduced in {time.perf_counter() - t0:.3f} s",
              file=sys.stderr)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown
        run = types.SimpleNamespace(
            reduced=red, counters=runner.counters(), config=config,
            traffic=traffic, peak=spec.peaks()[devices[0].device_kind],
            devices=[d.id for d in devices], window_s=window_s)
        metrics = {}
        for m in spec.metrics_for(bench, "per_layer", cell["name"]):
            v = spec.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        measured = {"setup_s": setup_s, runner.rate_metric: units / window_s}
        metrics = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics_for(bench, "end_to_end",
                                             cell["name"])}

    limits = spec.limits()
    t0 = time.perf_counter()
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in runner.check(runner.collect())}
    print(f"reference check: {time.perf_counter() - t0:.3f} s",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": units, "failed": 0, "metrics": metrics,
            "device": device, **result, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    if not (spec.ROOT / "src" / "repro").is_dir():
        fail(f"no program under {spec.ROOT / 'src'}; run from a checkout")
    sys.path.insert(0, str(spec.ROOT / "src"))
    use_compile_cache()
    devices = chips(cell["chips"])
    result = run_cell(bench, cell, spec.config(cell["config"]),
                      spec.traffic(cell["traffic"]), args.seed, args.seconds,
                      bool(args.trace), devices,
                      str(spec.ROOT / "runs" / "bench" / args.workload))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
