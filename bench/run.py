#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything the run needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the driver that mix names in
``bench/drivers/<driver>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

Set-up (imports, device init, inputs and weights drawn on the device from
the seed, compile or cache load, warm calls) runs first; then the window
dispatches the program's entry for ``--seconds`` (``--trace 0``: the
end-to-end metrics) or for the traffic's ``trace_seconds`` under the
profiler (``--trace 1``: the per-layer metrics, read from the trace by
stage and by part, and from the driver's counters, which one untimed call
reads after the window).  Afterwards the program's state is freed and the
reference checks what the timed entry produced.
The last line of standard output is the result object; the numbers
compared, each with its limit, close standard error and the result.

Refuses to run, and prints no result, without a TPU or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_IMPORT = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def process_age_s() -> float:
    """Seconds since this process started (from /proc), or since this
    module was imported where /proc is not there."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def load_json(relpath: str) -> dict:
    """A JSON file of the checkout, by its path from the root."""
    with open(ROOT / relpath) as fh:
        return json.load(fh)


def _load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, found by name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1]} {name!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The ``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    return _load("metrics", metric).read


@functools.lru_cache(maxsize=None)
def load_driver(driver: str):
    """The ``Driver`` class of ``bench/drivers/<driver>.py`` (loaded once:
    its jitted references are kept between cells of one process)."""
    return _load("drivers", driver).Driver


def cell_plan(name: str) -> dict:
    """The cell's entry, configuration and traffic, found by name."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(configs[cell["config"]]["file"])
    traffic = load_json(f"bench/traffic/{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])]
    return {"cell": cell, "config": conf, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def enable_cache():
    """JAX's persistent cache at a fixed path inside the checkout, every
    program of the cell written to it."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache reads an access-time file beside
    # every entry, and fails on entries that lack one
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Backend compiles and persistent-cache hits and misses, from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def window(cell, seconds: float, annotate: bool = False):
    """Dispatch calls until ``seconds`` have passed at the end of a
    completed call.  Each call is started before the previous one is waited
    for, so the device never waits on the host between calls.  Returns
    (completed calls, seconds from the first dispatch to the end of the
    last completed call); the call still in flight at the end is waited
    for and not counted."""
    import jax
    span = (jax.profiler.TraceAnnotation if annotate
            else lambda _: contextlib.nullcontext())
    calls = 0
    t0 = time.perf_counter()
    with span("bench/dispatch"):
        pending = cell.dispatch()
    while True:
        with span("bench/dispatch"):
            nxt = cell.dispatch()
        with span("bench/wait"):
            jax.block_until_ready(pending)
        calls += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
        pending = nxt
    jax.block_until_ready(nxt)
    cell.drop_last()
    return calls, elapsed


def per_layer(plan, cell, red, counted, calls, chips, kind) -> dict:
    """Each per-layer metric of the cell that its reader finds.  A reader's
    context holds the traced window's reduction ``red`` (``stage_s``,
    ``part_s``, ...), the counts from shapes, the driver's
    ``part_counts()``, and the driver's counters (``counted``, read after
    the window) under their own names."""
    from bench import flops
    peak = flops.peaks(kind)
    units = calls * cell.ops_per_call
    ctx = {"unit": cell.unit, "units": units, "chips": chips, "peak": peak,
           "window_s": red["window_s"], "busy_s": red["busy_s"],
           "stage_s": red["stage_s"], "part_s": red["part_s"],
           "collective_s": red["collective_s"],
           "flops_per_op": cell.flops_per_op(),
           "part_counts": cell.part_counts()}
    if cell.unit == "rounds":
        ctx["train_flops"], ctx["train_bytes"] = cell.train_counts()
    ctx.update(counted)
    out = {}
    for m in plan["per_layer"]:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def build(plan, seed: int, devices=None):
    """The cell's driver for ``seed`` on ``devices`` (default: the first
    ``chips`` devices JAX finds), not yet set up."""
    traffic = plan["traffic"]
    if devices is None:
        devices = devices_for(int(plan["cell"]["chips"]), require_tpu=False)
    return load_driver(traffic["driver"])(plan["config"], traffic, seed,
                                          devices)


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, mode: str = "program", plan=None):
    """One run; returns the result object (``checks`` last) and what set-up
    and the window compiled, with the readings no limit names (``read``).  ``mode`` other than "program" puts the
    control or a planted fault of the driver's ``modes()`` in the
    program's place: the run must then come out not correct.  The program
    runs with its matrix products at the configuration's
    ``matmul_precision``."""
    plan = plan or cell_plan(workload)
    enable_cache()
    import jax
    with jax.default_matmul_precision(plan["config"]["matmul_precision"]):
        return _run(plan, seed, seconds, trace, require_tpu, mode)


def _run(plan, seed, seconds, trace, require_tpu, mode):
    import jax
    from repro.core import engine  # noqa: F401  (the system under test)
    from bench import parts, tracing
    counter = CompileCounter()
    devs = devices_for(int(plan["cell"]["chips"]), require_tpu)
    cell = build(plan, seed, devs)
    cell.setup()
    setup_s = process_age_s()
    compiles_setup = counter.compiles
    red = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with jax.profiler.trace(tdir):
                calls, elapsed = window(cell, float(
                    plan["traffic"]["trace_seconds"]), annotate=True)
            compiles_window = counter.compiles - compiles_setup
            hlo = cell.hlo_text()
            events = tracing.load_events(tdir, tracing.hlo_scopes(hlo))
            red = tracing.reduce_events(events)
            red["part_s"] = parts.reduce_parts(
                events, parts.fallback_scopes(hlo))["part_s"]
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        calls, elapsed = window(cell, seconds)
        compiles_window = counter.compiles - compiles_setup
    attempted = calls * cell.ops_per_call
    failed = cell.failed()
    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    # untimed, after the window's compiles are counted and its peak read
    counted = cell.counters() if trace else {}
    cell.release()
    numbers = cell.check((mode,))[mode]
    limits = plan["traffic"]["limits"]
    from bench import compare
    correct, checks = compare.judge(numbers, limits)
    correct = correct and failed == 0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if trace:
        result["metrics"] = per_layer(plan, cell, red, counted, calls,
                                      len(devs), devs[0].device_kind)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["device"] = device
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        rate = attempted / elapsed
        metrics = {}
        for m in plan["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            else:
                metrics[m["name"]] = {"value": rate, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = checks
    compile_info = {"in_setup": compiles_setup, "in_window": compiles_window,
                    "cache_hits": counter.hits, "cache_misses": counter.misses,
                    "setup_s": setup_s, "window_s": elapsed, "calls": calls,
                    "read": {k: v for k, v in numbers.items()
                             if k not in limits}}
    return result, compile_info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    result, info = run(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    print(json.dumps({"run": info}), flush=True)
    for name, row in result["checks"].items():
        print(f"check {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
