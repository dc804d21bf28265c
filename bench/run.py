#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a deployment (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); the mix names the driver
(``bench/drivers/<driver>.py``) that runs it. The run sets up (bulk-loads
the window from the seed, warms up every program the cell uses), measures
for ``--seconds``, reads the peak device memory, frees the program's state,
compares what the timed path produced with the plain reference
(``bench/reference.py``) and prints one JSON line last on standard output.

With ``--trace 0`` the line holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the same
timed path by the readers in ``bench/metrics/<metric>.py``.

``--control 1`` runs the cell's control instead: the program walks with
another bias than the deployment states, which the reference must find.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 before doing any work. Compiled programs are kept in
``$JAX_COMPILATION_CACHE_DIR`` when it is set, and otherwise in
``.bench_cache/jax`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE = CHECKOUT / ".bench_cache" / "jax"

sys.path.insert(0, str(BENCH))


class NoChip(RuntimeError):
    pass


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: Path = CHECKOUT):
    """(cell, deployment, traffic, end-to-end metrics, per-layer metrics)
    of a cell of ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported
                              else [])]
    return cell, config, traffic, e2e, layer


class Context:
    """What a driver gets: the deployment, the traffic, the seed, whether
    this is the control, and host spans named into the profiler trace."""

    def __init__(self, name, config, traffic, seed: int, control: bool):
        self.name = name
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.control = control

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            yield


class Reading:
    """What a per-layer reader gets: the trace's reduction, the run's
    counts, the chip's peaks, the deployment and the traffic."""

    def __init__(self, trace, counts, peaks, config, traffic):
        self.trace = trace
        self.counts = counts
        self.peaks = peaks
        self.config = config
        self.traffic = traffic


class GcClock:
    """Seconds the host spends in Python's garbage collector while on."""

    def __init__(self):
        self.seconds = 0.0
        self._t = 0.0

    def _tick(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t

    def __enter__(self):
        gc.callbacks.append(self._tick)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._tick)


def check_chips(cell: dict):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             control: bool = False, *, root: Path = CHECKOUT,
             require_chip: bool = True, overrides=None) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``overrides`` (tests only) replaces parts of the deployment and the
    traffic, as {"config": {...}, "traffic": {...}}, to run at a size a
    test can hold."""
    t_start = time.perf_counter()
    cell, config, traffic, e2e, layer = load_cell(name, root)
    for part, value in (overrides or {}).items():
        {"config": config, "traffic": traffic}[part].update(value)
    if not (CHECKOUT / "src" / "repro").is_dir():
        raise NoChip("the program (src/repro) is not in this checkout")
    import jax
    if require_chip:
        devices = check_chips(cell)
    else:
        devices = jax.devices()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path.insert(0, str(CHECKOUT / "src"))

    import hbm
    import trace_reduce

    ctx = Context(name, config, traffic, seed, control)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    run = driver.Run(ctx)
    run.setup()
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    try:
        with ctx.span("window"), GcClock() as gc_clock:
            run.measure(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    used = devices[:cell["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    attempted, failed = run.attempted_failed()

    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {}
    if trace:
        found = list(Path(tdir).rglob("*.trace.json.gz"))
        reduction = trace_reduce.reduce(*trace_reduce.load(found[0])) \
            if found else None
        shutil.rmtree(tdir, ignore_errors=True)
        if reduction is not None:
            device.update(busy_s=reduction.busy_s,
                          window_s=reduction.window_s)
            result["breakdown"] = reduction.breakdown
            reading = Reading(reduction, run.counts(),
                              hbm.peaks(device["kind"]), config, traffic)
            for m in layer:
                reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
                value = reader.read(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = run.end_to_end()
        values["setup_s"] = setup_s
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    run.release()
    t_ref = time.perf_counter()
    numbers, seen = run.check()
    limits = json.loads((BENCH / "limits.json").read_text())
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    info = dict(seen, reference_s=time.perf_counter() - t_ref,
                gc_s=gc_clock.seconds)
    return dict(correct=correct, attempted=attempted, failed=failed,
                metrics=metrics, device=device, **result, run=info,
                checks=checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), bool(args.control))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
