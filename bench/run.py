"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The shared loop of every cell: find the cell in ``BENCHMARK.json`` and its
files by name (``workloads/<cell>.json``, ``configs/<config>.json``,
``traffic/<kind>.py``, ``metrics/<metric>.py``); check the device; set up
and warm up (``setup_s``, from process start); run the measured window,
under the profiler with ``--trace 1``; read the peak memory; free the
program's state; compare what the window produced with the plain reference;
print the compared numbers with their limits as the last lines of standard
error, and one JSON object as the last line of standard output.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoDevice(RuntimeError):
    """The machine lacks what the cell needs; no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict            # workloads/<cell>.json
    config: dict              # configs/<config>.json
    end_to_end: list          # BENCHMARK.json metric entries of this cell
    per_layer: list


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cell: Cell
    traffic: object           # the traffic kind's object (``.layer``)
    trace: object             # ``traces.Trace`` of the window, or None
    spans: object             # ``spans.Spans`` of the window
    peaks: dict               # ``peaks.json`` entry of this device kind


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"have {[w['name'] for w in spec['workloads']]}")
    entry = entries[0]
    workload = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    if workload["traffic"] != entry["traffic"]:
        raise SystemExit(f"bench: {name}: BENCHMARK.json says traffic "
                         f"{entry['traffic']!r}, its workload file "
                         f"{workload['traffic']!r}")
    config = json.loads(
        (BENCH / "configs" / f"{entry['config']}.json").read_text())
    return Cell(name, int(entry["chips"]), workload, config,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def load_reader(metric: str):
    """``metrics/<metric>.py``'s ``read(ctx)``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileCounter:
    """Backend compiles and persistent-cache reads while registered."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found platform {dev.platform!r} "
                       f"({dev.device_kind}, {len(devices)} device(s))")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s); JAX found "
                       f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _number(v):
    """JSON has no infinity: a non-finite reading prints as null."""
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cell: Cell | None = None,
             control: bool = False, log=print) -> dict:
    """One run of a cell; returns the result object. ``cell`` replaces the
    files' cell (the tests run small copies of it); ``control`` adds the
    reference one precision step lower, in the program's place, under
    ``control`` (the control script's use; the benchmark's runs never)."""
    import jax

    from bench import peaks as peaks_table

    cell = cell or load_cell(name)
    device = device_info(cell.chips, require_tpu)
    peak_table = (peaks_table.for_kind(device["kind"]) if require_tpu
                  else None)
    log(f"[info] cell {cell.name}: {device['kind']} x{device['count']}, "
        f"jax {jax.__version__}, seed {seed}, window {seconds} s, trace "
        f"{int(trace)}")

    counter = CompileCounter()
    try:
        return _run(cell, seed, seconds, trace, device, peak_table, counter,
                    control, log)
    finally:
        counter.close()


def _run(cell, seed, seconds, trace, device, peak_table, counter, control,
         log) -> dict:
    import jax

    from bench import traces
    from bench.check import judge
    from bench.deploy import Deployment
    from bench.spans import Spans

    spans = Spans(trace)
    kind = importlib.import_module(f"bench.traffic.{cell.workload['traffic']}")
    traffic = kind.Traffic(Deployment(cell.config, cell.chips),
                           cell.workload["params"], seed, spans)
    traffic.setup()
    c0, h0 = counter.compiles, counter.cache_hits
    setup_s = time.perf_counter() - PROCESS_START
    log(f"[info] set-up {setup_s:.6f} s: {c0} compiles, {h0} compile-cache "
        f"reads")

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(log_dir,
                                     profiler_options=traces.options())
        t0 = time.perf_counter()
        with spans.span("window"):
            traffic.window(seconds)
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        in_window = counter.compiles - c0
        log(f"[info] window {window_s:.6f} s: {in_window} compiles, "
            f"{counter.cache_hits - h0} compile-cache reads")
        peak = memory_peak(cell.chips)
        reduced = (traces.load(traces.find_xplane(log_dir), cell.chips)
                   if trace else None)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    metrics, breakdown = {}, None
    if trace:
        ctx = Context(cell, traffic, reduced, spans, peak_table)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        breakdown = {"device_ops": reduced.top_ops(),
                     "idle_gaps": reduced.idle_gaps()}
    else:
        values = traffic.end_to_end()
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = peak

    attempted, failed = traffic.attempted, traffic.failed
    traffic.release()
    gc.collect()
    readings = traffic.readings()
    ok, table = judge(readings, cell.workload["limits"])
    result = {"correct": bool(ok and failed == 0 and attempted > 0),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control"] = judge(traffic.control_readings(),
                                  cell.workload["limits"])[1]
    result["checks"] = {k: {"value": _number(v["value"]),
                            "limit": v["limit"]} for k, v in table.items()}
    return result


def prepare() -> str:
    """Make ``bench`` and the program importable and turn JAX's persistent
    compilation cache on in this checkout (``.jax_cache/``, never a
    directory shared with another checkout); returns its directory."""
    if sys.path and Path(sys.path[0]).resolve() == BENCH:
        del sys.path[0]      # the bench modules are imported as ``bench.*``
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from repro.launch import compile_cache
    import jax
    cache_dir = compile_cache.enable()
    # every program, however quick to compile, is read back by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cache_dir = prepare()
    except ImportError as e:
        print(f"bench: the program is not importable from {ROOT / 'src'} "
              f"({e})", file=sys.stderr)
        return 2
    print(f"[info] compile cache {cache_dir}", file=sys.stderr)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace),
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, row in result["checks"].items():
        print(f"[check] {name} {row['value']} limit {row['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
