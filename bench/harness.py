"""The data-driven part of the benchmark: find a cell's configuration, traffic
mix, driver and per-layer metric readers by the names in ``BENCHMARK.json``,
check the device, and assemble the result line.

Files, each found by name, none listed anywhere else:

* ``bench/configs/<config>.json``   a configuration (sizes as run);
* ``bench/workloads/<traffic>.json`` a traffic mix (data only); its ``kind``
  names the driver ``bench/traffic/<kind>.py``, whose ``run(ctx)`` builds
  the cell, measures the window and checks the answers;
* ``bench/metrics/<metric>.py``     a per-layer metric: ``read(ctx)``
  returns a number, or ``None`` where the run has nothing to read.

No JAX import happens at module import time.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_BYTES = 4 << 30


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, missing file, unknown peak)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"{path} not found")
    return load_json(path)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload named {name!r} in BENCHMARK.json; known: "
                     f"{[c['name'] for c in spec['workloads']]}")


def load_config(name: str, bench: Path = BENCH) -> dict:
    path = bench / "configs" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"configuration file {path} not found")
    return load_json(path)


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    path = bench / "workloads" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"traffic mix file {path} not found")
    return load_json(path)


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise BenchError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str, bench: Path = BENCH):
    return _load_module(bench / "traffic" / f"{kind}.py",
                        f"bench_traffic_{kind.replace('-', '_')}")


def metric_module(name: str, bench: Path = BENCH):
    return _load_module(bench / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_")
                        .replace("-", "_"))


def end_to_end_for(spec: dict, cell: str) -> list:
    """The end-to-end metric entries that ``cell`` reports."""
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(spec: dict, cell: str) -> list:
    """The per-layer metric entries read in ``cell``'s traced run: those that
    list it, and those without a list whose end-to-end metric it reports."""
    e2e = {m["name"] for m in end_to_end_for(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices; a ``BenchError`` on any other backend
    or on fewer chips.  Never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found platform {devices[0].platform!r}, not "
                         "a TPU: this benchmark measures the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def device_record(devices: list) -> dict:
    """platform, kind, count and the peak memory of the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def enable_compile_cache() -> None:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache`` (or
    ``$JAX_COMPILATION_CACHE_DIR``), for every program however short its
    compile, so that only a checkout's first run compiles.  A size limit
    below :data:`CACHE_BYTES` is raised to it: a training window embeds its
    dataset (0.5 GB at Google Local scale), and a cache that refused it
    would compile the window again in every run."""
    import jax
    from repro.launch import enable_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    limit = jax.config.jax_compilation_cache_max_size
    if 0 < limit < CACHE_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)


class Profiler:
    """Profiler trace of the measured window, written under ``$TMPDIR`` and
    reduced (``bench.trace``) when it stops; inactive when ``on`` is false."""

    def __init__(self, on: bool):
        self.on = on
        self.summary = None
        self._dir: Optional[tempfile.TemporaryDirectory] = None
        self._span = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        self._dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        jax.profiler.start_trace(self._dir.name)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        if not self.on or self._dir is None:
            return
        import jax
        from bench import trace
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            self.summary = trace.reduce_dir(self._dir.name)
        finally:
            self._dir.cleanup()
            self._dir = None


def settle_host() -> None:
    """End of set-up: collect garbage once and freeze what survives, so
    that no full collection over the set-up's objects (JAX keeps millions)
    stalls the host inside the measured window."""
    gc.collect()
    gc.freeze()


def span(name: str):
    """A host span in the profiler's trace (no cost while not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


@dataclasses.dataclass
class Context:
    """Everything a traffic driver gets: the cell, its files and the run's
    arguments.  ``t0`` is the process start on ``time.perf_counter``."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t0: float
    devices: list = dataclasses.field(default_factory=list)
    peaks: dict = dataclasses.field(default_factory=dict)
    log: Callable[[str], None] = log

    @property
    def window_seconds(self) -> float:
        """Length of the measured window: ``--seconds``, or in a traced run
        the traffic mix's ``trace_seconds`` where that is shorter."""
        if self.trace:
            return min(self.seconds, self.traffic["trace_seconds"])
        return self.seconds


@dataclasses.dataclass
class Outcome:
    """What a traffic driver returns.  ``end_to_end`` holds every
    end-to-end metric the driver measured (the harness keeps those the cell
    declares); ``counters`` feeds the per-layer readers; ``checks`` maps each
    compared number to ``(value, limit)`` and ``correct`` is whether every
    one is within its limit."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    checks: dict
    device: dict
    counters: dict = dataclasses.field(default_factory=dict)
    summary: Any = None            # bench.trace.TraceSummary when traced


def within(checks: dict) -> bool:
    return all(math.isfinite(v) and v <= lim for v, lim in checks.values())


def result_line(spec: dict, ctx: Context, out: Outcome,
                bench: Path = BENCH) -> dict:
    """The last line of standard output, per the benchmark's contract."""
    cell = ctx.cell["name"]
    metrics = {}
    if not ctx.trace:
        for m in end_to_end_for(spec, cell):
            if m["name"] in out.end_to_end:
                metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    else:
        mctx = {"cell": ctx.cell, "config": ctx.config,
                "traffic": ctx.traffic, "peaks": ctx.peaks,
                "counters": out.counters, "trace": out.summary,
                "device": out.device}
        for m in per_layer_for(spec, cell):
            value = metric_module(m["name"], bench).read(mctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(out.device)
    line = {"correct": bool(out.correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if ctx.trace and out.summary is not None:
        device["busy_s"] = out.summary.busy_s
        device["window_s"] = out.summary.window_s
        line["breakdown"] = {"device_ops": out.summary.device_ops,
                             "idle_gaps": out.summary.idle_gaps}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def print_checks(checks: dict) -> None:
    for k, (v, lim) in checks.items():
        ok = "ok" if (math.isfinite(v) and v <= lim) else "FAIL"
        print(f"[check] {k} = {v!r} limit {lim!r} {ok}", file=sys.stderr,
              flush=True)


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = benchmark()
        cell = find_cell(spec, args.workload)
        config = load_config(cell["config"])
        traffic = load_traffic(cell["traffic"])
        driver = kind_module(traffic["kind"])
        devices = accelerator(int(cell["chips"]))
        enable_compile_cache()
        ctx = Context(cell=cell, config=config, traffic=traffic,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t0=t0, devices=devices,
                      peaks=peaks(devices[0].device_kind))
        out = driver.run(ctx)
        line = result_line(spec, ctx, out)
    except BenchError as e:
        log(f"no result: {e}")
        return 2
    print_checks(out.checks)
    print(json.dumps(line), flush=True)
    return 0
