"""Sweep of open-loop request rates for a serving cell, to find the highest
rate the system sustains (the knee).  The cell's traffic file then fixes
its rate as a number; the benchmark's runs never search for one.

    python bench/knee.py --workload <serving cell> --seed 1 \
        [--fractions 0.5 0.7 0.8 0.9 1.0 1.1] [--seconds 8]

One process: the server is built once; a few full-batch calls give the
capacity C = max_batch / call seconds; then each fraction f of C is offered
for ``--seconds`` (the cell's arrival law) and the completed rate, p50,
p99 and requests per device call are printed, one JSON line per rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness
    from bench.traffic import serve

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.5, 0.7, 0.8, 0.9, 1.0, 1.1])
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = harness.benchmark()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    devices = harness.accelerator(int(cell["chips"]))
    harness.enable_compile_cache()
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds, trace=False,
                          t0=time.perf_counter(), devices=devices)
    server = serve.build_server(ctx)
    b = traffic["max_batch"]
    ids = np.arange(b) * 7919 % config["num_users"]
    server.recommend_many(ids)
    t = time.perf_counter()
    calls = 5
    for _ in range(calls):
        server.recommend_many(ids)
    call_s = (time.perf_counter() - t) / calls
    cap = b / call_s
    print(json.dumps({"call_ms": 1e3 * call_s, "capacity_per_s": cap}),
          flush=True)
    for f in args.fractions:
        c = dataclasses.replace(ctx, traffic=dict(traffic,
                                                  rate_per_s=f * cap))
        win = serve.measure(c, server, args.seconds, harness.Profiler(False))
        lat = (win.done - win.due) * 1e3
        ok = np.isfinite(lat)
        last = np.nanmax(win.done) if ok.any() else float("nan")
        print(json.dumps({
            "fraction": f, "offered_per_s": f * cap,
            "completed_per_s": float(ok.sum() / last),
            "answered": int(ok.sum()), "requests": int(len(lat)),
            "p50_ms": float(np.percentile(lat[ok], 50)),
            "p99_ms": float(np.percentile(lat[ok], 99)),
            "batch_fill": win.requests / max(win.device_calls, 1),
            "generator_late_p99_ms": float(np.percentile(win.late, 99) * 1e3),
        }), flush=True)
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
