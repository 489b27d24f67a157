"""Readings behind the limits of ``correct``: the program's numbers over many
seeds, the control's (the reference in the program's place, one precision
lower) and those of planted faults.  The benchmark's own runs do not run
this; its readings are what ``PERF.md`` sets each limit from.

    python bench/control.py --workload <cell> --seeds 1 2 3 \
        [--variants program control half stale altered] [--seconds 3] \
        [--vary-stream] [--out readings.json]

Training cells (one process, no measured window needed):

* ``program``  the first window of the program, against the reference;
* ``control``  the reference one precision lower, in the program's place
               (fp32 tables: the step in bfloat16; int8: rows read as int4);
* ``half``     the program with half of each batch left out, the mean taken
               over the rest;
* ``stale``    the program with a step that returns its state unchanged.

A training cell's traffic mix fixes the data and the program's batch
stream (its window program embeds the data), so the benchmark's seeds vary
the tables and the tile only.  ``--vary-stream`` draws the data and the
batch stream from each seed as well, at the cost of a window compile per
seed.

Serving cells (a short window at the cell's own rate per seed):

* ``program``  the served answers of a sample, against the exact top-k;
* ``control``  the same requests answered from rows one precision lower
               (int8 tables: int4; fp32 tables: bfloat16);
* ``altered``  the program with one id of every answer altered where the
               top-k is produced.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def patched(module, name: str, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def half_batch_step(step):
    """A training step that leaves out the second half of its batch."""
    from repro.core import mf

    def fault(state, batch, rng, cfg, **kw):
        h = batch.user_ids.shape[0] // 2
        return step(state, mf.Batch(batch.user_ids[:h], batch.pos_ids[:h]),
                    rng, cfg, **kw)
    return fault


def stale_step(step):
    """A training step that returns its state unchanged."""
    def fault(state, batch, rng, cfg, **kw):
        _, loss = step(state, batch, rng, cfg, **kw)
        return state, loss
    return fault


def altered_topk(topk):
    """Top-k whose first id in every answer is moved to the next item."""
    import jax.numpy as jnp

    def fault(params, user_ids, k, **kw):
        ids = topk(params, user_ids, k, **kw)
        n = params.item_table.shape[0]
        return ids.at[:, 0].set((ids[:, 0] + 1) % n).astype(jnp.int32)
    return fault


def seed_stream(traffic: dict, seed: int) -> dict:
    """A training mix whose data and batch stream are drawn from ``seed``."""
    return dict(traffic, program_seed=seed,
                data=dict(traffic["data"], seed=seed))


def train_readings(ctx, variants) -> dict:
    import jax
    from repro.core import mf

    from bench import reference
    from bench.traffic import train

    train_pos = jax.block_until_ready(train.dataset(ctx.config, ctx.traffic))
    ref = train.reference_observation(ctx, train_pos)
    faults = {"half": half_batch_step, "stale": stale_step}
    out = {}
    for v in variants:
        if v == "control":
            obs = train.reference_observation(
                ctx, train_pos, reference.LOWER[ctx.config["table_format"]])
        elif v == "program" or v in faults:
            wrap = faults.get(v)
            with (patched(mf, "heat_train_step", wrap(mf.heat_train_step))
                  if wrap else contextlib.nullcontext()):
                tr = train.setup(ctx, train_pos)
            obs = tr.observation
            del tr
        else:
            raise ValueError(f"unknown training variant {v!r}")
        out[v] = reference.compare_mf(obs, ref)
    return out


def serve_readings(ctx, variants, seconds: float) -> dict:
    import numpy as np
    from repro.core import mf

    from bench import harness, reference
    from bench.traffic import serve

    out, k = {}, ctx.traffic["k"]
    for v in variants:
        if v == "control":
            continue
        with (patched(mf, "topk_all_items", altered_topk(mf.topk_all_items))
              if v == "altered" else contextlib.nullcontext()):
            server = serve.build_server(ctx)
            win = serve.measure(ctx, server, seconds,
                                harness.Profiler(False))
            server.stop()
            del server
        pick, served = serve.sample(ctx, win)
        rows, iq = serve.reference_rows(ctx.config, ctx.seed,
                                        win.users[pick])
        out[v] = {"topk_gap": reference.topk_gap(rows, iq, served, k),
                  "answered": int(np.isfinite(win.done).sum()),
                  "requests": int(len(win.due))}
        if "control" in variants and v == "program":
            lower = reference.LOWER[ctx.config["table_format"]]
            lowp = np.asarray(reference.topk_lowp(rows, iq, k, lower))
            out["control"] = {"topk_gap": reference.topk_gap(rows, iq, lowp,
                                                             k)}
        del rows, iq
    return out


def main(argv=None) -> int:
    t0 = time.perf_counter()
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["program", "control"])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--vary-stream", action="store_true",
                    help="training: draw the data and the batch stream "
                         "from each seed too")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = harness.benchmark()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    devices = harness.accelerator(int(cell["chips"]))
    harness.enable_compile_cache()
    results = {}
    for seed in args.seeds:
        mix = seed_stream(traffic, seed) if args.vary_stream else traffic
        ctx = harness.Context(cell=cell, config=config, traffic=mix,
                              seed=seed, seconds=args.seconds, trace=False,
                              t0=time.perf_counter(), devices=devices,
                              peaks=harness.peaks(devices[0].device_kind))
        if mix["kind"] == "train":
            r = train_readings(ctx, args.variants)
        else:
            r = serve_readings(ctx, args.variants, args.seconds)
        results[str(seed)] = r
        print(json.dumps({"seed": seed, **r}), flush=True)
    summary = {}
    for v in args.variants:
        for key in next(iter(results.values())).get(v, {}):
            vals = [r[v][key] for r in results.values()]
            summary[f"{v}.{key}"] = {"min": min(vals), "max": max(vals)}
    print(json.dumps({"summary": summary,
                      "wall_s": time.perf_counter() - t0}), flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "results": results,
             "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
