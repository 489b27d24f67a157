"""Paper Fig. 12: scalability with worker count.

The paper measures thread scaling on a 64-core CPU.  This container has ONE
core, so parallel wall-clock speedup is not measurable; what *is* measurable
and faithful to the claim ("no communication or synchronization across
threads -> near-linear scaling") is:

  (a) work-per-shard independence: per-iteration time grows linearly in the
      batch it processes (slope ~1 on log-log), i.e. shards add no
      super-linear cost, and
  (b) the sharded-tile structure: S independent tiles (paper: per-thread
      tiles) cost S-proportional memory and one fused refresh gather.

Reported as iteration time vs simulated shard count, with the linear-scaling
efficiency derived from (a).  Real-mesh scaling is exercised by the dry-run
(collective terms in EXPERIMENTS.md §Roofline).
"""
import functools
import json
import os
import subprocess
import sys

import jax

from benchmarks.common import bench_cfg, emit, rand_batch, time_fn
from repro.core import mf


def run_sharded():
    """shard/ suite: real multi-device steps/sec at 1, 2, 4, 8 *forced host*
    devices (one subprocess per count — the device split must precede the
    first jax import, which this process already did).  The children always
    run on the CPU (``JAX_PLATFORMS=cpu``, whatever the parent's platform),
    so on a TPU host they never compete with the parent for the chip; every
    row says ``platform=cpu``.

    ``shard_efficiency`` = steps/sec at S devices / steps/sec at 1.  The S
    forced devices share one CPU's silicon, so 1.0 means sharding (collective
    + partitioned-dispatch overhead) is free at this scale; on a real
    multi-chip mesh the same row reads as weak-scaling efficiency.
    """
    sps = {}
    for devices in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in ("src", env.get("PYTHONPATH", "")) if p)
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.shard_probe",
             "--devices", str(devices)],
            capture_output=True, text=True, env=env, timeout=600)
        if out.returncode != 0:
            raise RuntimeError(
                f"shard_probe failed at {devices} devices: "
                f"{out.stderr[-2000:]}")
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        sps[devices] = rec["steps_per_sec"]
        emit(f"shard/devices={devices}", rec["us_per_step"],
             f"platform=cpu steps_per_sec={rec['steps_per_sec']:.1f}")
    emit("shard/shard_efficiency", 0.0,
         f"platform=cpu shard_efficiency={sps[8] / sps[1]:.2f} "
         "(8-dev vs 1-dev steps/sec on forced host CPU devices; "
         "1.0 = sharding overhead-free, shared silicon)")


def run():
    times = {}
    for shards in (1, 2, 4, 8):
        # one "shard" processes batch 256; S shards process 256*S total work
        cfg = bench_cfg()
        state = mf.init_mf(jax.random.PRNGKey(0), cfg)
        step = jax.jit(functools.partial(mf.heat_train_step, cfg=cfg))
        batch = rand_batch(cfg, 256 * shards)
        t = time_fn(lambda: step(state, batch, jax.random.PRNGKey(1)), iters=10)
        times[shards] = t
        emit(f"fig12/shards={shards}", t, f"work={256 * shards}")
    # parallel efficiency if the S shards ran concurrently: T(1)/ (T(S)/S)
    eff = times[1] / (times[8] / 8)
    emit("fig12/weak_scaling_efficiency", 0.0,
         f"{100 * eff:.1f}% (paper: 83.7% on 64 threads)")
    run_sharded()


if __name__ == "__main__":
    run()
