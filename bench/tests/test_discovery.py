"""A new configuration, traffic mix or per-layer metric is a new file: the
harness finds each by its name in ``BENCHMARK.json``, and no file that is
already there changes."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench import harness

BENCH = Path(harness.__file__).resolve().parent


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


@pytest.fixture
def bench_copy(tmp_path):
    dst = tmp_path / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_new_metric_and_workload_files_are_found(bench_copy):
    bench = bench_copy / "bench"
    before = _digests(bench)
    (bench / "metrics" / "queue_wait_ms.serve.py").write_text(
        "def read(ctx):\n"
        "    c = ctx['counters']\n"
        "    return 2.0 * c['requests'] if c.get('requests') else None\n")
    mix = json.loads((bench / "workloads" / "serve-top10-poisson.json")
                     .read_text())
    mix["rate_per_s"] = 5.0
    (bench / "workloads" / "serve-low.json").write_text(json.dumps(mix))
    cfg = json.loads((bench / "configs" / "google-local.json").read_text())
    (bench / "configs" / "google-small.json").write_text(json.dumps(cfg))
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "metrics/queue_wait_ms.serve.py", "workloads/serve-low.json",
        "configs/google-small.json"}

    spec = harness.benchmark(bench_copy)
    spec["workloads"].append({"name": "google-serve-low",
                              "config": "google-small",
                              "traffic": "serve-low", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][2]["workloads"].append("google-serve-low")
    spec["per_layer"].append({"name": "queue_wait_ms.serve", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "serving queue and batching",
                              "moves": "serve_p99_ms",
                              "workloads": ["google-serve-low"]})
    cell = harness.find_cell(spec, "google-serve-low")
    assert harness.load_traffic(cell["traffic"], bench)["rate_per_s"] == 5.0
    assert harness.load_config(cell["config"], bench)["num_users"] == 4570000
    assert harness.kind_module("serve", bench).run is not None
    names = [m["name"] for m in harness.per_layer_for(spec, cell["name"])]
    assert names == ["queue_wait_ms.serve"]
    assert [m["name"] for m in harness.end_to_end_for(spec, cell["name"])] \
        == ["setup_s", "serve_p99_ms"]

    ctx = harness.Context(cell=cell, config={}, traffic={}, seed=0,
                          seconds=1.0, trace=True, t0=0.0)
    out = harness.Outcome(correct=True, attempted=3, failed=0,
                          end_to_end={}, checks={"gap": (0.0, 1.0)},
                          device={"platform": "tpu", "count": 1},
                          counters={"requests": 3})
    line = harness.result_line(spec, ctx, out, bench)
    assert line["metrics"] == {"queue_wait_ms.serve": {"value": 6.0,
                                                       "unit": "ms"}}
    assert list(line)[-1] == "checks"


def test_metric_without_a_workloads_list_is_read_where_its_metric_is():
    spec = harness.benchmark()
    spec["per_layer"].append({"name": "queue_wait_ms.serve", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "serving queue and batching",
                              "moves": "serve_p99_ms"})
    serving = [c["name"] for c in spec["workloads"]
               if c["name"] in spec["end_to_end"][2]["workloads"]]
    assert serving
    for cell in spec["workloads"]:
        names = [m["name"] for m in harness.per_layer_for(spec, cell["name"])]
        assert ("queue_wait_ms.serve" in names) == (cell["name"] in serving)


def test_metric_that_finds_nothing_is_left_out():
    spec = harness.benchmark()
    cell = harness.find_cell(spec, "amazon-serve")
    ctx = harness.Context(cell=cell, config={}, traffic={}, seed=0,
                          seconds=1.0, trace=True, t0=0.0)
    out = harness.Outcome(correct=True, attempted=0, failed=0, end_to_end={},
                          checks={}, device={"count": 1}, counters={})
    assert harness.result_line(spec, ctx, out)["metrics"] == {}


def test_every_declared_file_exists():
    spec = harness.benchmark()
    for c in spec["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert harness.load_config(c["name"])["num_users"] > 0
    for w in spec["workloads"]:
        traffic = harness.load_traffic(w["traffic"])
        assert (BENCH / "traffic" / f"{traffic['kind']}.py").is_file()
    for m in spec["per_layer"]:
        assert callable(harness.metric_module(m["name"]).read)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")


def test_no_tpu_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.accelerator(1)
