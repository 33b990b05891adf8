"""The harness finds a cell's files by name, and the real command refuses
anything but a TPU."""
import json
import os
import subprocess
import sys

import harness
import tiny
from conftest import ROOT

METRIC = '''"""A throwaway per-layer metric: deadlines in the traced window."""


def read(ctx):
    return float(len(ctx.steps))
'''


def snapshot(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            path = os.path.join(base, f)
            out[os.path.relpath(path, root)] = open(path, "rb").read()
    return out


def test_new_config_traffic_and_metric_by_name(tmp_path):
    root = tiny.make_root(str(tmp_path))
    before = snapshot(root)
    bench_before = json.load(open(os.path.join(root, "BENCHMARK.json")))
    # a later change adds files and entries, and edits nothing
    c = json.load(open(os.path.join(root, "bench/configs/tiny-isc-qvga-tiered.json")))
    c.update(name="added-config", h=16, w=24)
    json.dump(c, open(os.path.join(root, "bench/configs/added-config.json"), "w"))
    m = json.load(open(os.path.join(root, "bench/traffic/tiered_scenes.json")))
    m["scenes"]["telemetry"] = ["hotel_bar"]
    json.dump(m, open(os.path.join(root, "bench/traffic/added_mix.json"), "w"))
    open(os.path.join(root, "bench/metrics/added_deadlines.py"), "w").write(METRIC)
    bench = json.loads(json.dumps(bench_before))
    bench["configs"].append({"name": "added-config", "source": "toy",
                             "file": "bench/configs/added-config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added_cell", "config": "added-config",
                               "traffic": "added_mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "added_deadlines", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "runtime", "moves": "events_per_s"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    res = tiny.drive(root, "added_cell", trace=True)
    assert res["correct"], res["checks"]
    got = res["per_layer"]["added_deadlines"]
    assert got["value"] >= 1 and got["unit"] == "count"
    assert "host_offer_ms" in res["per_layer"]
    assert set(res["e2e"]) == {"events_per_s", "deadline_p95_ms",
                               "peak_hbm_gib", "setup_s"}
    after = snapshot(root)
    assert all(after[k] == v for k, v in before.items())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][:len(bench_before[key])] == bench_before[key]


def test_sharded_config_by_name(tmp_path):
    """A configuration with ``mesh_devices`` shards the slot pool over
    that many devices, and needs no edit of the harness."""
    root = tiny.make_root(str(tmp_path))
    c = json.load(open(os.path.join(root, "bench/configs/tiny-isc-qvga-tiered.json")))
    c.update(name="added-sharded", mesh_devices=4)
    json.dump(c, open(os.path.join(root, "bench/configs/added-sharded.json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "added-sharded", "source": "toy",
                             "file": "bench/configs/added-sharded.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added_x4", "config": "added-sharded",
                               "traffic": "tiered_scenes", "chips": 4,
                               "why": "test"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    res = tiny.drive(root, "added_x4")
    assert res["correct"], res["checks"]
    assert res["sharded_over"] == 4
    # the four-chip cell of the benchmark, found by its name
    cell = harness.load_cell(ROOT, "qvga_tiered_x4")
    assert cell.config["name"] == "isc-qvga-tiered-x4"
    assert cell.config["mesh_devices"] == cell.workload["chips"] == 4
    assert cell.config["n_slots"] == 4 * 64


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qvga_tiered_steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_command_refuses_the_cpu():
    out = _run_command(ROOT)
    assert out.returncode != 0 and not _has_result(out.stdout)
    assert "no TPU" in out.stderr


def test_command_needs_the_program(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run_command(str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0 and not _has_result(out.stdout)


def test_samples_cover_every_tier_and_chip():
    """The compared products are spread over the tiers and, within each,
    over the chips: a fault on one chip cannot miss the sample."""
    samples = [{"k": k, "tier": tier, "chip": (k * 7 + j) % 4, "sensor": j}
               for k in range(1, 31) for j, tier in enumerate(["g", "t"])]
    for seed in range(20):
        got = harness.pick_samples(samples, seed)
        assert len(got) == harness.SAMPLES
        assert {(s["tier"], s["chip"]) for s in got} == {
            (t, c) for t in "gt" for c in range(4)}
        assert {s["k"] for s in got} >= {30}


def test_ingest_batches_warmed_per_chip():
    """The warmed ingest batches are rows a chip: on one chip as the
    whole fleet's; spread over four chips, fewer and smaller."""
    import generator

    config = json.load(open(os.path.join(
        ROOT, "bench/configs/isc-qvga-tiered.json")))
    config.update(h=24, w=32, chunk_capacity=16)
    mix = json.load(open(os.path.join(ROOT, "bench/traffic/tiered_scenes.json")))
    traffic = generator.generate(mix, config, 5)
    one = harness.batch_sizes(config, traffic, 24)
    assert one == harness.batch_sizes(config, traffic, 24, [0] * len(traffic))
    spread = harness.batch_sizes(config, traffic, 24,
                                 [i % 4 for i in range(len(traffic))])
    assert len(one) > 2 and spread == one[:len(spread)]
    assert len(spread) < len(one)
