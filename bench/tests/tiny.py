"""A checkout of the benchmark at toy sizes, for runs on the CPU.

``make_root(dst)`` copies ``BENCHMARK.json`` and ``bench/`` into
``dst``, links the program's ``src/``, and adds a tiny copy of each
configuration (24x32 sensors, a few of each tier, an 8-slot pool, a
narrow head) with one cell each, keeping the real configuration's
limits, its ``mesh_devices``, on as many emulated CPU devices, and its
``fleet_copies``.
Nothing of the original checkout is changed.
"""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CELLS = {"tiny_qvga": ("isc-qvga-tiered", "tiered_scenes"),
         "tiny_qvga_x4": ("isc-qvga-tiered-x4", "tiered_scenes")}


def make_root(dst: str) -> str:
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    bench = json.load(open(os.path.join(dst, "BENCHMARK.json")))
    for cell, (name, traffic) in CELLS.items():
        c = json.load(open(os.path.join(dst, "bench/configs", name + ".json")))
        c.update(name="tiny-" + name, h=24, w=32, n_slots=8, connect_wave=4)
        copies = c.get("fleet_copies", 1)
        for t in c["tiers"]:
            # 3 + 5 sensors as one copy, 1 + 1 per copy of several
            t["sensors"] = copies * max(
                1, (3 if t["tier"] == "gesture" else 5) // copies)
            for p in t["products"].values():
                if p["kind"] == "classify":
                    p["width"] = 8
        path = f"bench/configs/tiny-{name}.json"
        json.dump(c, open(os.path.join(dst, path), "w"))
        bench["configs"].append({"name": c["name"], "source": "toy size",
                                 "file": path, "reduced": [], "why": "tests"})
        bench["workloads"].append({"name": cell, "config": c["name"],
                                   "traffic": traffic,
                                   "chips": c.get("mesh_devices", 1),
                                   "why": "tests"})
    json.dump(bench, open(os.path.join(dst, "BENCHMARK.json"), "w"))
    return dst


DRIVE = """
import json, sys, time
T = time.perf_counter()
root, cell, trace, fault = sys.argv[1], sys.argv[2], int(sys.argv[3]), sys.argv[4]
sys.path[:0] = [root + "/bench", root + "/bench/traffic"]
import harness, faults
c = harness.load_cell(root, cell)
harness.import_program(root)
harness.configure_jax(root, c.config)
tap, patch = faults.FAULTS[fault]
with patch():
    res = harness.run_cell(root, cell, 2**31 + 11, 2.0, bool(trace), T,
                           backend="interpret", tap=tap, control=True)
print(json.dumps(res, default=float))
"""


def drive(root: str, cell: str, trace: bool = False, fault: str = "none"):
    """Run ``harness.run_cell`` of the checkout at ``root`` in a fresh
    process on as many CPU devices as the cell has chips (Pallas
    interpreter), skipping the look for a chip; returns its result."""
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cpu_devices = next(w["chips"] for w in bench["workloads"]
                       if w["name"] == cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cpu_devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={cpu_devices}").strip()
    out = subprocess.run([sys.executable, "-c", DRIVE, root, cell,
                          str(int(trace)), fault],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
