"""The `gang-racks` fixture: a deployment with racks stated by rule and
gangs among its job shapes, added to a copy of the benchmark as files
and entries only. It is no cell and no real deployment (`"source": "test
fixture"`): it shows that the harness can state one. Its files are in
`data/gang-racks/`; its configuration states a real size (552 servers in
24 racks) and a rehearsal scale.

    python benchmark/tests/gang_racks_fixture.py <directory>

makes `<directory>` a checkout of its own (the benchmark, the program,
`BENCHMARK.json` with the fixture's entries) in which
`benchmark/run.py --workload gang-racks.gangs` runs, on the chip too.
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "tests", "data", "gang-racks")
CELL = "gang-racks.gangs"
METRICS = ("gang_select_p50_ms", "gang_select_p95_ms")
ADDED = {"configs/gang-racks.json", "traffic/gangs.json",
         "checks/gang_slices.py",
         *(f"metrics/{name}.json" for name in METRICS)}


def copy_benchmark(top, link_program=True) -> str:
    """`top`/benchmark as a copy of the benchmark, the program beside
    it; returns the copy's path."""
    bench_dir = os.path.join(top, "benchmark")
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench_dir, ignore=ignore)
    if link_program:
        os.symlink(os.path.join(ROOT, "nomad_tpu"),
                   os.path.join(top, "nomad_tpu"))
    else:
        shutil.copytree(os.path.join(ROOT, "nomad_tpu"),
                        os.path.join(top, "nomad_tpu"), ignore=ignore)
    return bench_dir


def install(top) -> None:
    """The fixture's files into `top`/benchmark, its entries into
    `top`/BENCHMARK.json: nothing that was there is edited."""
    bench_dir = os.path.join(top, "benchmark")
    os.makedirs(os.path.join(bench_dir, "checks"), exist_ok=True)
    for src, dst in (("config.json", "configs/gang-racks.json"),
                     ("traffic.json", "traffic/gangs.json"),
                     ("gang_slices.py", "checks/gang_slices.py"),
                     *((f"{name}.json", f"metrics/{name}.json")
                       for name in METRICS)):
        shutil.copy(os.path.join(DATA, src), os.path.join(bench_dir, dst))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "gang-racks", "source": "test fixture",
        "file": "benchmark/configs/gang-racks.json", "reduced": [],
        "why": "fixture"})
    bench["workloads"].append({
        "name": CELL, "config": "gang-racks", "traffic": "gangs",
        "chips": 1, "why": "fixture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the open loops' latency and tails, and nothing of preemption
        if m["name"].startswith("place_due_"):
            m["workloads"].append(CELL)
    for name in METRICS:
        with open(os.path.join(DATA, f"{name}.json")) as f:
            spec = json.load(f)
        bench["per_layer"].append({
            **{k: spec[k] for k in ("name", "unit", "better", "source",
                                    "layer", "moves")},
            "workloads": [CELL]})
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    copy_benchmark(sys.argv[1], link_program=False)
    install(sys.argv[1])
