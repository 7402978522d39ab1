"""Readings of a cell's compared numbers, for the program and for its
control, over many seeds in one process.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed: the cell's set-up, a short window of ``--seconds`` at the
cell's own load, then the numbers the run compares (the program against
the reference) and the same numbers with the control in the program's
place (the reference at the next precision below, ``reference.py``).
One JSON line per seed.  The limits in ``cells/<name>.json`` are set
between the largest program reading and the smallest control reading.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(layout, name: str, seed: int, seconds: float, devices):
    cell = layout.workload(name)
    traffic = layout.json("traffic", cell["traffic"])
    loop = layout.module("loops", traffic["loop"]).Loop(
        layout.json("configs", cell["config"]), traffic, devices, seed)
    loop.plan()
    loop.warm()
    done, start = 0, time.perf_counter()
    while not done or time.perf_counter() - start < seconds:
        done += loop.step()[loop.UNIT]
    loop.finish()
    gc.collect()
    t = time.perf_counter()
    program = loop.check()
    ref_s = time.perf_counter() - t
    control = loop.check_control()
    return {"workload": name, "seed": seed, "units": done,
            "reference_s": ref_s, "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch import compile_cache
    layout = harness.Layout.load()
    devices = harness.require_chips(layout.workload(args.workload)["chips"])
    compile_cache.enable()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(layout, args.workload, seed,
                                  args.seconds, devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
