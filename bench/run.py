"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload W --seed N --seconds S --trace 0|1

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
traced, ``breakdown``), and the numbers compared with the reference,
each beside its limit, under ``checks`` and as the last lines of
standard error.  With ``--trace 0`` the metrics are the cell's
end-to-end ones, with ``--trace 1`` its per-layer ones, read from a
profiler trace of the whole window.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up runs from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
LIMIT_S = 340                     # a run that hangs ends before 360 s


def _watchdog():
    print(f"bench: run exceeded {LIMIT_S} s", file=sys.stderr, flush=True)
    os._exit(124)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    layout = harness.Layout.load()
    cell = layout.workload(args.workload)
    devices = harness.require_chips(cell["chips"])

    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    # every program goes to the cache, so only a checkout's first run
    # compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    timer = threading.Timer(LIMIT_S, _watchdog)
    timer.daemon = True
    timer.start()
    result = harness.run_cell(layout, args.workload, args.seed,
                              args.seconds, bool(args.trace), devices, T0)
    timer.cancel()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # no teardown output may follow the result and the checks
    os._exit(code)
