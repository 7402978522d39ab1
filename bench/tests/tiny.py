"""Tiny copies of the benchmark's cells, for runs on the CPU.

``layout(tmp)`` writes, into a directory of its own, a configuration,
a cell file and a benchmark entry for each real cell at a tiny size
(named ``tiny-<cell>``), a copy of the FusedMM cell on four devices and
one new metric, and returns a ``harness.Layout`` that finds them there
before the real parts.  The tiny cells keep the real cells' limits,
traffic and loops.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import harness

#: the configurations' sizes cut to what the CPU runs in seconds
TINY = {"scale": 8, "m": 256, "n": 256}
NEW_METRIC = "rounds_done"
FOUR = "tiny-fusedmm-4chip"


def _dump(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=2))


def layout(tmp) -> harness.Layout:
    tmp = Path(tmp)
    real = harness.Layout.load()
    spec = json.loads(json.dumps(real.spec))
    for w in list(real.spec["workloads"]):
        name, config = "tiny-" + w["name"], "tiny-" + w["config"]
        cfg = real.json("configs", w["config"])
        cfg.update({k: v for k, v in TINY.items() if k in cfg})
        _dump(tmp / "configs" / f"{config}.json", cfg)
        _dump(tmp / "cells" / f"{name}.json", real.json("cells", w["name"]))
        spec["workloads"].append(dict(w, name=name, config=config))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if w["name"] in m.get("workloads", []):
                m["workloads"].append(name)
    # the FusedMM cell again on four devices, where its exchange exists
    first = next(w for w in spec["workloads"]
                 if w["traffic"] == "fusedmm-rounds"
                 and w["name"].startswith("tiny-"))
    _dump(tmp / "cells" / f"{FOUR}.json",
          real.json("cells", first["name"][len("tiny-"):]))
    spec["workloads"].append(dict(first, name=FOUR, chips=4))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if first["name"] in m.get("workloads", []):
            m["workloads"].append(FOUR)
    (tmp / "metrics").mkdir(parents=True, exist_ok=True)
    (tmp / "metrics" / f"{NEW_METRIC}.py").write_text(
        '"""Rounds the window completed."""\n\n\n'
        'def read(run):\n    return run.counters.get("rounds")\n')
    spec["end_to_end"].append({
        "name": NEW_METRIC, "unit": "rounds", "better": "higher",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["tiny-rmat18-fusedmm-1chip"]})
    return harness.Layout(spec, roots=(tmp, harness.BENCH))


def run(lay: harness.Layout, name: str, devices, seed: int = 7,
        seconds: float = 0.0) -> dict:
    """One untraced run of a tiny cell: a window of one unit."""
    import time
    return harness.run_cell(lay, name, seed, seconds, False, devices,
                            time.perf_counter())
