"""Run the full dry-run sweep: every runnable (arch x shape x mesh) cell.

Each cell runs in its own subprocess (compile-memory isolation; a single
OOM or crash marks that cell failed without killing the sweep).  Results
land in results/dryrun/<arch>__<shape>__<mesh>.json plus a summary JSONL.

  PYTHONPATH=src python -m repro.launch.sweep_dryrun [--only-single-pod]

``--fusedmm`` sweeps the paper's distributed FusedMM cells instead: every
algorithm registered in repro.core.api x its supported elisions, each
cell one `dryrun_fusedmm` subprocess — the sweep itself never branches
per family.

  PYTHONPATH=src python -m repro.launch.sweep_dryrun --fusedmm \
      [--fusedmm-m 1048576] [--fusedmm-r 256]

The sweep itself never imports JAX, which would take an accelerator away
from its children; even the cell list comes from a child.  Every child
runs on the CPU backend.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ARCHS = [
    "jamba-v0.1-52b", "stablelm-1.6b", "llama3.2-1b", "qwen3-1.7b",
    "qwen3-4b", "qwen2-vl-72b", "mamba2-1.3b", "deepseek-v2-lite-16b",
    "phi3.5-moe-42b-a6.6b", "hubert-xlarge",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

# gradient-accumulation depth per arch (train cells): larger models need
# smaller micro-tokens to fit activation working sets in 16 GB HBM
MICROBATCH = {
    "jamba-v0.1-52b": 16, "qwen2-vl-72b": 16, "phi3.5-moe-42b-a6.6b": 8,
    "deepseek-v2-lite-16b": 8, "qwen3-4b": 8,
}


ELISIONS = ("none", "reuse", "fused")

#: environment of every child: compile-only work, on the CPU backend
CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def fusedmm_cells():
    """The FULL (algo, elision) grid with per-cell support status.

    Sweeps every family x {none, reuse, fused} cell — not just the
    registry-declared ones — so structurally impossible cells (s25
    "fused") appear as explicit skip records in the summary instead of
    being silently omitted; docs/algorithms.md's feasibility table is
    regenerable from the sweep output.  No per-family branching: a new
    registered algorithm appears here automatically.
    """
    code = ("import json; from repro.core import api; "
            "from repro.launch.sweep_dryrun import ELISIONS; "
            "print(json.dumps([(n, el, el in api.ALGORITHMS[n].elisions) "
            "for n in sorted(api.ALGORITHMS) for el in ELISIONS]))")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, check=True, env=CHILD_ENV)
    return [tuple(c) for c in json.loads(proc.stdout.splitlines()[-1])]


def _print_fusedmm_summary(summary_path):
    """Render the sweep as an algo x elision status table (every cell
    reported — ok / skipped / failed / unsupported, never omitted)."""
    cells = {}
    with open(summary_path) as f:
        for line in f:
            r = json.loads(line)
            if "skipped" in r:
                status = "skipped"
            elif not r.get("ok"):
                status = "FAILED"
            else:
                status = f"ok c={r.get('c')}"
            cells[(r["algo"], r["elision"])] = status
    algos = sorted({a for a, _ in cells})
    width = max(12, *(len(v) + 2 for v in cells.values()))
    print("\nFUSEDMM SWEEP SUMMARY (algo x elision)")
    print(f"{'':6s}" + "".join(f"{el:>{width}s}" for el in ELISIONS))
    for a in algos:
        row = "".join(f"{cells.get((a, el), '-'):>{width}s}"
                      for el in ELISIONS)
        print(f"{a:6s}{row}")


def run_fusedmm_sweep(args):
    os.makedirs(args.outdir, exist_ok=True)
    summary_path = os.path.join(args.outdir, "summary_fusedmm.jsonl")
    done = set()
    if os.path.exists(summary_path):
        with open(summary_path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("ok"):     # failed/timed-out cells retry
                    done.add((r["algo"], r["elision"]))

    def emit(rec):
        with open(summary_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)

    for algo, elision, supported in fusedmm_cells():
        if (algo, elision) in done:
            continue
        if not supported:
            # structurally impossible cell: an explicit skip record, no
            # subprocess (there is no executor to lower)
            emit(dict(algo=algo, elision=elision, ok=True, seconds=0.0,
                      error="",
                      skipped="unsupported elision (structurally "
                              "impossible; see docs/algorithms.md)"))
            continue
        tag = f"fusedmm__{algo}__{elision}"
        out = os.path.join(args.outdir, tag + ".json")
        cmd = [sys.executable, "-m", "repro.launch.dryrun_fusedmm",
               "--algo", algo, "--elision", elision,
               "--m", str(args.fusedmm_m), "--r", str(args.fusedmm_r),
               "--nnz-row", str(args.fusedmm_nnz_row), "--out", out]
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.timeout, env=CHILD_ENV)
            ok = proc.returncode == 0
            err = proc.stderr[-2000:] if not ok else ""
        except subprocess.TimeoutExpired:
            ok, err = False, "timeout"
        rec = dict(algo=algo, elision=elision, ok=ok,
                   seconds=round(time.time() - t0, 1), error=err)
        if ok and os.path.exists(out):
            try:
                with open(out) as f:
                    r = json.load(f)
                if "skipped" in r:
                    rec["skipped"] = r["skipped"]
                else:
                    rec["c"] = r.get("c")
                    rec["paper_words"] = r.get("paper_words")
                    rec["wire_gb"] = round(
                        r["collectives"]["total_wire_bytes"] / 1e9, 3)
            except Exception as e:     # pragma: no cover
                rec["parse_error"] = str(e)
        emit(rec)
    _print_fusedmm_summary(summary_path)
    print("FUSEDMM SWEEP COMPLETE")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument("--only-single-pod", action="store_true")
    ap.add_argument("--timeout", type=int, default=1500)
    ap.add_argument("--archs", default=None, help="comma-separated subset")
    ap.add_argument("--fusedmm", action="store_true",
                    help="sweep distributed FusedMM cells instead of LM")
    ap.add_argument("--fusedmm-m", type=int, default=1 << 20)
    ap.add_argument("--fusedmm-r", type=int, default=256)
    ap.add_argument("--fusedmm-nnz-row", type=int, default=32)
    args = ap.parse_args(argv)

    if args.fusedmm:
        return run_fusedmm_sweep(args)

    os.makedirs(args.outdir, exist_ok=True)
    summary_path = os.path.join(args.outdir, "summary.jsonl")
    archs = args.archs.split(",") if args.archs else ARCHS
    meshes = [False] if args.only_single_pod else [False, True]

    done = set()
    if os.path.exists(summary_path):
        with open(summary_path) as f:
            for line in f:
                r = json.loads(line)
                done.add((r["arch"], r["shape"], r["multi_pod"]))

    for multi_pod in meshes:
        for arch in archs:
            for shape in SHAPES:
                key = (arch, shape, multi_pod)
                if key in done:
                    continue
                tag = f"{arch}__{shape}__{'multi' if multi_pod else 'single'}"
                out = os.path.join(args.outdir, tag + ".json")
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", out,
                       "--microbatch", str(MICROBATCH.get(arch, 4))]
                if multi_pod:
                    cmd.append("--multi-pod")
                t0 = time.time()
                try:
                    proc = subprocess.run(cmd, capture_output=True,
                                          text=True, timeout=args.timeout,
                                          env=CHILD_ENV)
                    ok = proc.returncode == 0
                    err = proc.stderr[-2000:] if not ok else ""
                except subprocess.TimeoutExpired:
                    ok, err = False, "timeout"
                dt = time.time() - t0
                rec = dict(arch=arch, shape=shape, multi_pod=multi_pod,
                           ok=ok, seconds=round(dt, 1), error=err)
                if ok and os.path.exists(out):
                    try:
                        with open(out) as f:
                            r = json.load(f)
                        if "skipped" in r:
                            rec["skipped"] = r["skipped"]
                        else:
                            rec["temp_gb"] = round(
                                r["memory"]["temp_size_in_bytes"] / 1e9, 2)
                            rec["flops"] = r["cost"].get("flops")
                            rec["wire_gb"] = round(
                                r["collectives"]["total_wire_bytes"] / 1e9,
                                3)
                    except Exception as e:     # pragma: no cover
                        rec["parse_error"] = str(e)
                with open(summary_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                print(json.dumps(rec), flush=True)
    print("SWEEP COMPLETE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
