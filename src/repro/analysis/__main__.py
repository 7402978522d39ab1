"""CLI: ``python -m repro.analysis [lint|conformance|all]``.

Exit status is nonzero when any lint violation (non-allowlisted
finding) or failing conformance cell exists — CI gates on it.  The
conformance sweep needs a multi-device mesh, so the device-count flag
is set *before* anything imports jax (XLA pins the host device count
at first backend init); an inherited XLA_FLAGS wins.
"""
import argparse
import os
import sys


def _parse(argv):
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repo invariant linter + schedule-conformance verifier")
    ap.add_argument("command", nargs="?", default="lint",
                    choices=("lint", "conformance", "all"))
    ap.add_argument("--root", default=None,
                    help="src directory to lint (default: the installed "
                         "repro package's src root)")
    ap.add_argument("--report", default=None,
                    help="write ANALYSIS_report.json here (default: "
                         "ANALYSIS_report.json for conformance/all, "
                         "none for lint)")
    ap.add_argument("--family", default=None,
                    help="restrict conformance to one registry family")
    ap.add_argument("--comm", default=None, choices=("dense", "sparse"),
                    help="restrict conformance to one wire format")
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host device count for conformance "
                         "(ignored when XLA_FLAGS is already set)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(sys.argv[1:] if argv is None else argv)
    report = {"schema": 1}
    failed = False

    if args.command in ("lint", "all"):
        from repro.analysis import lint
        findings, scanned = lint.run_lint(src_root=args.root)
        print(lint.render_findings(findings))
        report["lint"] = lint.make_lint_report(findings, scanned)
        failed |= bool(lint.violations(findings))

    if args.command in ("conformance", "all"):
        if "XLA_FLAGS" not in os.environ:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"   # lowering only: never a chip
        from repro.analysis import conformance
        comms = (args.comm,) if args.comm else ("dense", "sparse")

        def progress(row):
            words = ("" if row["modeled_words"] is None else
                     f" modeled={row['modeled_words']:.0f}"
                     f" measured={row['measured_words']:.0f}")
            print(f"{row['verdict']:4s} {row['cell']:32s} "
                  f"[{row['mode']}] collectives={row['collectives']}"
                  + words)
            for err in row["errors"]:
                print(f"     ! {err}")

        conf = conformance.run_conformance(family=args.family,
                                           comms=comms,
                                           progress=progress)
        report["conformance"] = conf
        print(f"conformance: {conf['pass']} pass, {conf['fail']} fail "
              f"({conf['structural']} structural) on p={conf['p']}")
        failed |= conf["fail"] > 0

    report_path = args.report
    if report_path is None and args.command != "lint":
        report_path = "ANALYSIS_report.json"
    if report_path:
        from repro.analysis.findings import write_report
        write_report(report, report_path)
        print(f"wrote {report_path}")

    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
