#!/usr/bin/env python
"""graftlint CLI: JAX-hazard static analysis over the package.

Usage::

    python tools/graftlint.py mxnet_tpu/                 # lint, exit 1 on findings
    python tools/graftlint.py mxnet_tpu tools chip_smoke.py \
        --baseline tools/graftlint_baseline.json          # gate on NEW findings
    python tools/graftlint.py --write-baseline --baseline B.json PATHS
    python tools/graftlint.py --write-env-docs            # regen docs/env_vars.md
    python tools/graftlint.py --check-env-docs            # verify docs in sync

Exit codes: 0 clean, 1 new findings (or docs drift), 2 usage error.
Rule catalog / annotation syntax: docs/static_analysis.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from mxnet_tpu.analysis import graftlint  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*", help="files/dirs to analyze")
    ap.add_argument("--baseline", help="accepted-findings JSON file")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite --baseline with the current findings")
    ap.add_argument("--rules", help="comma list of rule ids to run "
                    "(default: all of %s)" % ", ".join(graftlint.RULES))
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--write-env-docs", action="store_true",
                    help="regenerate the MXNET_TPU block of "
                    "docs/env_vars.md from mxnet_tpu/env.py")
    ap.add_argument("--check-env-docs", action="store_true",
                    help="fail if docs/env_vars.md is out of sync with "
                    "the env registry")
    args = ap.parse_args(argv)

    if args.write_env_docs or args.check_env_docs:
        from mxnet_tpu import env

        doc_path = os.path.join(_ROOT, "docs", "env_vars.md")
        in_sync = env.sync_docs(doc_path, check=args.check_env_docs)
        if args.check_env_docs and not in_sync:
            print("graftlint: docs/env_vars.md is OUT OF SYNC with "
                  "mxnet_tpu/env.py — run "
                  "`python tools/graftlint.py --write-env-docs`")
            return 1
        if args.write_env_docs and not in_sync:
            print("graftlint: rewrote the generated block of %s"
                  % os.path.relpath(doc_path))
        if not args.paths:
            return 0

    if not args.paths:
        ap.print_usage()
        return 2

    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        bad = set(rules) - set(graftlint.RULES)
        if bad:
            print("graftlint: unknown rule(s): %s" % ", ".join(sorted(bad)))
            return 2
    config = graftlint.Config(rules=rules)
    findings = graftlint.analyze_paths(args.paths, config, root=_ROOT)

    baseline = set()
    if args.baseline and os.path.exists(args.baseline) \
            and not args.write_baseline:
        baseline = graftlint.load_baseline(args.baseline)

    if args.write_baseline:
        if not args.baseline:
            print("graftlint: --write-baseline needs --baseline PATH")
            return 2
        graftlint.save_baseline(args.baseline, findings)
        print("graftlint: wrote %d accepted finding(s) to %s"
              % (len(findings), args.baseline))
        return 0

    new, accepted = graftlint.partition(findings, baseline)
    stale = baseline - {f.fingerprint for f in findings}

    if args.json:
        print(json.dumps({"new": [f.to_dict() for f in new],
                          "accepted": [f.to_dict() for f in accepted],
                          "stale_baseline": sorted(stale)}, indent=1))
    else:
        for f in new:
            print("%s:%d: [%s] %s\n    %s"
                  % (f.path, f.line, f.rule, f.message, f.snippet))
        if accepted:
            print("graftlint: %d baselined finding(s) suppressed"
                  % len(accepted))
        if stale:
            print("graftlint: %d stale baseline entr%s (fixed findings "
                  "still in the baseline — rewrite it with "
                  "--write-baseline)"
                  % (len(stale), "y" if len(stale) == 1 else "ies"))
        print("graftlint: %d new finding(s) in %d file(s)"
              % (len(new), len({f.path for f in new})))
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
