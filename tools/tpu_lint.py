#!/usr/bin/env python
"""tpu-lint CLI: whole-repo static analysis gate.

Runs the ten TPL rules over the tree and exits non-zero on any unbaselined
finding (or stale baseline entry, on a full run). Loads
``paddle_tpu/analysis`` standalone — without importing ``paddle_tpu`` and
therefore without importing jax — and keeps a per-file findings cache
(keyed mtime+size+rules-hash) so a warm run is O(changed files): ~10s cold,
~2s warm on the full tree.

Usage:
  python tools/tpu_lint.py                  # human output, exit 0/1
  python tools/tpu_lint.py --json           # machine output
  python tools/tpu_lint.py --changed        # findings in git-changed files only
  python tools/tpu_lint.py --changed=main   # ... changed relative to a ref
  python tools/tpu_lint.py --explain TPL003
  python tools/tpu_lint.py --rules TPL001,TPL005
  python tools/tpu_lint.py --no-cache       # force a full re-lint
  python tools/tpu_lint.py --update-baseline   # absorb current findings

Suppression: inline `# tpu-lint: disable=TPL00x` on (or above) the
offending line, or a justified entry in tools/lint_baseline.json.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
DEFAULT_BASELINE = ROOT / "tools" / "lint_baseline.json"
DEFAULT_CACHE = ROOT / "tools" / ".tpu_lint_cache.json"


def load_analysis():
    """Load paddle_tpu/analysis as a standalone package (no jax import)."""
    if "tpu_analysis" in sys.modules:
        return sys.modules["tpu_analysis"]
    pkg_dir = ROOT / "paddle_tpu" / "analysis"
    spec = importlib.util.spec_from_file_location(
        "tpu_analysis",
        pkg_dir / "__init__.py",
        submodule_search_locations=[str(pkg_dir)],
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["tpu_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


def changed_paths(root: Path, ref: str):
    """Repo-relative .py paths changed vs ``ref`` (tracked) or untracked."""
    out = set()
    for cmd in (
        ["git", "-C", str(root), "diff", "--name-only", ref, "--"],
        ["git", "-C", str(root), "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=30)
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr.strip() or f"{' '.join(cmd)} failed")
        out.update(
            line.strip()
            for line in proc.stdout.splitlines()
            if line.strip().endswith(".py")
        )
    return sorted(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tpu_lint", description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=str(ROOT), help="repo root to scan")
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE), help="suppression file")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument("--rules", default="", help="comma-separated subset, e.g. TPL001,TPL003")
    ap.add_argument(
        "--changed",
        nargs="?",
        const="HEAD",
        default=None,
        metavar="REF",
        help="report per-file findings only for files changed vs REF "
        "(default HEAD) or untracked; global drift rules still see the "
        "whole tree",
    )
    ap.add_argument(
        "--cache",
        default=str(DEFAULT_CACHE),
        help="per-file findings cache path (keyed mtime+size+rules-hash)",
    )
    ap.add_argument("--no-cache", action="store_true", help="ignore and don't write the cache")
    ap.add_argument("--explain", metavar="RULE", help="print what a rule enforces and exit")
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the baseline: keep still-matching justified entries, "
        "add current unbaselined findings with a TODO justification, drop stale keys",
    )
    args = ap.parse_args(argv)

    an = load_analysis()

    if args.explain:
        rule = args.explain.upper()
        if rule not in an.RULES:
            print(f"unknown rule {rule}; known: {', '.join(sorted(an.RULES))}")
            return 2
        title, severity, text = an.RULES[rule]
        print(f"{rule} ({title}, {severity})\n\n{text}")
        return 0

    rules = [r.strip().upper() for r in args.rules.split(",") if r.strip()] or None

    only_paths = None
    if args.changed is not None:
        try:
            only_paths = changed_paths(Path(args.root).resolve(), args.changed)
        except (RuntimeError, OSError, subprocess.TimeoutExpired) as exc:
            print(f"tpu-lint: --changed failed: {exc}", file=sys.stderr)
            return 2
    if args.update_baseline and only_paths is not None:
        print("tpu-lint: --update-baseline needs the full view; drop --changed",
              file=sys.stderr)
        return 2

    # a filtered run cannot judge entries for rules/files it did not report
    full_run = rules is None and only_paths is None

    t0 = time.time()
    result = an.lint_tree(
        args.root,
        cache_path=None if args.no_cache else args.cache,
        rules=rules,
        only_paths=only_paths,
    )
    baseline = an.Baseline.load(args.baseline)
    unbaselined, baselined, stale = baseline.split(result.findings)
    if not full_run:
        stale = []
    wall_s = time.time() - t0

    if args.update_baseline:
        kept = [e for e in baseline.entries if e["key"] not in stale]
        known = {e["key"] for e in kept}
        added = 0
        for f in unbaselined:
            if f.key not in known:
                kept.append({"key": f.key, "justification": "TODO: justify or fix"})
                known.add(f.key)
                added += 1
        an.Baseline(kept).save(args.baseline)
        print(
            f"baseline updated: {len(kept)} entries "
            f"(+{added} new, -{len(stale)} stale)"
        )
        return 0

    current_keys = {f.key for f in result.findings}
    if args.json:
        print(
            json.dumps(
                {
                    "tool": "tpu_lint",
                    "files_scanned": result.files_scanned,
                    "files_linted": result.files_linted,
                    "files_cached": result.files_cached,
                    "cache": result.cache_state,
                    "wall_s": round(wall_s, 3),
                    "rule_timings_s": result.timings,
                    "unbaselined": len(unbaselined),
                    "baselined": len(baselined),
                    "stale_baseline": stale,
                    "findings": [f.to_dict() for f in unbaselined],
                }
            )
        )
    else:
        for f in unbaselined:
            print(f"{f.path}:{f.line}: {f.rule} {f.severity}: {f.message}")
            if f.hint:
                print(f"    hint: {f.hint}")
            print(f"    key:  {f.key}")
        for key in stale:
            near = an.nearest_key(key, current_keys)
            print(f"stale baseline entry (no longer fires): {key}")
            if near:
                print(f"    nearest current finding: {near}")
        print(
            f"tpu-lint: {result.files_scanned} files "
            f"({result.files_cached} cached, {result.files_linted} linted), "
            f"{len(unbaselined)} unbaselined, {len(baselined)} baselined, "
            f"{len(stale)} stale, {wall_s:.2f}s"
        )
        if unbaselined or stale:
            print(
                "fix the findings, add `# tpu-lint: disable=RULE` where justified "
                "inline, or run with --update-baseline and justify each entry."
            )
    return 1 if (unbaselined or stale) else 0


if __name__ == "__main__":
    sys.exit(main())
