"""``python -m repro.analysis`` — run the full static pass.

Both engines by default; ``--lint-only`` skips the (jax-importing)
kernel audit and ``--audit-only`` skips the AST rules.  Exit status 1
iff findings survive the ``analysis.toml`` allowlist — CI keys on
that, so does the tier-1 test.
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time


def _find_root(start: pathlib.Path) -> pathlib.Path:
    for cand in (start, *start.parents):
        if (cand / ".git").exists() or (cand / "analysis.toml").is_file():
            return cand
    return start


def _lint(root, rule_names):
    from repro.analysis.config import AnalysisConfig
    from repro.analysis.lint import run_lint
    from repro.analysis.rules import ALL_RULES
    rules = list(ALL_RULES)
    if rule_names:
        known = {r.name for r in rules}
        unknown = set(rule_names) - known
        if unknown:
            raise SystemExit(
                f"unknown rule(s) {sorted(unknown)}; available: {sorted(known)}")
        rules = [r for r in rules if r.name in rule_names]
    return run_lint(root, rules, AnalysisConfig.load(root))


def _audit(arch: str, max_batch: int, path_names):
    import jax

    from repro.analysis.kernel_audit import audit_registry
    from repro.serving.trigger import init_weights
    cfg = importlib.import_module(f"repro.configs.{arch}").MODEL
    params = init_weights(cfg, jax.random.PRNGKey(0))
    return audit_registry(cfg, params, max_batch=max_batch,
                          names=path_names or None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static analysis: AST lint rules + kernel-contract audit")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as a JSON document")
    ap.add_argument("--rules", default="",
                    help="comma-separated lint rule subset (default: all)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--lint-only", action="store_true",
                      help="AST rules only (no jax import)")
    mode.add_argument("--audit-only", action="store_true",
                      help="kernel-contract audit only")
    ap.add_argument("--paths", default="",
                    help="comma-separated registered path subset to audit")
    ap.add_argument("--arch", default="jedi_30p",
                    help="config module under repro.configs (default: "
                         "jedi_30p)")
    ap.add_argument("--max-batch", type=int, default=1024,
                    help="bucket-ladder ceiling for the audit")
    ap.add_argument("--root", default=None,
                    help="repo root (default: discovered from cwd)")
    args = ap.parse_args(argv)

    root = (pathlib.Path(args.root).resolve() if args.root
            else _find_root(pathlib.Path.cwd().resolve()))
    rule_names = [r.strip() for r in args.rules.split(",") if r.strip()]
    path_names = [p.strip() for p in args.paths.split(",") if p.strip()]

    findings = []
    timings = {}
    if not args.audit_only:
        t0 = time.perf_counter()
        findings += _lint(root, rule_names)
        timings["lint_s"] = round(time.perf_counter() - t0, 3)
    if not args.lint_only:
        t0 = time.perf_counter()
        findings += _audit(args.arch, args.max_batch, path_names)
        timings["audit_s"] = round(time.perf_counter() - t0, 3)

    if args.as_json:
        print(json.dumps({"findings": [f.as_dict() for f in findings],
                          "count": len(findings), "timings": timings},
                         indent=2))
    else:
        for f in findings:
            print(f.render())
        engines = " + ".join(f"{k[:-2]} {v:.2f}s" for k, v in timings.items())
        status = "clean" if not findings else f"{len(findings)} finding(s)"
        print(f"repro.analysis: {status} ({engines})", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
