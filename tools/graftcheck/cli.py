"""graftcheck CLI — run passes, apply suppressions, report, exit.

Exit codes (scripts consume these; a caller gates on 0):

  * ``0`` — clean (every finding suppressed or none at all)
  * ``1`` — unsuppressed findings
  * ``2`` — internal errors (a pass crashed or detected its own vacuity);
    never suppressible, because a broken audit must not read as a clean repo
  * ``3`` — usage error (bad flag, unknown pass)
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

from tools.graftcheck import findings as fmod
from tools.graftcheck import registry
from tools.graftcheck.context import RepoContext, git_changed_files
from tools.graftcheck.findings import Finding

EXIT_CLEAN = 0
EXIT_FINDINGS = 1
EXIT_INTERNAL = 2
EXIT_USAGE = 3

DEFAULT_SUPPRESSIONS = pathlib.Path(__file__).with_name("suppressions.txt")


class _Parser(argparse.ArgumentParser):
    def error(self, message):  # argparse defaults to exit code 2
        self.exit(EXIT_USAGE, f"{self.prog}: error: {message}\n")


def build_parser() -> argparse.ArgumentParser:
    p = _Parser(
        prog="graftcheck",
        description="framework-aware static analysis: AST lints, jaxpr "
                    "trace audits, compiled-HLO audits "
                    "(docs/STATIC_ANALYSIS.md)")
    p.add_argument("--root", default=".", help="repo root (default: cwd)")
    p.add_argument("--layer", choices=registry.LAYERS,
                   help="run only this layer's passes")
    p.add_argument("--pass", dest="passes", action="append", default=[],
                   metavar="PASS_ID", help="run only the named pass "
                   "(repeatable); overrides --layer")
    p.add_argument("--changed", action="store_true",
                   help="fast pre-commit mode: scan only files changed vs "
                   "HEAD; anchored repo-wide passes run only when an anchor "
                   "changed; trace passes (jaxpr/hlo) are skipped with a "
                   "notice unless --trace, --pass, or --layer opts them in")
    p.add_argument("--trace", action="store_true",
                   help="with --changed: run the jaxpr/hlo trace passes "
                   "too (seconds of compile time) instead of skipping them")
    p.add_argument("--update-budgets", action="store_true",
                   help="fresh-compile every budgeted program and rewrite "
                   "configs/hlo_budgets.json (provenance: jax version, "
                   "mesh, config digest) — budget bumps land as reviewable "
                   "diffs, never silently")
    p.add_argument("--list-passes", action="store_true",
                   help="list registered passes and exit")
    p.add_argument("--json", metavar="FILE",
                   help="also write the dtf-lint-report/1 JSON here "
                   "('-' for stdout)")
    p.add_argument("--format", choices=("table", "json"), default="table",
                   help="stdout format (default: table)")
    p.add_argument("--suppressions", default=str(DEFAULT_SUPPRESSIONS),
                   help="suppression file (default: tools/graftcheck/"
                   "suppressions.txt)")
    return p


def select_passes(args, changed: set[str] | None) -> list[registry.PassInfo]:
    if args.passes:
        return [registry.get_pass(pid) for pid in args.passes]
    infos = list(registry.PASSES.values())
    if args.layer:
        infos = [p for p in infos if p.layer == args.layer]
    elif args.changed and not getattr(args, "trace", False):
        # Trace layers (jaxpr/hlo) compile the real step — seconds, not
        # milliseconds; the fast pre-commit loop is AST-only unless the
        # caller opts back in with --trace (main() prints the skip count).
        infos = [p for p in infos if p.layer == registry.LAYER_AST]
    if changed is not None:
        infos = [p for p in infos if p.relevant_for_changed(changed)]
    return infos


def skipped_trace_passes(args, changed: set[str]) -> list[registry.PassInfo]:
    """The trace (jaxpr/hlo) passes a --changed run dropped — the explicit
    notice keeps the fast path honest about what it did NOT check."""
    if not args.changed or args.layer or args.passes \
            or getattr(args, "trace", False):
        return []
    return [p for p in registry.PASSES.values()
            if p.layer in registry.TRACE_LAYERS
            and p.relevant_for_changed(changed)]


def run_passes(ctx: RepoContext,
               infos: list[registry.PassInfo]) -> list[Finding]:
    findings: list[Finding] = []
    for info in infos:
        try:
            findings.extend(info.fn(ctx))
        except Exception as exc:  # a crashed audit must not read as clean
            findings.append(Finding(
                info.pass_id, "pass", f"pass crashed: {exc!r}",
                severity=fmod.SEVERITY_INTERNAL))
    return findings


def format_table(report: dict, infos: list[registry.PassInfo]) -> str:
    lines = []
    rows = [f for f in report["findings"] if not f["suppressed"]]
    sup = [f for f in report["findings"] if f["suppressed"]]
    if rows:
        w_pass = max(len(f["pass_id"]) for f in rows)
        w_where = max(len(f["where"]) for f in rows)
        for f in sorted(rows, key=lambda f: (f["pass_id"], f["where"])):
            tag = " [internal]" if f["severity"] == fmod.SEVERITY_INTERNAL else ""
            lines.append(f"{f['pass_id']:<{w_pass}}  {f['where']:<{w_where}}"
                         f"  {f['message']}{tag}")
        lines.append("")
    c = report["counts"]
    lines.append(
        f"graftcheck: {len(infos)} pass(es) run, {c['findings']} finding(s)"
        f" ({c['internal_errors']} internal), {c['suppressed']} suppressed")
    for f in sup:
        lines.append(f"  suppressed: {f['pass_id']} {f['where']} — "
                     f"{f['justification']}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)

    if args.list_passes:
        for info in sorted(registry.PASSES.values(),
                           key=lambda p: (p.layer, p.pass_id)):
            print(f"{info.pass_id:<26} [{info.layer}]  {info.description}")
        return EXIT_CLEAN

    root = pathlib.Path(args.root).resolve()

    if args.update_budgets:
        from tools.graftcheck import hlo_passes
        ctx = RepoContext(root)
        try:
            path = hlo_passes.write_budgets(ctx)
        except Exception as exc:
            print(f"graftcheck: --update-budgets failed: {exc!r}",
                  file=sys.stderr)
            return EXIT_INTERNAL
        print(f"graftcheck: wrote {path} — review and commit the diff")
        return EXIT_CLEAN

    changed = None
    if args.changed:
        try:
            changed = git_changed_files(root)
        except RuntimeError as exc:
            print(f"graftcheck: --changed needs git: {exc}", file=sys.stderr)
            return EXIT_USAGE

    try:
        infos = select_passes(args, changed)
    except KeyError as exc:
        print(f"graftcheck: {exc.args[0]}", file=sys.stderr)
        return EXIT_USAGE

    if changed is not None:
        skipped = skipped_trace_passes(args, changed)
        if skipped:
            print(f"graftcheck: {len(skipped)} trace passes skipped in "
                  f"--changed mode ({', '.join(sorted(p.pass_id for p in skipped))})"
                  f" — add --trace to run them")

    ctx = RepoContext(root, changed=changed)
    findings = run_passes(ctx, infos)
    sups, sup_findings = fmod.load_suppressions(args.suppressions)
    findings.extend(sup_findings)
    full_run = (changed is None and not args.passes and not args.layer)
    stale = fmod.apply_suppressions(
        findings, sups, suppression_file=pathlib.Path(args.suppressions).name,
        stale_check_ids=None if full_run else {i.pass_id for i in infos})
    if changed is None:  # --changed sees partial files; can't judge staleness
        findings.extend(stale)

    report = fmod.build_report(findings, [i.pass_id for i in infos], root)
    if args.format == "json":
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(format_table(report, infos))
    if args.json:
        payload = json.dumps(report, indent=2, sort_keys=True)
        if args.json == "-":
            if args.format != "json":
                print(payload)
        else:
            pathlib.Path(args.json).write_text(payload + "\n")

    if report["counts"]["internal_errors"]:
        return EXIT_INTERNAL
    if report["counts"]["findings"]:
        return EXIT_FINDINGS
    return EXIT_CLEAN
