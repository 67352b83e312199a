"""CLI for the static verification layer (DESIGN.md §5).

    python -m repro.analysis --all            # the CI gate
    python -m repro.analysis --lint --locks   # source analyzers only
    python -m repro.analysis --plan p.pkl     # verify a pickled plan

Exits non-zero on any diagnostic.  ``--all`` runs the lint, the
lock-discipline checker and a planner self-check: a handful of real
plans built against small cubes, each required to verify clean — so the
gate exercises ``plan_check`` against live planner output, not just
fixtures.

Source analyzers are pure ast and never import jax; only the
``--self-check`` path imports the planner.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from .concurrency import check_lock_discipline
from .diagnostics import Diagnostic, render
from .lint import lint_tree
from .plan_check import check_plan, check_plan_file


def _default_src_root() -> Path:
    # in-repo layout: .../src/repro/analysis/__main__.py → src/repro
    return Path(__file__).resolve().parents[1]


def self_check() -> list[Diagnostic]:
    """Verify live planner output on small cubes (imports repro.core)."""
    import numpy as np

    from repro.core import (Box, OrderedAxis, Polygon, PolytopeExtractor,
                            Request, Select, TensorDatacube)

    cube = TensorDatacube([
        OrderedAxis("t", np.arange(4.0)),
        OrderedAxis("x", np.arange(32.0)),
        OrderedAxis("y", np.arange(32.0)),
    ])
    tri = np.array([[4.0, 2.0], [28.0, 9.0], [15.0, 30.0]])
    requests = {
        "box": Request([Select("t", [1.0]),
                        Box(("x", "y"), [3.0, 4.0], [10.0, 21.0])]),
        "triangle": Request([Select("t", [0.0]), Polygon(("x", "y"), tri)]),
        "span_all": Request([Box(("t", "x"), [0.0, 0.0], [3.0, 31.0])]),
    }
    pe = PolytopeExtractor(cube)
    diags: list[Diagnostic] = []
    for name, req in requests.items():
        plan, stats = pe.plan(req)
        for d in check_plan(plan, datacube=cube, stats=stats):
            diags.append(Diagnostic(d.rule, f"[self-check {name}] "
                                    + d.message))
    return diags


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static verification layer: plan checker, AST lint, "
                    "lock-discipline race detector.")
    ap.add_argument("--all", action="store_true",
                    help="run lint + locks + planner self-check "
                         "(the CI gate)")
    ap.add_argument("--lint", action="store_true", help="AST lint rules")
    ap.add_argument("--locks", action="store_true",
                    help="lock-discipline checker")
    ap.add_argument("--self-check", action="store_true",
                    help="verify live planner output on small cubes")
    ap.add_argument("--plan", nargs="*", metavar="PKL", default=[],
                    help="pickled ExtractionPlan files to verify")
    ap.add_argument("--n-elements", type=int, default=None,
                    help="datacube element count for --plan bounds checks")
    ap.add_argument("--root", type=Path, default=None,
                    help="source root to analyze (default: the installed "
                         "repro package directory)")
    args = ap.parse_args(argv)

    src_root = args.root if args.root is not None else _default_src_root()
    diags: list[Diagnostic] = []
    ran = False

    if args.all or args.lint:
        ran = True
        diags += lint_tree(src_root)
    if args.all or args.locks:
        ran = True
        diags += check_lock_discipline(src_root)
    for pf in args.plan:
        ran = True
        diags += check_plan_file(pf, n_elements=args.n_elements)
    if args.all or args.self_check:
        ran = True
        diags += self_check()

    if not ran:
        ap.print_help()
        return 2
    if diags:
        print(render(diags), file=sys.stderr)
        print(f"\n{len(diags)} diagnostic(s).", file=sys.stderr)
        return 1
    print("repro.analysis: all checks clean.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
