"""Campaign driver CLI.

    python -m attacking_federate_learning_tpu.campaigns spec.json \
        [--executor supervisor|inline] [--order grouped|spec|shuffled] \
        [--cache-dir D --cache-budget-mb N] [--deadline SECS] [--dry-run]

Also dispatched as ``... cli campaign <spec.json> ...`` (cli.py).  The
spec is a CampaignSpec JSON (campaigns/spec.py; ARCHITECTURE.md
"Campaign engine" documents the format).  Exit status: 0 = every cell
done or skipped, 1 = some cell failed (or a bad spec), 75 = stopped
cleanly at the wall-clock deadline (re-invoke to continue — the
campaign journal resumes only the remaining cells).
"""

from __future__ import annotations

import argparse


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="attacking_federate_learning_tpu campaign",
        description="Run a declarative defense x attack x topology "
                    "sweep as a resumable, cache-aware campaign "
                    "(campaigns/scheduler.py).")
    p.add_argument("spec", help="CampaignSpec JSON path")
    p.add_argument("--executor", default="supervisor",
                   choices=["supervisor", "inline"],
                   help="'supervisor' runs each cell as a child under "
                        "tools/supervisor.py (bounded retries, journal "
                        "audit — the durable default); 'inline' runs "
                        "cells in-process, grid-style (shared caches, "
                        "fastest for small cells)")
    p.add_argument("--order", default=None,
                   choices=["grouped", "spec", "shuffled"],
                   help="cell ordering (default: the spec's; 'grouped' "
                        "= priority bands, HLO-signature groups "
                        "adjacent inside each; 'shuffled' is the "
                        "deterministic control arm)")
    p.add_argument("--run-dir", default=None,
                   help="campaign + run store root (default: the "
                        "spec base's run_dir, else 'runs')")
    p.add_argument("--cache-dir", default=None,
                   help="persistent compile-cache dir pinned onto "
                        "every cell (default: the ambient cache)")
    p.add_argument("--cache-budget-mb", default=0.0, type=float,
                   help="evict least-recently-used cache entries "
                        "between cells to stay under this many MB "
                        "(0 = unbounded; needs --cache-dir)")
    p.add_argument("--deadline", default=None, type=float,
                   metavar="SECS",
                   help="wall-clock budget for THIS invocation (a "
                        "time-boxed machine): past it the campaign "
                        "checkpoints cleanly and exits 75")
    p.add_argument("--max-retries", default=2, type=int,
                   help="per-cell supervisor retry budget")
    p.add_argument("--no-journal-runs", action="store_true",
                   help="inline executor only: run cells without "
                        "per-run journals/registry stamps")
    p.add_argument("--no-cost-report", action="store_true",
                   help="supervisor executor: do not force "
                        "--cost-report onto cells (drops the per-cell "
                        "compile/cache evidence)")
    p.add_argument("--dry-run", action="store_true",
                   help="print the validated, ordered plan and exit")
    args = p.parse_args(argv)

    from attacking_federate_learning_tpu.campaigns.scheduler import (
        Campaign
    )
    from attacking_federate_learning_tpu.campaigns.spec import (
        CampaignSpec
    )

    try:
        spec = CampaignSpec.load(args.spec)
    except (OSError, ValueError, TypeError) as e:
        print(f"campaign: bad spec {args.spec}: {e}")
        return 1
    camp = Campaign(spec, run_dir=args.run_dir,
                    executor=args.executor, order=args.order,
                    cache_dir=args.cache_dir,
                    cache_budget_mb=args.cache_budget_mb,
                    max_retries=args.max_retries,
                    deadline_s=args.deadline,
                    journal_runs=not args.no_journal_runs,
                    cost_report=not args.no_cost_report)
    try:
        cells = camp.plan()
    except ValueError as e:
        print(f"campaign: bad spec {args.spec}: {e}")
        return 1
    if args.dry_run:
        print(f"== campaign {spec.campaign_id}: {len(cells)} cells, "
              f"order={camp.order}, executor={camp.executor_name} ==")
        for i, c in enumerate(cells):
            state = camp.journal.state_of(c.cell_id)
            note = (f"SKIP: {c.skip}" if c.skip else state)
            print(f"  {i:3d}  [{c.group}] p{c.priority}  "
                  f"{c.cell_id}  {note}")
        return 0
    if args.executor == "inline":
        # Backend selection must precede the first jax op (cli.py
        # apply_backend; the supervisor children do this themselves).
        from attacking_federate_learning_tpu.cli import apply_backend
        apply_backend(str(spec.base.get("backend", "auto")))
    rc = camp.run()
    man = camp.journal.read_manifest() or {}
    counts = man.get("counts", {})
    print(f"[campaign] {spec.campaign_id}: {man.get('status', '?')}  "
          + "  ".join(f"{k}={v}" for k, v in sorted(counts.items()))
          + f"  cache={man.get('cache', {})}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
