# One function per paper table. Print ``name,us_per_call,derived`` CSV;
# ``--json PATH`` additionally writes the fabric sweep as machine-readable
# JSON (name, us_per_call, derived, engine tag, parsed metrics) — the
# ``BENCH_fabric.json`` artifact CI tracks PR-over-PR.
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main(argv=None) -> None:
    import jax

    from benchmarks import fabric_sweep, paper_benches, roofline
    from repro.core.network import ENGINES
    p = argparse.ArgumentParser()
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write the fabric sweep cells as JSON (e.g. "
                        "BENCH_fabric.json)")
    p.add_argument("--only", default=None, metavar="NAME",
                   help="run only the fabric bench family; values other "
                        "than 'fabric' additionally keep only cells "
                        "whose name contains NAME as a substring "
                        "(e.g. --only hotspot).  All fabric sweep "
                        "families still execute — use --tags to skip "
                        "whole families.  Errors if nothing matches.")
    p.add_argument("--tags", default=None, metavar="TAG[,TAG...]",
                   help="run only the fabric sweep families whose cells "
                        "carry one of these tags (e.g. 'adaptive' or "
                        "'mcast,hetero'); implies skipping the "
                        "paper/roofline families")
    p.add_argument("--engine", default=fabric_sweep.DEFAULT_ENGINE,
                   choices=sorted(ENGINES),
                   help="fabric event-transport engine")
    p.add_argument("--slow", action="store_true",
                   help="include the slow-lane fabric rows (N=32/64, 8x8)")
    args = p.parse_args(argv)

    fabric_only = args.only is not None or args.tags is not None
    rows = []
    if not fabric_only:
        for fn in paper_benches.ALL:
            rows.extend(fn())
    tag_sel = args.tags.split(",") if args.tags else None
    try:
        fabric_cells = fabric_sweep.run_structured(engine=args.engine,
                                                   slow=args.slow,
                                                   tags=tag_sel)
    except ValueError as e:   # unknown --tags: fail loudly, not empty
        p.error(str(e))
    if tag_sel is None and jax.default_backend() == "tpu":
        # the fabric roofline cells (both pallas kernels vs the chip's
        # memory-bandwidth bound) ride every untagged fabric sweep on a
        # TPU; elsewhere there is no chip bound to compare against
        fabric_cells.extend(roofline.fabric_roofline_cells())
    if args.only not in (None, "fabric"):
        all_names = [c["name"] for c in fabric_cells]
        fabric_cells = [c for c in fabric_cells if args.only in c["name"]]
        if not fabric_cells:
            # a typo must not silently produce an empty CSV/JSON
            p.error(f"--only {args.only!r} matched no fabric cells; "
                    f"available: {', '.join(all_names)}")
    rows.extend((c["name"], c["us_per_call"], c["derived"])
                for c in fabric_cells)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.json:
        import jaxlib
        with open(args.json, "w") as f:
            json.dump({"bench": "fabric_sweep", "engine": args.engine,
                       "slow_lane": args.slow,
                       "backend": jax.default_backend(),
                       "jax_version": jax.__version__,
                       "jaxlib_version": jaxlib.__version__,
                       "cells": fabric_cells},
                      f, indent=2)
        print(f"# wrote {len(fabric_cells)} fabric cells to {args.json}",
              file=sys.stderr)


if __name__ == '__main__':
    main()
