"""CPU rehearsal of one cell: the whole run at a tiny size, no chip.

    python3 -m bench.rehearse --workload <cell> [--seed N] [--seconds S]
        [--trace 0|1] [--events-per-chip N]

Runs ``bench.run`` in this process with ``JAX_PLATFORMS=cpu`` (and as
many host devices as the cell asks chips), skipping only the look for a
TPU, with the traffic cut to ``--events-per-chip``.  It finds wrong
paths, arguments and control flow before a chip is spent.  Every number
it prints is a CPU number and is labelled ``cpu``: it is never a device
metric, and ``bench.run`` itself still refuses any platform but a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--events-per-chip", type=int, default=4)
    a = ap.parse_args(argv)
    from bench import run as br
    plan = br.cell_plan(a.workload)
    os.environ["JAX_PLATFORMS"] = "cpu"
    chips = int(plan["cell"]["chips"])
    if chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    plan["mix"] = dict(plan["mix"], events_per_chip=a.events_per_chip)
    res = br.run(a.workload, a.seed, a.seconds, bool(a.trace), plan=plan,
                 check_chips=False)
    res = {"rehearsal": "cpu", **res,
           "metrics": {k: dict(v, unit=f"{v['unit']} (cpu)")
                       for k, v in res["metrics"].items()}}
    print(json.dumps(res), flush=True)
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
