"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration, traffic mix, runner
and per-layer metric readers by name, runs it on the machine it is started
on, and prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``compared``: each number that decided
``correct`` beside its limit.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Exits non-zero and prints no result unless jax finds a TPU with the chips
the cell asks for.  ``--rehearse-cpu`` runs the same path tiny on a CPU-only
jax, to find faults before a chip call: it prints no metric at all and names
the CPU as its device.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny labelled run on a CPU-only jax; prints no metric")
    args = ap.parse_args(argv)

    from benchmark.harness import manifest as mf
    from benchmark.harness.device import NoChip
    from benchmark.harness.result import emit

    manifest = mf.load()
    bad = mf.problems(manifest)
    if bad:
        print("BENCHMARK.json: " + "; ".join(bad), file=sys.stderr)
        return 2
    cell = mf.Cell(manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        out = cell.runner().run(cell, args, T_START)
    except NoChip as e:
        print(f"benchmark: {e}: refusing to run", file=sys.stderr)
        return 2
    print("facts: " + json.dumps(out["facts"]), file=sys.stderr)
    emit(out["correct"], out["attempted"], out["failed"], out["metrics"], out["device"],
         out["compared"], out.get("breakdown"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
