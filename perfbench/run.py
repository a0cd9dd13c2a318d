#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell (configuration, traffic mix,
metrics) is read from ``BENCHMARK.json`` and the files it names; inputs
are made from ``--seed``.  Set-up warms every shape, then steps run back
to back for ``--seconds``; ``--trace 1`` runs the same loop under the
profiler, for at most ``harness.TRACE_SECONDS``, and reports the
per-layer metrics instead of the end-to-end ones.  Afterwards the answers are compared with a plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: every compared number beside
its limit, which also end standard error.  Without a TPU, with fewer
chips than the cell asks for, or without the program's sources, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs under /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parent / "src"))
    try:
        import harness
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"perfbench: cannot import the program: {e}", file=sys.stderr)
        return 2
    try:
        result, lines, _ = harness.run(args.workload, args.seed,
                                       args.seconds, bool(args.trace),
                                       t_start=T_START)
    except harness.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
