#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 perfbench/calibrate.py --workload <name> --seconds <s> \
        --seeds 101 102 ... [--out chiprun_out/calib.jsonl]

One process runs the cell once per seed, each with the window given,
and prints one JSON line per seed: the program's ``answer_gap`` (the
lower reading: the largest over sound runs), and the control's -- the
same reference computed in bfloat16, put in the program's place and
compared the same way (the upper reading: the smallest over seeds) --
beside the run's end-to-end metrics and checks.  The benchmark's own
runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    # the TPU runtime logs under /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parent / "src"))
    import harness
    out = open(args.out, "a") if args.out else None
    try:
        t_start = T0
        for seed in args.seeds:
            result, _, control = harness.run(
                args.workload, seed, args.seconds, False, t_start=t_start,
                control=True)
            line = json.dumps({
                "workload": args.workload, "seed": seed,
                "answer_gap": result["checks"]["answer_gap"]["value"],
                "control_gap": max(g for _, g, _ in control),
                "correct": result["correct"], "metrics": result["metrics"],
                "checks": result["checks"]})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
            gc.collect()
            t_start = time.perf_counter()
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
