"""Paper Figs. 5-10: learning curves, full participation vs random 20%.

Writes CSV curves per (dataset, model, method, participation) to
benchmarks/artifacts/curves/.
"""
from __future__ import annotations

import argparse
import csv
import os
import time

from repro.fl import FLConfig, run_simulation
from repro.launch.cache import enable_compile_cache

ART = os.path.join(os.path.dirname(__file__), "artifacts", "curves")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--n-per-class", type=int, default=300)
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--model", default="mlp")
    args = ap.parse_args()

    os.makedirs(ART, exist_ok=True)
    for participation, label in [(1.0, "full"), (0.2, "rand20")]:
        rows = {}
        for method in ["rbla", "zeropad", "fft"]:
            cfg = FLConfig(dataset=args.dataset, model=args.model,
                           method=method, rounds=args.rounds,
                           n_per_class=args.n_per_class,
                           n_test_per_class=100, local_epochs=2,
                           lr=0.05,
                           participation=participation, seed=42)
            t0 = time.time()
            hist = run_simulation(cfg)
            rows[method] = hist.test_acc
            print(f"curves/{args.dataset}/{args.model}/{method}/{label},"
                  f"{(time.time()-t0)*1e6/args.rounds:.0f},"
                  f"final={hist.test_acc[-1]:.4f}")
        path = os.path.join(
            ART, f"{args.dataset}_{args.model}_{label}.csv")
        with open(path, "w", newline="") as f:
            wr = csv.writer(f)
            wr.writerow(["round"] + list(rows))
            for i in range(args.rounds):
                wr.writerow([i + 1] + [f"{rows[m][i]:.4f}" for m in rows])


if __name__ == "__main__":
    main()
