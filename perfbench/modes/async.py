"""``async``: a backlog drained through ``AsyncAggregator.submit`` from a
ring of device-resident uploads, one fold per upload (``buffer_size=1``)
on the Pallas backend, each upload discounted by its staleness
polynomially, ``(1 + tau) ** -staleness_a``.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import gen
import work
from tracing import phase

#: the loop family, which decides the metrics its cells report
FAMILY = "async"

#: the traffic keys this mode reads; a traffic file with others is refused
KEYS = frozenset({"mode", "strategy", "codec", "ring", "rank_mix",
                  "weights", "staleness_a", "staleness_max", "warmup_folds",
                  "sampled", "limits"})


class Loop:
    def __init__(self, cell, seed: int, reference):
        import jax
        from repro.core import ServerState
        from repro.fl import AsyncAggregator
        self.cell, self.seed, self.ref, t = cell, seed, reference, cell.traffic
        self.layout = gen.program_layout(cell.config)
        self.ring = int(t["ring"])
        self.ranks = np.asarray(
            gen.pool_ranks(t, self.layout.r_max, self.ring), np.int32)
        self.pool = gen.make_pool(self.layout, seed, self.ranks, t["codec"],
                                  stream=0)
        state = gen.make_global(self.layout, seed)
        self.service = AsyncAggregator(
            t["strategy"],
            ServerState(adapters=state, base_trainable={},
                        r_max=self.layout.r_max),
            buffer_size=1, staleness="polynomial",
            staleness_a=float(t["staleness_a"]), backend="pallas")
        self.stream = gen.FoldStream(seed, self.ring, t["weights"],
                                     int(t["staleness_max"]))
        self.j = 0
        self.failed = 0
        self.folded_ranks: list = []
        jax.block_until_ready((self.pool, state))

    def warm(self):
        for _ in range(int(self.cell.traffic["warmup_folds"])):
            self.step()
        self.failed, self.folded_ranks = 0, []

    def step(self):
        """One fold; returns (latency ms, host ms, uploads)."""
        import jax
        from repro.core import ClientUpdate
        with phase("generate"):
            u, n_ex, tau = self.stream[self.j]
            upd = ClientUpdate(adapters=self.pool[u], base_trainable={},
                               n_examples=n_ex, rank=int(self.ranks[u]))
            version = self.service.version - tau
        with phase("call"):
            t0 = time.perf_counter()
            advanced = self.service.submit(upd, model_version=version)
            t1 = time.perf_counter()
        with phase("block"):
            jax.block_until_ready(self.service.state.adapters)
            t2 = time.perf_counter()
        self.failed += not advanced
        self.folded_ranks.append(int(self.ranks[u]))
        self.j += 1
        return (t2 - t0) * 1e3, (t1 - t0) * 1e3, 1

    def last(self):
        """The upload just folded: ``(index, the state after it)``."""
        return self.j - 1, self.service.state.adapters

    def window_work(self, steps: int) -> dict:
        """Work of the window's folds (their ranks differ), summed."""
        lay, codec = self.layout, self.cell.traffic["codec"]
        tot = {"bytes": 0, "flops": 0}
        for rank in self.folded_ranks[-steps:] if steps else []:
            w = work.fold_work(lay.pairs.values(), lay.r_max, rank, codec)
            tot["bytes"] += w["bytes"]
            tot["flops"] += w["flops"]
        return tot

    def checks(self, before: dict, after: dict) -> dict:
        """Every upload of the window was folded."""
        return {"rejected_uploads": (self.failed, 0)}

    def free(self):
        self.pool = self.service = None

    def reference(self, answers, dtype):
        """``[(index, gap, rank leaves off)]`` for the kept answers: the
        state after fold ``j`` is the reference's ``folded`` over the ring,
        each upload's mass the sum of its discounted weights up to ``j``."""
        t, lay = self.cell.traffic, self.layout
        pool = gen.make_pool(lay, self.seed, self.ranks, t["codec"],
                             stream=0)
        pool_pairs = [compare.pairs_of(c) for c in pool]
        first = compare.pairs_of(gen.make_global(lay, self.seed))
        a = float(t["staleness_a"])
        out = []
        for j, got in answers:
            mass = np.zeros(self.ring, np.float64)
            for i in range(j + 1):
                u, n_ex, tau = self.stream[i]
                mass[u] += n_ex * (1.0 + tau) ** -a
            want = self.ref.folded(pool_pairs, mass.astype(np.float32),
                                   self.ranks, first, dtype)
            out.append((j, *compare.judge(got, want, lay.r_max)))
        return out
