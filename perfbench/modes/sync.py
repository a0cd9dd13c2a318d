"""``sync``: closed-loop rounds through
``AggregationStrategy.aggregate_adapters`` on the Pallas backend, the
previous global donated, over a pool that is the cohort, drawn in a new
order with new weights each round; each global is the next round's
``prev_global``.  Before round ``k`` the upload in slot ``k % n`` is
replaced by a new one of the same rank: no cohort repeats, and the
program may drop what it kept of the last one.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import gen
import work
from tracing import phase

#: the loop family, which decides the metrics its cells report
FAMILY = "sync"

#: the traffic keys this mode reads; a traffic file with others is refused
KEYS = frozenset({"mode", "strategy", "codec", "cohort", "rank_mix",
                  "weights", "warmup_rounds", "sampled", "limits"})

#: the stream of the uploads that refresh the pool, one per round
REFRESH_STREAM = 2


class Loop:
    failed = 0

    def __init__(self, cell, seed: int, reference):
        import jax
        from repro.core import get_strategy
        self.cell, self.seed, self.ref, t = cell, seed, reference, cell.traffic
        self.layout = gen.program_layout(cell.config)
        self.n = int(t["cohort"])
        self.ranks = np.asarray(
            gen.pool_ranks(t, self.layout.r_max, self.n), np.int32)
        self.pool = gen.make_pool(self.layout, seed, self.ranks, t["codec"],
                                  stream=0)
        self.prev = gen.make_global(self.layout, seed)
        self.strategy = get_strategy(t["strategy"]).with_options()
        self.k = 0
        jax.block_until_ready((self.pool, self.prev))

    def warm(self):
        for _ in range(int(self.cell.traffic["warmup_rounds"])):
            self.step()

    def step(self):
        """One round; returns (latency ms, host ms, uploads)."""
        import jax
        t = self.cell.traffic
        with phase("generate"):
            slot = self.k % self.n
            self.pool[slot] = jax.block_until_ready(gen.make_upload(
                self.layout, self.seed, self.ranks[slot], t["codec"],
                REFRESH_STREAM, self.k))
            order, w = gen.round_draw(self.seed, self.k, self.n,
                                      t["weights"])
            uploads = [self.pool[i] for i in order]
            ranks = self.ranks[order]
        with phase("call"):
            t0 = time.perf_counter()
            out = self.strategy.aggregate_adapters(
                uploads, w, r_max=self.layout.r_max, client_ranks=ranks,
                prev_global=self.prev, backend="pallas", donate=True)
            t1 = time.perf_counter()
        with phase("block"):
            jax.block_until_ready(out)
            t2 = time.perf_counter()
        self.prev = out
        self.k += 1
        return (t2 - t0) * 1e3, (t1 - t0) * 1e3, self.n

    def last(self):
        """The round just finished: ``(index, its global)``."""
        return self.k - 1, self.prev

    def window_work(self, steps: int) -> dict:
        lay = self.layout
        w = work.round_work(lay.pairs.values(), lay.r_max,
                            self.ranks.tolist(), self.cell.traffic["codec"])
        return {k: v * steps for k, v in w.items()}

    def checks(self, before: dict, after: dict) -> dict:
        """No round of the window reused staged buffers."""
        return {"pack_reuses_in_window": (
            after["pack_reuses"] - before["pack_reuses"], 0)}

    def free(self):
        self.pool = self.prev = self.strategy = None

    def reference(self, answers, dtype):
        """``[(index, gap, rank leaves off)]`` for the kept answers, the
        reference computed from the seed alone in ``dtype``.

        Every round's cohort is the whole pool, so it holds the same
        ranks: the rows it owns are owned in every round, and the rows
        above its top rank were never owned and keep the first global
        through the chain of rounds.  So round ``k``'s reference is its
        RBLA over the first global, and no earlier round is needed."""
        t, lay = self.cell.traffic, self.layout
        first = compare.pairs_of(gen.make_global(lay, self.seed))
        out = []
        for k, got in answers:
            pool = self.pool_at(k)
            order, w = gen.round_draw(self.seed, k, self.n, t["weights"])
            want = self.ref.aggregate(
                [compare.pairs_of(pool[i]) for i in order], w,
                self.ranks[order], first, dtype)
            out.append((k, *compare.judge(got, want, lay.r_max)))
            del pool, want
        return out

    def pool_at(self, k: int) -> list:
        """The pool as round ``k`` saw it, made again from the seed: each
        slot holds its last refresh at or before ``k``, or its first
        upload."""
        codec, n = self.cell.traffic["codec"], self.n
        pool = []
        for s in range(n):
            j = k - (k - s) % n
            stream, index = (REFRESH_STREAM, j) if j >= 0 else (0, s)
            pool.append(gen.make_upload(self.layout, self.seed,
                                        self.ranks[s], codec, stream, index))
        return pool
