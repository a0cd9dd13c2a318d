"""Compiled aggregation plans: packed cohort buffers, fused round launches.

Eager strategy execution walks the adapter pytree in Python and issues one
device computation (or one Pallas launch) per LoRA pair -- O(layers x
clients) host dispatch per FL round, the dominant server cost at scale.
This module turns a round into a **compiled plan**:

1. **Pack.**  All adapter pairs of a cohort are flattened into a small
   number of packed ``(n_clients, rows, width)`` buffers **bucketed by
   (row width, dtype)**.  A factors contribute their rank rows directly;
   B factors ride transposed so the rank axis leads everywhere.  Each
   packed row carries its owner metadata -- the delta_{i,r} rank-row mask
   column -- which is *static* given the cohort's rank multiset, so the
   whole (n, rows) owner-mask matrix is precomputed on the host once per
   plan.  Layer-stacked (leading-dim) pairs pack like everything else:
   layer ``l`` of a pair occupies its own row range with its own per-layer
   mask column, which is how the long-standing layer-stacked Pallas
   fallback disappears.
2. **Lower.**  The whole round -- leaf math, ``prev_global`` retention,
   the strategy's weight transform, finalize bookkeeping -- becomes a
   single jitted function issuing **one fused computation per bucket**
   (the ``packed_agg`` / ``packed_stack`` Pallas kernels on the pallas
   backend, a fused einsum on ref, one shard_map on distributed) instead
   of one launch per pair.  Server-state buffers can be **donated**.
3. **Cache.**  Plans are cached on the strategy instance keyed by the
   :class:`CohortSpec` -- tree structure, leaf shapes/dtypes, the rank
   multiset, backend, mesh -- the way ``make_distributed_aggregator``
   already caches per-mesh fns.  ``AggregationStrategy.plan(state, spec)``
   is the public entry; ``aggregate_adapters`` routes through it
   automatically and falls back to the per-leaf reference path only when
   the cohort cannot be described host-side (traced values, bare leaves).

The per-leaf ``aggregate_tree`` is the float32 reference: every packed
plan must reproduce it allclose (see ``tests/test_plan.py`` and the
parity/property suites, which exercise plans end to end), and it runs the
rounds a plan refuses.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import get_registry as _obs_registry
from repro.obs import host_syncs, span

from .aggregation import _EPS
from .compat import shard_map_no_check
from .masks import pad_to_rank

PyTree = Any


class PlanUnavailable(Exception):
    """A compiled plan cannot be built for these inputs (traced values,
    bare leaves, mismatched prev shapes); callers fall back to the
    per-leaf reference path, which handles everything."""


class BufferMemo:
    """Single-entry memo keyed by buffer *identity* over immutable jax
    arrays (the stack/pack reuse caches).

    The invariants both users need, kept in one place:

    * an id fingerprint is trustworthy only while every fingerprinted
      buffer is alive (a live weakref pins the id to its object);
    * mutable numpy uploads and tracers are never stored (in-place
      mutation / trace leakage would make identity lie);
    * the payload is released *eagerly* -- ``weakref.finalize`` on the
      fingerprinted buffers drops the entry the moment any of them is
      collected, so a memo never pins a dead cohort's bytes;
    * with ``require_repeat=True`` a payload is kept only for a
      fingerprint seen on consecutive stores: a loop whose cohorts
      never repeat retains a tuple of ids and weakrefs (bytes), not a
      cohort-sized payload, and no finalizers accumulate on long-lived
      buffers that are never actually reused.
    """

    def __init__(self, require_repeat: bool = False):
        self._require_repeat = require_repeat
        self._entry = None             # (ids, payload, refs, token)
        self._candidate = None         # (ids, refs): seen once

    @staticmethod
    def fingerprintable(leaves) -> bool:
        return all(isinstance(v, jax.Array)
                   and not isinstance(v, jax.core.Tracer) for v in leaves)

    def lookup(self, leaves):
        """The stored payload iff ``leaves`` are exactly the buffers it
        was stored under; None otherwise."""
        entry = self._entry
        if entry is None:
            return None
        ids, payload, refs, _ = entry
        if any(r() is None for r in refs):
            if self._entry is entry:   # stale: release without waiting
                self._entry = None
            return None
        if ids != tuple(id(v) for v in leaves):
            return None
        return payload

    def store(self, leaves, payload) -> None:
        import weakref
        leaves = list(leaves)
        if not leaves or not self.fingerprintable(leaves):
            return
        ids = tuple(id(v) for v in leaves)
        if self._require_repeat:
            cand = self._candidate
            seen_before = (cand is not None and cand[0] == ids
                           and all(r() is not None for r in cand[1]))
            if not seen_before:        # first sight: fingerprint only
                self._candidate = (ids,
                                   [weakref.ref(v) for v in leaves])
                return
        token = object()
        self._entry = (ids, payload,
                       [weakref.ref(v) for v in leaves], token)
        wself = weakref.ref(self)

        def _release(wself=wself, token=token):
            m = wself()                # holds only the token: a newer
            if (m is not None and m._entry is not None
                    and m._entry[3] is token):
                m._entry = None        # entry is never clobbered
        for v in leaves:               # ANY buffer dying releases it
            weakref.finalize(v, _release)


class DispatchCounter:
    """Counts host->device computation dispatches issued by the tracked
    entry points: every Pallas kernel wrapper call (``repro.kernels``)
    and every :class:`CompiledRound` execution.  The aggregation
    benchmarks read this to report dispatches per round, in windows of
    their own: ``count`` since the last ``reset()``.  Process totals
    per kernel are the registry's ``kernel_dispatches_total``.
    """

    def __init__(self):
        self.count = 0

    def inc(self, n: int = 1) -> None:
        self.count += n

    def reset(self) -> int:
        prev, self.count = self.count, 0
        return prev


dispatch_counter = DispatchCounter()

_SYNCS_COHORT = host_syncs("cohort_spec")
_SYNCS_STATE = host_syncs("state_spec")
_PACK_RUNS = _obs_registry().counter(
    "plan_pack_runs_total", "packed-bucket builds, by strategy",
    labelnames=("strategy",))
_PACK_REUSES = _obs_registry().counter(
    "plan_pack_reuses_total",
    "packed-bucket memo reuses (same cohort buffers), by strategy",
    labelnames=("strategy",))


def default_client_mesh(n_clients: int, client_axis: str):
    """1-D client mesh over the largest device count dividing
    ``n_clients`` (every shard carries the same number of clients) --
    the shared default for every distributed aggregation path."""
    from jax.sharding import Mesh
    devs = jax.devices()
    k = max(i for i in range(1, len(devs) + 1) if n_clients % i == 0)
    return Mesh(np.asarray(devs[:k]), (client_axis,))


# ------------------------------------------------------------- cohort spec --
def _is_pair(node) -> bool:
    return (isinstance(node, Mapping) and "A" in node and "B" in node
            and "rank" in node)


def _walk_pairs(tree, path=()):
    """Yield ``(path, pair)`` for every LoRA pair; raise
    :class:`PlanUnavailable` on bare array leaves (plans pack whole
    pairs; generic leaf trees stay on the reference path)."""
    if _is_pair(tree):
        yield path, tree
        return
    if isinstance(tree, Mapping):
        for k, v in tree.items():
            yield from _walk_pairs(v, path + (k,))
        return
    if isinstance(tree, (tuple, list)):
        for i, v in enumerate(tree):
            yield from _walk_pairs(v, path + (i,))
        return
    if tree is None:
        return
    raise PlanUnavailable(
        f"bare leaf of type {type(tree).__name__} at {path}; plans pack "
        "whole LoRA pairs")


def _read_ranks(ranks: Sequence, prevs: Sequence, client_ranks):
    """A walk's rank values on the host, in ONE ``jax.device_get`` over
    all of them: the walk waits on the device once, not once per rank
    leaf.  ``ranks`` are ``(what, leaf)`` pairs, ``prevs`` the previous
    global's ``(path, pair)`` pairs, ``client_ranks`` may be None.  Each
    ``jax.Array`` among them counts as one read in
    ``host_syncs_total{site="cohort_spec"}``; a numpy or Python value is
    no read.  Returns ``(rank arrays, prev rank arrays, client ranks
    tuple or None)``."""
    named = list(ranks) + [(f"prev rank leaf at {path}", pp["rank"])
                           for path, pp in prevs]
    if client_ranks is not None:
        named.append(("client_ranks", client_ranks))
    for what, x in named:
        if isinstance(x, jax.core.Tracer):
            raise PlanUnavailable(f"{what} is traced; plans are host-built")
    _SYNCS_COHORT.inc(sum(isinstance(x, jax.Array) for _, x in named))
    got = [np.asarray(v) for v in jax.device_get([x for _, x in named])]
    if client_ranks is not None:
        client_ranks = _rank_tuple(got.pop())
    return got[:len(ranks)], got[len(ranks):], client_ranks


def _rank_tuple(x: np.ndarray) -> tuple:
    return tuple(int(v) for v in x.ravel())


@dataclasses.dataclass(frozen=True)
class PairMeta:
    """Static description of one stacked LoRA pair in a cohort."""
    path: tuple
    a_shape: tuple
    a_dtype: str
    b_shape: tuple
    b_dtype: str
    rank_shape: tuple          # stacked rank leaf shape, incl. client axis
    ranks: tuple               # flattened concrete stacked rank values
    prev_a_shape: tuple | None = None
    prev_b_shape: tuple | None = None
    prev_rank_shape: tuple | None = None
    prev_ranks: tuple | None = None

    def rank_values(self) -> np.ndarray:
        return np.asarray(self.ranks, np.int64).reshape(self.rank_shape)

    def prev_rank_values(self) -> np.ndarray | None:
        if self.prev_ranks is None:
            return None
        return np.asarray(self.prev_ranks,
                          np.int64).reshape(self.prev_rank_shape)


@dataclasses.dataclass(frozen=True)
class CohortSpec:
    """Hashable plan-cache key: everything a compiled round closes over.

    Two cohorts with the same spec share one compiled plan; a new rank
    multiset, tree structure, backend, mesh, or prev layout builds (and
    caches) a new one.
    """
    n_clients: int
    kind: str                       # resolved backend: ref|pallas|distributed
    r_max: int | None
    pairs: tuple[PairMeta, ...]
    client_ranks: tuple | None
    has_prev: bool
    interpret: bool | None = None
    mesh: Any = None
    client_axis: str = "clients"
    #: per-client upload codec names ("none"|"bf16"|"int8") for a cohort
    #: planned from per-client trees (see repro.core.codec; a plain
    #: cohort is all "none"); None = a stacked cohort.
    #: Part of the key: a codec-mix change re-plans (and re-traces the
    #: executor), a rank-multiset repeat under the same mix still hits.
    codecs: tuple | None = None

    def client_ranks_array(self):
        if self.client_ranks is None:
            return None
        return jnp.asarray(self.client_ranks, jnp.int32)


def _prev_pairs(prev_tree, paths) -> list:
    """``(path, pair)`` of ``prev_tree`` at each of ``paths`` (empty
    without one)."""
    if prev_tree is None:
        return []
    have = dict(_walk_pairs(prev_tree))
    for path in paths:
        if path not in have:
            raise PlanUnavailable(f"prev tree missing pair at {path}")
    return [(path, have[path]) for path in paths]


def _with_prev(meta: dict, pp, prk: np.ndarray) -> None:
    meta.update(prev_a_shape=tuple(pp["A"].shape),
                prev_b_shape=tuple(pp["B"].shape),
                prev_rank_shape=tuple(prk.shape), prev_ranks=_rank_tuple(prk))


def build_cohort_spec(stacked_tree: PyTree, *, kind: str,
                      r_max: int | None = None, client_ranks=None,
                      prev_tree: PyTree | None = None,
                      interpret: bool | None = None, mesh=None,
                      client_axis: str = "clients") -> CohortSpec:
    """Describe a stacked cohort host-side.  Raises
    :class:`PlanUnavailable` when the description needs values tracing
    hides (rank leaves, weights under jit) or the tree has bare leaves."""
    walked = list(_walk_pairs(stacked_tree))
    if not walked:
        raise PlanUnavailable("no LoRA pairs in the cohort tree")
    for path, pair in walked:
        if (isinstance(pair["A"], jax.core.Tracer)
                or isinstance(pair["B"], jax.core.Tracer)):
            raise PlanUnavailable("cohort leaves are traced")
        if pair["A"].ndim < 3 or pair["B"].ndim < 3:
            raise PlanUnavailable(
                f"pair at {path} is not stacked over clients")
    prevs = _prev_pairs(prev_tree, [path for path, _ in walked])
    rks, prks, client_ranks = _read_ranks(
        [(f"rank leaf at {path}", pair["rank"]) for path, pair in walked],
        prevs, client_ranks)
    pairs = []
    for i, (path, pair) in enumerate(walked):
        A, B, rk = pair["A"], pair["B"], rks[i]
        meta = dict(path=path, a_shape=tuple(A.shape), a_dtype=str(A.dtype),
                    b_shape=tuple(B.shape), b_dtype=str(B.dtype),
                    rank_shape=tuple(rk.shape), ranks=_rank_tuple(rk))
        if prevs:
            _with_prev(meta, prevs[i][1], prks[i])
        pairs.append(PairMeta(**meta))
    return CohortSpec(n_clients=int(walked[0][1]["A"].shape[0]), kind=kind,
                      r_max=r_max, pairs=tuple(pairs),
                      client_ranks=client_ranks,
                      has_prev=prev_tree is not None, interpret=interpret,
                      mesh=mesh if kind == "distributed" else None,
                      client_axis=client_axis)


def build_encoded_cohort_spec(client_trees: Sequence, codecs, *, kind: str,
                              r_max: int | None = None, client_ranks=None,
                              prev_tree: PyTree | None = None,
                              interpret: bool | None = None,
                              client_axis: str = "clients") -> CohortSpec:
    """Describe a cohort from its *per-client* adapter trees, never
    leafwise-stacked: plain uploads (codec ``"none"``) and encoded ones
    carrying wire dtypes (``repro.core.codec``) alike -- stacking int8
    next to fp32 would either fail or promote, and stacking at all makes
    a cohort-sized copy the packed buckets do not need.  ``codecs`` is
    the per-client codec-name tuple (``cohort_codecs``, or all
    ``"none"`` for a plain cohort).  Pair metadata records the
    **decoded** dtypes -- a wire dtype dequantizes to f32, a ``"none"``
    client's leaves keep their own, and the pair takes the dtype
    stacking would promote the clients' to -- so bucketing and unpacking
    match the stacked cohort's exactly and only ``spec.codecs``
    distinguishes the layout.  Every rank leaf of the walk (the
    previous global's and ``client_ranks`` included) is read in one
    :func:`_read_ranks`."""
    codecs = tuple(codecs)
    n = len(client_trees)
    if n == 0:
        raise PlanUnavailable("empty cohort")
    if len(codecs) != n:
        raise PlanUnavailable(f"{len(codecs)} codecs for {n} clients")
    if any(c not in ("none", "bf16", "int8") for c in codecs):
        raise PlanUnavailable(
            "per-pair mixed codecs inside one client are not plannable")
    walked = [list(_walk_pairs(t)) for t in client_trees]
    paths = [p for p, _ in walked[0]]
    if not paths:
        raise PlanUnavailable("no LoRA pairs in the cohort trees")
    for i, wl in enumerate(walked[1:], start=1):
        if [p for p, _ in wl] != paths:
            raise PlanUnavailable(
                f"client {i}'s tree structure differs from client 0's")
    prevs = _prev_pairs(prev_tree, paths)
    geometry = []
    for pi, path in enumerate(paths):
        cps = [wl[pi][1] for wl in walked]
        if any(isinstance(p[s], jax.core.Tracer) for p in cps for s in "AB"):
            raise PlanUnavailable("cohort leaves are traced")
        shapes = {(tuple(p["A"].shape), tuple(p["B"].shape)) for p in cps}
        if len(shapes) > 1:
            raise PlanUnavailable(
                f"clients disagree on pair shapes at {path}")
        (a_shape, b_shape), = shapes
        dtypes = [str(jnp.result_type(*{
            p[s].dtype if c == "none" else jnp.float32
            for p, c in zip(cps, codecs)})) for s in "AB"]
        geometry.append(((n,) + a_shape, (n,) + b_shape, dtypes))
    rks, prks, client_ranks = _read_ranks(
        [(f"rank leaf at {path}", wl[pi][1]["rank"])
         for pi, path in enumerate(paths) for wl in walked],
        prevs, client_ranks)
    pairs = []
    for pi, path in enumerate(paths):
        rk = np.stack(rks[pi * n:(pi + 1) * n])
        if client_ranks is None and rk.ndim == 1:
            # the first scalar-rank pair names each client's rank, as
            # for a stacked cohort (``strategy._infer_ranks``)
            client_ranks = _rank_tuple(rk)
        a_shape, b_shape, (a_dtype, b_dtype) = geometry[pi]
        meta = dict(path=path, a_shape=a_shape, a_dtype=a_dtype,
                    b_shape=b_shape, b_dtype=b_dtype,
                    rank_shape=tuple(rk.shape), ranks=_rank_tuple(rk))
        if prevs:
            _with_prev(meta, prevs[pi][1], prks[pi])
        pairs.append(PairMeta(**meta))
    return CohortSpec(n_clients=n, kind=kind, r_max=r_max,
                      pairs=tuple(pairs), client_ranks=client_ranks,
                      has_prev=prev_tree is not None, interpret=interpret,
                      mesh=None, client_axis=client_axis, codecs=codecs)


# ---------------------------------------------------------- packed layout --
@dataclasses.dataclass
class Slot:
    """One pair side's home inside a packed bucket."""
    pair_idx: int
    side: str                  # "A" | "B"
    lead: tuple                # leading (layer/expert) dims
    r_st: int                  # storage rank rows per lead index
    rows: int                  # prod(lead) * r_st
    width: int
    dtype: str
    offset: int = 0            # row offset inside the bucket


@dataclasses.dataclass
class Bucket:
    """All slots sharing (row width, dtype): one fused launch per round."""
    width: int
    dtype: str
    slots: list
    rows: int = 0
    mask: np.ndarray | None = None     # (n, rows) owner mask, host-built


def _side_geometry(meta: PairMeta, side: str):
    shape = meta.a_shape if side == "A" else meta.b_shape
    lead = tuple(shape[1:-2])
    if side == "A":
        r_st, width = shape[-2], shape[-1]
        dtype = meta.a_dtype
    else:
        r_st, width = shape[-1], shape[-2]
        dtype = meta.b_dtype
    rows = int(np.prod(lead, dtype=np.int64)) * r_st if lead else r_st
    return lead, int(r_st), int(rows), int(width), dtype


def _slot_mask(meta: PairMeta, slot: Slot, n: int,
               use_mask: bool) -> np.ndarray:
    """Per-row owner mask (n, rows): row (l, j) of client i is owned iff
    j < rank_i[l] -- the delta_{i,r} indicator in packed-row form."""
    if not use_mask:
        return np.ones((n, slot.rows), np.float32)
    rk = meta.rank_values()                      # (n, *rank_leaf_shape)
    mid = len(slot.lead) - (rk.ndim - 1)
    r = rk.reshape(rk.shape + (1,) * mid + (1,))
    m = np.arange(slot.r_st).reshape((1,) * (1 + len(slot.lead))
                                     + (slot.r_st,)) < r
    m = np.broadcast_to(m, (n,) + slot.lead + (slot.r_st,))
    return np.ascontiguousarray(
        m.reshape(n, slot.rows).astype(np.float32))


def _make_buckets(spec: CohortSpec, use_mask: bool) -> list:
    buckets: dict = {}
    for pi, meta in enumerate(spec.pairs):
        for side in ("A", "B"):
            lead, r_st, rows, width, dtype = _side_geometry(meta, side)
            key = (width, dtype)
            b = buckets.setdefault(key, Bucket(width=width, dtype=dtype,
                                               slots=[]))
            b.slots.append(Slot(pair_idx=pi, side=side, lead=lead,
                                r_st=r_st, rows=rows, width=width,
                                dtype=dtype, offset=b.rows))
            b.rows += rows
    out = list(buckets.values())
    for b in out:
        b.mask = np.concatenate(
            [_slot_mask(spec.pairs[s.pair_idx], s, spec.n_clients, use_mask)
             for s in b.slots], axis=1)
    return out


def pair_side_rows(x, side: str):
    """Rank-axis-leading row view of one LoRA pair side: A
    ``(..., r, fan_in)`` passes through, B ``(..., fan_out, r)`` rides
    transposed to ``(..., r, fan_out)`` -- THE packed row convention
    shared by plan buckets and the serving
    :class:`~repro.serving.AdapterStore`.  Involution: applying it twice
    (same side) restores the leaf layout."""
    if side == "B":
        x = jnp.swapaxes(x, -1, -2)
    return x


def _pack_side(x, slot: Slot):
    """(n, *lead, ...) leaf -> (n, rows, width) f32, rank axis leading."""
    x = pair_side_rows(x, slot.side)
    return x.reshape(x.shape[:1] + (slot.rows, slot.width)).astype(
        jnp.float32)


def _pack_prev_side(x, slot: Slot):
    """Like :func:`_pack_side` for an unstacked (server-state) leaf."""
    x = pair_side_rows(x, slot.side)
    return x.reshape((slot.rows, slot.width)).astype(jnp.float32)


def _unpack_slot(out, slot: Slot, meta: PairMeta):
    """(rows, width) f32 block -> the slot's original leaf layout."""
    y = out[slot.offset:slot.offset + slot.rows]
    y = y.reshape(slot.lead + (slot.r_st, slot.width))
    return pair_side_rows(y, slot.side).astype(slot.dtype)


# ------------------------------------------------------- tree (re)building --
def _make_rebuilder(tree) -> Callable:
    """Recipe to rebuild ``tree``'s container structure from a flat list
    of per-pair replacements (in :func:`_walk_pairs` order)."""
    counter = [0]

    def recipe(t):
        if _is_pair(t):
            i = counter[0]
            counter[0] += 1
            return ("pair", i)
        if isinstance(t, Mapping):
            return ("map", {k: recipe(v) for k, v in t.items()})
        if isinstance(t, (tuple, list)):
            return ("seq", type(t), [recipe(v) for v in t])
        return ("leaf", t)

    r = recipe(tree)

    def rebuild(pairs: Sequence):
        def go(node):
            tag = node[0]
            if tag == "pair":
                return pairs[node[1]]
            if tag == "map":
                return {k: go(v) for k, v in node[1].items()}
            if tag == "seq":
                return node[1](go(v) for v in node[2])
            return node[1]
        return go(r)
    return rebuild


def _ab_list(tree) -> list:
    return [{"A": p["A"], "B": p["B"]} for _, p in _walk_pairs(tree)]


# ------------------------------------------------------------ the product --
class CompiledRound:
    """One compiled aggregation round for a fixed :class:`CohortSpec`.

    ``__call__(stacked_tree, weights, prev_tree, donate=False)`` runs the
    round; with ``donate=True`` the previous global's A/B buffers are
    donated to XLA (the caller must not touch them afterwards -- jax
    raises on any use of a donated buffer).

    Attributes the benchmarks and tests read:

    ``kind``
        "packed" (fused buckets) or "eager" (the per-leaf reference
        math -- unknown strategies and paths with their own caching).
    ``n_kernel_launches``
        fused device computations issued per round (packed plans:
        #buckets; others: best-effort 1 / None).
    ``n_fallback_pairs``
        pairs a packed plan still routes through reference pair math
        (e.g. flora's over-cap SVD re-projection).
    ``n_pallas_launches``
        compiled Pallas kernels among those launches (0 on the ref and
        distributed backends and wherever a pallas plan runs the XLA
        lowering instead -- interpret mode, unaligned stack copies, mixed
        codec means; None where the plan cannot tell: eager rounds).
    ``input_shardings``
        ``(shape, sharding)`` of each packed client buffer the last call
        handed to its collective (distributed plans; None elsewhere) --
        where each chip's share of the cohort actually sat.
    ``pack_memo``
        the :class:`BufferMemo` of packed buckets a re-participating
        cohort reuses (mean plans; None elsewhere).
    """

    def __init__(self, strategy, spec: CohortSpec, kind: str,
                 execute: Callable, *, n_kernel_launches: int | None = None,
                 n_fallback_pairs: int = 0,
                 n_pallas_launches: int | None = None,
                 pack_memo: BufferMemo | None = None):
        self.strategy = strategy
        self.spec = spec
        self.kind = kind
        self._execute = execute
        self.n_kernel_launches = n_kernel_launches
        self.n_fallback_pairs = n_fallback_pairs
        self.n_pallas_launches = n_pallas_launches
        self.input_shardings = None
        self.pack_memo = pack_memo
        self.n_calls = 0

    def __call__(self, stacked_tree: PyTree, weights, prev_tree=None,
                 donate: bool = False) -> PyTree:
        dispatch_counter.inc()
        self.n_calls += 1
        return self._execute(stacked_tree, jnp.asarray(weights, jnp.float32),
                             prev_tree, donate)

    def describe(self) -> str:
        return (f"CompiledRound({self.strategy.name}/{self.spec.kind}, "
                f"kind={self.kind}, launches={self.n_kernel_launches}, "
                f"pallas={self.n_pallas_launches}, "
                f"fallback_pairs={self.n_fallback_pairs})")


def _out_rank_leaves(spec: CohortSpec, r_out_per_pair=None):
    """Finalized rank leaves, host-built: fixed-rank plans write r_max
    (or the storage rank) directly; stack plans write each pair's static
    output rank."""
    leaves = []
    for i, meta in enumerate(spec.pairs):
        shape = tuple(meta.rank_shape[1:])
        if r_out_per_pair is not None:
            val = int(r_out_per_pair[i])
        else:
            val = int(spec.r_max if spec.r_max is not None
                      else meta.a_shape[-2])
        leaves.append(jnp.full(shape, val, jnp.int32))
    return leaves


# ------------------------------------------------------ packed mean plans --
def _bucket_mean_ref(x, mask_const, wt, prev, norm_by: str,
                     norm_restore: bool, scales=None):
    """Fused reference math for one bucket: the packed-row form of
    rbla/zeropad/fedavg leaf math (+ rbla_norm's per-row norm restore).
    ``scales`` (n, rows) dequantizes int8 payloads on the fly (the scale
    folds into the value einsum; the owner-mass denominator is
    scale-free)."""
    m = mask_const
    x = x.astype(jnp.float32)
    if scales is not None:
        x = scales[:, :, None] * x
    num = jnp.einsum("n,nr,nrd->rd", wt, m, x)
    if norm_by == "mask":
        den = jnp.einsum("n,nr->r", wt, m)[:, None]
        fb = prev if prev is not None else jnp.zeros_like(num)
        out = jnp.where(den > 0, num / (den + _EPS), fb)
    else:
        out = num / (jnp.sum(wt) + _EPS)
    if norm_restore:
        xm = m[:, :, None] * x
        row_norms = jnp.sqrt(jnp.einsum("nrd,nrd->nr", xm, xm))
        w_rows = (m > 0).astype(jnp.float32) * wt[:, None]
        target = (jnp.sum(w_rows * row_norms, axis=0)
                  / (jnp.sum(w_rows, axis=0) + _EPS))
        agg_norms = jnp.sqrt(jnp.sum(out ** 2, axis=1))
        scale = jnp.where(agg_norms > _EPS, target / (agg_norms + _EPS),
                          1.0)
        out = out * scale[:, None]
    return out


def _shape_key(spec: CohortSpec) -> tuple:
    """Everything a mean-mode *executor* (the jitted function) depends
    on: shapes, dtypes, backend, prev presence -- but NOT the rank
    multiset.  Owner masks and client ranks enter as runtime data, so
    one compiled executor serves every cohort with this layout and a new
    rank multiset costs a new (cheap) plan, not a new XLA compile.  The
    codec mix IS part of the key: wire dtypes and the group split change
    the traced computation."""
    return (spec.kind, spec.n_clients, spec.has_prev, spec.interpret,
            spec.mesh, spec.client_axis, spec.codecs,
            tuple((m.a_shape, m.a_dtype, m.b_shape, m.b_dtype)
                  for m in spec.pairs))


def _build_mean_round(strategy, spec: CohortSpec,
                      norm_restore: bool = False) -> CompiledRound:
    if spec.codecs is not None:
        return _build_encoded_mean_round(strategy, spec, norm_restore)
    buckets = _make_buckets(spec, strategy.use_mask)
    retains = strategy.retains_prev and spec.has_prev
    if retains:
        for meta in spec.pairs:       # mean plans overlay prev in place
            if (meta.prev_a_shape != meta.a_shape[1:]
                    or meta.prev_b_shape != meta.b_shape[1:]):
                raise PlanUnavailable(
                    "prev leaf shapes differ from the cohort's")
    cr = spec.client_ranks_array()
    norm_by = strategy.norm_by
    rank_leaves = _out_rank_leaves(spec)
    masks = [jnp.asarray(b.mask) for b in buckets]

    if spec.kind == "distributed":
        return _build_mean_distributed(strategy, spec, buckets, masks,
                                       rank_leaves, retains)

    # interpreted Pallas pays per-op Python overhead proportional to the
    # packed bucket's grid, so "one fused launch" *loses* to many small
    # compiled launches on CPU; route interpret-mode plans through the
    # fused XLA lowering and keep the true kernel for compiled backends
    from repro.kernels.runtime import auto_interpret
    use_kernel = (spec.kind == "pallas"
                  and not auto_interpret(spec.interpret))
    # robust reductions (trimmed/median/clipped) reuse the mean family's
    # packed buckets; the knobs are baked into the traced combine, so
    # they join the executor cache key
    robust = getattr(strategy, "robustness", "none")
    knobs = ((robust, float(getattr(strategy, "clip_norm", 0.0) or 0.0),
              float(getattr(strategy, "trim_frac", 0.0) or 0.0))
             if robust != "none" else ())

    exec_cache = strategy.__dict__.setdefault("_plan_exec_cache", {})
    key = ("mean", norm_restore, knobs, _shape_key(spec))
    fns = exec_cache.get(key)
    if fns is None:
        def pack_fn(ab):
            """Cohort uploads -> one packed (n, rows, width) buffer per
            bucket.  Split from the combine so a re-participating cohort
            (same upload buffers) reuses its packed buckets and only the
            combine re-runs -- the weight-only update."""
            xs = []
            for b in buckets:
                xs.append(jnp.concatenate(
                    [_pack_side(ab[s.pair_idx][s.side], s)
                     for s in b.slots],
                    axis=1) if len(b.slots) > 1 else _pack_side(
                        ab[b.slots[0].pair_idx][b.slots[0].side],
                        b.slots[0]))
            return xs

        def combine_fn(xs, wt_raw, prev_ab, ms, crv):
            wt = strategy.transform_weights(wt_raw, crv)
            outs = []
            for bi, b in enumerate(buckets):
                prev = None
                if retains:
                    parts = [_pack_prev_side(prev_ab[s.pair_idx][s.side],
                                             s) for s in b.slots]
                    prev = (jnp.concatenate(parts, axis=0)
                            if len(parts) > 1 else parts[0])
                if robust != "none":
                    if use_kernel:
                        from repro.kernels.rbla_agg.ops import (
                            packed_robust_inline)
                        out = packed_robust_inline(
                            xs[bi], ms[bi], wt, prev, mode=robust,
                            clip_norm=knobs[1], trim_frac=knobs[2],
                            interpret=spec.interpret)
                    elif (spec.kind == "pallas"
                          and robust in ("trimmed", "median")):
                        # interpret-mode order statistics: the fused
                        # odd-even network in plain XLA -- jnp.sort is a
                        # serial per-lane sort on CPU and the emulated
                        # kernel pays per-tile grid overhead
                        from repro.kernels.rbla_agg.ref import (
                            packed_robust_xla)
                        out = packed_robust_xla(
                            xs[bi], ms[bi], wt, prev, mode=robust,
                            clip_norm=knobs[1], trim_frac=knobs[2])
                    else:
                        from repro.kernels.rbla_agg.ref import (
                            packed_robust_ref)
                        out = packed_robust_ref(
                            xs[bi], ms[bi], wt, prev, mode=robust,
                            clip_norm=knobs[1], trim_frac=knobs[2])
                elif use_kernel:
                    from repro.kernels.rbla_agg.ops import packed_agg_inline
                    out = packed_agg_inline(xs[bi], ms[bi], wt, prev,
                                            norm_by=norm_by,
                                            norm_restore=norm_restore,
                                            interpret=spec.interpret)
                else:
                    out = _bucket_mean_ref(xs[bi], ms[bi], wt, prev,
                                           norm_by, norm_restore)
                outs.append(out)
            return [
                {s.side: _unpack_slot(outs[bi], s, spec.pairs[s.pair_idx])
                 for bi, b in enumerate(buckets) for s in b.slots
                 if s.pair_idx == pi}
                for pi in range(len(spec.pairs))]

        fns = (jax.jit(pack_fn), jax.jit(combine_fn),
               jax.jit(combine_fn, donate_argnums=(2,)))
        exec_cache[key] = fns
    pack, fn, fn_donate = fns
    rebuild = [None]
    # eager store is safe here: the fingerprinted buffers are the
    # *stacked* leaves, which outlive one call only when the strategy's
    # require_repeat stack memo decided the cohort repeats -- so for
    # fresh-per-round cohorts the packed payload is released at end of
    # round by the finalizers, and no finalizers accumulate on
    # long-lived user buffers (stacked leaves are new objects per round)
    pack_memo = BufferMemo()

    def execute(stacked_tree, w, prev_tree, donate):
        if rebuild[0] is None:
            rebuild[0] = _make_rebuilder(stacked_tree)
        ab = _ab_list(stacked_tree)
        stats = strategy.__dict__.setdefault(
            "plan_stats", {"hits": 0, "misses": 0})
        # same stacked buffers -> reuse the packed buckets; the memo
        # releases the packed payload as soon as the cohort's buffers
        # die (BufferMemo), so stale plans never pin cohort bytes
        leaves = [v for d in ab for v in (d["A"], d["B"])]
        with span("round.pack"):
            xs = pack_memo.lookup(leaves)
            if xs is not None:
                stats["pack_reuses"] = stats.get("pack_reuses", 0) + 1
                _PACK_REUSES.labels(strategy=strategy.name).inc()
            else:
                xs = pack(ab)
                pack_memo.store(leaves, xs)
                stats["pack_runs"] = stats.get("pack_runs", 0) + 1
                _PACK_RUNS.labels(strategy=strategy.name).inc()
        with span("round.combine"):
            prev_ab = _ab_list(prev_tree) if retains else None
            run = fn_donate if (donate and retains) else fn
            outs = run(xs, w, prev_ab, masks, cr)
            pairs = [{"A": o["A"], "B": o["B"], "rank": rank_leaves[i]}
                     for i, o in enumerate(outs)]
            return rebuild[0](pairs)

    return CompiledRound(strategy, spec, "packed", execute,
                         n_kernel_launches=len(buckets),
                         n_pallas_launches=len(buckets) if use_kernel else 0,
                         pack_memo=pack_memo)


# ------------------------------------ per-client (plain, encoded) plans --
def _enc_ab_list(tree) -> list:
    """Like :func:`_ab_list` but keeps the int8 codec's per-row scale
    leaves riding with each pair."""
    out = []
    for _, p in _walk_pairs(tree):
        d = {"A": p["A"], "B": p["B"]}
        for k in ("A_scale", "B_scale"):
            if k in p:
                d[k] = p[k]
        out.append(d)
    return out


def _pack_client_side(x, slot: Slot, wire: bool):
    """(*lead, ...) single-client leaf -> (rows, width); ``wire=True``
    keeps the upload's wire dtype (int8/bf16) so the packed payload never
    stages an fp32 copy."""
    x = pair_side_rows(x, slot.side)
    x = x.reshape((slot.rows, slot.width))
    return x if wire else x.astype(jnp.float32)


def _pack_client_scale(pair, slot: Slot):
    """Per-row dequant scales of one pair side -> (rows,) f32.  Both
    sides carry a ``(*lead, r)`` scale leaf on the packed row convention
    (B's packed rows are its columns), so the reshape is shared."""
    s = pair["A_scale" if slot.side == "A" else "B_scale"]
    return jnp.asarray(s, jnp.float32).reshape(slot.rows)


def _build_encoded_mean_round(strategy, spec: CohortSpec,
                              norm_restore: bool = False) -> CompiledRound:
    """Mean/robust packed round over a cohort of per-client trees (codecs
    from ``spec.codecs``; a plain cohort is one ``"none"`` group).

    Clients group by codec (static index tuples); one jitted ``pack_fn``
    packs each bucket's ``(n_g, rows, width)`` payload per group straight
    from the client leaves -- in the group's wire dtype, or f32 for
    ``"none"`` -- plus ``(n_g, rows)`` f32 scales for int8 groups; no
    stacked copy of the cohort is ever made.  A uniform-codec
    cohort keeps the one-fused-launch-per-bucket property -- the scales
    ride into ``packed_agg``/``packed_robust`` as runtime data and
    dequantization happens inside the kernel.  A mixed mean combines
    per-group partial sums (dequant folded into each group's value
    einsum); mixed *robust* rounds must dequantize-and-concatenate
    in-trace before the cross-group order statistics -- unavoidable, and
    still one jitted computation per round."""
    buckets = _make_buckets(spec, strategy.use_mask)
    retains = strategy.retains_prev and spec.has_prev
    if retains:
        for meta in spec.pairs:       # mean plans overlay prev in place
            if (meta.prev_a_shape != meta.a_shape[1:]
                    or meta.prev_b_shape != meta.b_shape[1:]):
                raise PlanUnavailable(
                    "prev leaf shapes differ from the cohort's")
    cr = spec.client_ranks_array()
    norm_by = strategy.norm_by
    rank_leaves = _out_rank_leaves(spec)

    # static codec groups, first-appearance order
    order: dict = {}
    for i, c in enumerate(spec.codecs):
        if c not in ("none", "bf16", "int8"):
            raise PlanUnavailable(f"client {i} uses unknown codec {c!r}")
        order.setdefault(c, []).append(i)
    groups = [(c, tuple(ix)) for c, ix in order.items()]
    # per-bucket per-group owner masks (host-sliced once per plan)
    masks = [[jnp.asarray(b.mask[list(ix)]) for _, ix in groups]
             for b in buckets]
    gidx = [jnp.asarray(ix, jnp.int32) for _, ix in groups]

    from repro.kernels.runtime import auto_interpret
    use_kernel = (spec.kind == "pallas"
                  and not auto_interpret(spec.interpret))
    robust = getattr(strategy, "robustness", "none")
    knobs = ((robust, float(getattr(strategy, "clip_norm", 0.0) or 0.0),
              float(getattr(strategy, "trim_frac", 0.0) or 0.0))
             if robust != "none" else ())

    def _robust_bucket(x, m, wt_g, prev):
        """Uniform-path robust dispatch on an already-grouped payload
        (scales=None: pass f32; else fused dequant)."""
        def run(fn, **kw):
            return fn(x[0], m, wt_g, prev, mode=robust, clip_norm=knobs[1],
                      trim_frac=knobs[2], scales=x[1],
                      out_dtype=jnp.float32, **kw)
        if use_kernel:
            from repro.kernels.rbla_agg.ops import packed_robust_inline
            return run(packed_robust_inline, interpret=spec.interpret)
        if spec.kind == "pallas" and robust in ("trimmed", "median"):
            from repro.kernels.rbla_agg.ref import packed_robust_xla
            return run(packed_robust_xla)
        from repro.kernels.rbla_agg.ref import packed_robust_ref
        return run(packed_robust_ref)

    exec_cache = strategy.__dict__.setdefault("_plan_exec_cache", {})
    key = ("mean", norm_restore, knobs, _shape_key(spec))
    fns = exec_cache.get(key)
    if fns is None:
        def pack_fn(clients):
            """Per-client uploads -> per-(bucket, group) wire-dtype
            payloads + int8 scale planes.  No fp32 staging: each group's
            (n_g, rows, width) buffer keeps the upload dtype."""
            xs, ss = [], []
            for b in buckets:
                bx, bs = [], []
                for cname, ix in groups:
                    per_client = []
                    per_scale = []
                    for i in ix:
                        parts = [_pack_client_side(
                            clients[i][s.pair_idx][s.side], s,
                            wire=cname != "none") for s in b.slots]
                        per_client.append(
                            jnp.concatenate(parts, axis=0)
                            if len(parts) > 1 else parts[0])
                        if cname == "int8":
                            sp = [_pack_client_scale(
                                clients[i][s.pair_idx], s)
                                for s in b.slots]
                            per_scale.append(jnp.concatenate(sp)
                                             if len(sp) > 1 else sp[0])
                    bx.append(jnp.stack(per_client))
                    bs.append(jnp.stack(per_scale) if per_scale else None)
                xs.append(bx)
                ss.append(bs)
            return xs, ss

        def combine_fn(xs, ss, wt_raw, prev_ab, ms, crv):
            wt = strategy.transform_weights(wt_raw, crv)
            wt_g = [wt[ix] for ix in gidx]
            outs = []
            for bi, b in enumerate(buckets):
                prev = None
                if retains:
                    parts = [_pack_prev_side(prev_ab[s.pair_idx][s.side],
                                             s) for s in b.slots]
                    prev = (jnp.concatenate(parts, axis=0)
                            if len(parts) > 1 else parts[0])
                if len(groups) == 1:
                    # uniform codec: one fused launch per bucket, scales
                    # as runtime data
                    if robust != "none":
                        out = _robust_bucket((xs[bi][0], ss[bi][0]),
                                             ms[bi][0], wt_g[0], prev)
                    elif use_kernel:
                        from repro.kernels.rbla_agg.ops import (
                            packed_agg_inline)
                        out = packed_agg_inline(
                            xs[bi][0], ms[bi][0], wt_g[0], prev,
                            norm_by=norm_by, norm_restore=norm_restore,
                            scales=ss[bi][0], out_dtype=jnp.float32,
                            interpret=spec.interpret)
                    else:
                        out = _bucket_mean_ref(xs[bi][0], ms[bi][0],
                                               wt_g[0], prev, norm_by,
                                               norm_restore,
                                               scales=ss[bi][0])
                elif robust != "none":
                    # cross-group order statistics need every client in
                    # one buffer: dequantize-and-concat in-trace
                    cat = []
                    for gi in range(len(groups)):
                        xg = xs[bi][gi].astype(jnp.float32)
                        if ss[bi][gi] is not None:
                            xg = ss[bi][gi][:, :, None] * xg
                        cat.append(xg)
                    out = _robust_bucket(
                        (jnp.concatenate(cat, axis=0), None),
                        jnp.concatenate(ms[bi], axis=0),
                        jnp.concatenate(wt_g), prev)
                else:
                    # mixed mean: per-group partial sums, dequant folded
                    # into each group's value einsum (scale rides on the
                    # (n, r) mask plane, never on the payload)
                    rows = b.rows
                    num = jnp.zeros((rows, xs[bi][0].shape[-1]),
                                    jnp.float32)
                    den = jnp.zeros((rows,), jnp.float32)
                    tnum = jnp.zeros((rows,), jnp.float32)
                    town = jnp.zeros((rows,), jnp.float32)
                    for gi in range(len(groups)):
                        xg = xs[bi][gi].astype(jnp.float32)
                        m = ms[bi][gi]
                        sg = ss[bi][gi]
                        mv = m if sg is None else m * sg
                        num = num + jnp.einsum("n,nr,nrd->rd", wt_g[gi],
                                               mv, xg)
                        den = den + jnp.einsum("n,nr->r", wt_g[gi], m)
                        if norm_restore:
                            xm = m[:, :, None] * xg
                            qn = jnp.sqrt(
                                jnp.einsum("nrd,nrd->nr", xm, xm))
                            rn = qn if sg is None else sg * qn
                            own = ((m > 0).astype(jnp.float32)
                                   * wt_g[gi][:, None])
                            tnum = tnum + jnp.sum(own * rn, axis=0)
                            town = town + jnp.sum(own, axis=0)
                    if norm_by == "mask":
                        fb = (prev if prev is not None
                              else jnp.zeros_like(num))
                        out = jnp.where(den[:, None] > 0,
                                        num / (den[:, None] + _EPS), fb)
                    else:
                        out = num / (jnp.sum(wt) + _EPS)
                    if norm_restore:
                        target = tnum / (town + _EPS)
                        agg = jnp.sqrt(jnp.sum(out ** 2, axis=1))
                        out = out * jnp.where(
                            agg > _EPS, target / (agg + _EPS), 1.0)[:, None]
                outs.append(out)
            return [
                {s.side: _unpack_slot(outs[bi], s, spec.pairs[s.pair_idx])
                 for bi, b in enumerate(buckets) for s in b.slots
                 if s.pair_idx == pi}
                for pi in range(len(spec.pairs))]

        fns = (jax.jit(pack_fn), jax.jit(combine_fn),
               jax.jit(combine_fn, donate_argnums=(3,)))
        exec_cache[key] = fns
    pack, fn, fn_donate = fns
    rebuild = [None]
    # require_repeat: the fingerprinted buffers are the uploads
    # themselves, which may live for many rounds (a pool the cohort is
    # drawn from) -- a cohort that does not repeat leaves a fingerprint,
    # not a cohort-sized payload or a finalizer on every upload
    pack_memo = BufferMemo(require_repeat=True)

    def execute(client_trees, w, prev_tree, donate):
        if rebuild[0] is None:
            rebuild[0] = _make_rebuilder(client_trees[0])
        clients = [_enc_ab_list(t) for t in client_trees]
        stats = strategy.__dict__.setdefault(
            "plan_stats", {"hits": 0, "misses": 0})
        leaves = [v for ab in clients for d in ab for v in d.values()]
        with span("round.pack"):
            packed = pack_memo.lookup(leaves)
            if packed is not None:
                stats["pack_reuses"] = stats.get("pack_reuses", 0) + 1
                _PACK_REUSES.labels(strategy=strategy.name).inc()
            else:
                packed = pack(clients)
                pack_memo.store(leaves, packed)
                stats["pack_runs"] = stats.get("pack_runs", 0) + 1
                _PACK_RUNS.labels(strategy=strategy.name).inc()
        with span("round.combine"):
            xs, ss = packed
            prev_ab = _ab_list(prev_tree) if retains else None
            run = fn_donate if (donate and retains) else fn
            outs = run(xs, ss, w, prev_ab, masks, cr)
            pairs = [{"A": o["A"], "B": o["B"], "rank": rank_leaves[i]}
                     for i, o in enumerate(outs)]
            return rebuild[0](pairs)

    # a mixed-codec mean combines per-group partial sums in XLA
    kernel_buckets = (len(buckets) if use_kernel
                      and (len(groups) == 1 or robust != "none") else 0)
    return CompiledRound(strategy, spec, "packed", execute,
                         n_kernel_launches=len(buckets),
                         n_pallas_launches=kernel_buckets,
                         pack_memo=pack_memo)


def _build_mean_distributed(strategy, spec, buckets, masks_const,
                            rank_leaves, retains) -> CompiledRound:
    """Packed shard_map: one collective round over the bucket buffers
    (clients sharded over the mesh axis, masks ride along sharded, the
    combine + prev retention computed replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = spec.n_clients
    mesh = spec.mesh
    ax = spec.client_axis
    if mesh is None:
        mesh = default_client_mesh(n, ax)
    cr = spec.client_ranks_array()
    norm_by = strategy.norm_by
    nb = len(buckets)

    exec_cache = strategy.__dict__.setdefault("_plan_exec_cache", {})
    key = ("mean_dist", _shape_key(spec))
    shard_fn = exec_cache.get(key)
    if shard_fn is None:
        def body(xs, ms, wt, prevs):
            outs = []
            for bi in range(nb):
                x, m = xs[bi], ms[bi]
                num = jax.lax.psum(jnp.einsum("n,nr,nrd->rd", wt, m, x),
                                   ax)
                if norm_by == "mask":
                    den = jax.lax.psum(jnp.einsum("n,nr->r", wt, m),
                                       ax)[:, None]
                    fb = prevs[bi] if retains else jnp.zeros_like(num)
                    outs.append(jnp.where(den > 0, num / (den + _EPS), fb))
                else:
                    den = jax.lax.psum(jnp.sum(wt), ax)
                    outs.append(num / (den + _EPS))
            return outs

        shard_fn = jax.jit(shard_map_no_check(
            body, mesh,
            in_specs=([P(ax)] * nb, [P(ax)] * nb, P(ax),
                      [P()] * nb if retains else []),
            out_specs=[P()] * nb))
        exec_cache[key] = shard_fn

    def round_fn(ab, wt_raw, prev_ab):
        wt = strategy.transform_weights(wt_raw, cr)
        xs = []
        for b in buckets:
            parts = [_pack_side(ab[s.pair_idx][s.side], s) for s in b.slots]
            xs.append(jnp.concatenate(parts, axis=1)
                      if len(parts) > 1 else parts[0])
        prevs = []
        if retains:
            for b in buckets:
                parts = [_pack_prev_side(prev_ab[s.pair_idx][s.side], s)
                         for s in b.slots]
                prevs.append(jnp.concatenate(parts, axis=0)
                             if len(parts) > 1 else parts[0])
        if not any(isinstance(x, jax.core.Tracer) for x in xs):
            rd.input_shardings = [(x.shape, x.sharding) for x in xs]
        outs = shard_fn(xs, masks_const, wt, prevs)
        return [
            {s.side: _unpack_slot(outs[bi], s, spec.pairs[s.pair_idx])
             for bi, b in enumerate(buckets) for s in b.slots
             if s.pair_idx == pi}
            for pi in range(len(spec.pairs))]

    rebuild = [None]

    by_client = NamedSharding(mesh, P(ax))

    def execute(stacked_tree, w, prev_tree, donate):
        if rebuild[0] is None:
            rebuild[0] = _make_rebuilder(stacked_tree)
        # each chip receives only its clients' rows before the packing,
        # so no chip ever holds the whole packed cohort
        ab = jax.device_put(_ab_list(stacked_tree), by_client)
        prev_ab = _ab_list(prev_tree) if retains else None
        outs = round_fn(ab, w, prev_ab)
        pairs = [{"A": o["A"], "B": o["B"], "rank": rank_leaves[i]}
                 for i, o in enumerate(outs)]
        return rebuild[0](pairs)

    rd = CompiledRound(strategy, spec, "packed", execute,
                       n_kernel_launches=len(buckets), n_pallas_launches=0)
    return rd


# ----------------------------------------------------- packed stack plans --
def _build_stack_round(strategy, spec: CohortSpec) -> CompiledRound:
    """flora's packed plan (ref + pallas): the whole stacking round is
    copies/scales at static offsets, fused into one ``packed_stack``
    launch (or one XLA slice-update chain) per bucket.  Pairs whose
    stacked rank exceeds the cap fall back to the reference pair math
    (SVD re-projection) inside the same jitted round."""
    n = spec.n_clients

    # ---- static per-pair stacking geometry ------------------------------
    plans = []                       # one entry per pair
    for meta in spec.pairs:
        ranks = meta.rank_values()
        if ranks.ndim > 1:           # layer-stacked: flora needs uniform
            flat = ranks.reshape(n, -1)
            if not np.all(flat == flat[:, :1]):
                raise PlanUnavailable(
                    "flora packs layer-stacked pairs only with uniform "
                    "per-client ranks")
            ranks = flat[:, 0]
        ranks = ranks.reshape(-1).astype(np.int64)
        lead_a, r_st_a, _, _, _ = _side_geometry(meta, "A")
        cap = strategy.resolve_cap(spec.r_max, r_storage=r_st_a)
        strategy._validate_cap(cap, ranks, spec.r_max)
        prev_rank = 0
        prev_r_st = 0
        if spec.has_prev and meta.prev_ranks is not None:
            prev_rank = int(np.max(meta.prev_rank_values()))
            prev_r_st = int(meta.prev_a_shape[-2])
        live = [i for i in range(n) if int(ranks[i]) > 0]
        seg_ranks = ([prev_rank] if prev_rank else []) \
            + [int(ranks[i]) for i in live]
        r_total = int(sum(seg_ranks))
        plans.append(dict(ranks=ranks, cap=cap, prev_rank=prev_rank,
                          prev_r_st=prev_r_st, live=live,
                          seg_ranks=seg_ranks, r_total=r_total,
                          packable=r_total <= cap))

    def _capped_r_out(p, meta):
        # mirrors _stack_pair's over-cap branch exactly
        base = (spec.r_max if spec.r_max is not None
                else meta.a_shape[-2])
        return min(int(base), p["cap"])

    rank_leaves = _out_rank_leaves(
        spec, [p["r_total"] if p["packable"] else _capped_r_out(p, m)
               for p, m in zip(plans, spec.pairs)])

    # ---- bucket the packable pairs; out layout = lead x cap per slot ----
    buckets: dict = {}
    for pi, meta in enumerate(spec.pairs):
        if not plans[pi]["packable"]:
            continue
        for side in ("A", "B"):
            lead, r_st, rows, width, dtype = _side_geometry(meta, side)
            key = (width, dtype)
            b = buckets.setdefault(
                key, Bucket(width=width, dtype=dtype, slots=[]))
            b.slots.append(Slot(pair_idx=pi, side=side, lead=lead,
                                r_st=r_st, rows=rows, width=width,
                                dtype=dtype))
    buckets = list(buckets.values())

    # scale vector layout: entry 0 is the constant 1.0 (A rows pass
    # verbatim); then one entry per (packable pair, segment) for B
    scale_slots: list = []           # (pair_idx, seg_index) in vector order
    for pi, p in enumerate(plans):
        if p["packable"]:
            for j in range(len(p["seg_ranks"])):
                scale_slots.append((pi, j))
    scale_index = {ps: 1 + k for k, ps in enumerate(scale_slots)}

    bucket_meta = []
    for b in buckets:
        in_off = 0
        prev_off = 0
        out_off = 0
        copies_x: list = []
        copies_prev: list = []
        for s in b.slots:
            p = plans[s.pair_idx]
            nlayers = int(np.prod(s.lead, dtype=np.int64)) if s.lead else 1
            cap = p["cap"]
            prev_r_st = p["prev_r_st"]
            for l in range(nlayers):
                dst = out_off + l * cap
                seg = 0
                if p["prev_rank"]:
                    si = (scale_index[(s.pair_idx, seg)]
                          if s.side == "B" else 0)
                    copies_prev.append((prev_off + l * prev_r_st, dst,
                                        p["prev_rank"], si))
                    dst += p["prev_rank"]
                    seg += 1
                for i in p["live"]:
                    r_i = int(p["ranks"][i])
                    si = (scale_index[(s.pair_idx, seg)]
                          if s.side == "B" else 0)
                    copies_x.append((i, in_off + l * s.r_st, dst, r_i, si))
                    dst += r_i
                    seg += 1
            s.offset = out_off
            out_off += nlayers * cap
            in_off += s.rows
            prev_off += nlayers * prev_r_st
        bucket_meta.append(dict(out_rows=out_off,
                                copies_x=tuple(copies_x),
                                copies_prev=tuple(copies_prev)))

    fallback = [pi for pi, p in enumerate(plans) if not p["packable"]]
    n_scales = 1 + len(scale_slots)

    # interpreted Pallas pays per-op Python overhead on every static copy,
    # so the fused stacking loses to XLA there; the copies are static
    # slices either way, so the ref lowering is just as fused (and is the
    # only lowering the "ref" backend may use).  The compiled kernel moves
    # whole row tiles, so a bucket with a copy off the tiling stacks in
    # XLA too.  _pack_side packs every bucket in f32, whatever the
    # cohort's dtype, so f32's 8-row tiling is the one that applies.
    from repro.kernels.rbla_agg.ops import packed_stack_compiles
    from repro.kernels.runtime import auto_interpret
    use_kernel = [spec.kind == "pallas"
                  and not auto_interpret(spec.interpret)
                  and packed_stack_compiles(
                      m["copies_x"], m["copies_prev"],
                      r_in=sum(s.rows for s in b.slots),
                      out_rows=m["out_rows"], d=b.width, dtype=jnp.float32)
                  for b, m in zip(buckets, bucket_meta)]

    def round_fn(ab, wt_raw, prev_ab):
        wt = wt_raw
        mean_w = jnp.mean(wt)
        # per-(pair, segment) B-column scales: mhat_i * r_out / r_i
        scales = [jnp.float32(1.0)]
        for pi, p in enumerate(plans):
            if not p["packable"]:
                continue
            masses = []
            if p["prev_rank"]:
                masses.append(strategy.prev_weight * mean_w)
            masses.extend(wt[i] for i in p["live"])
            m = jnp.stack(masses)
            mhat = m / (jnp.sum(m) + _EPS)
            r_out = jnp.float32(p["r_total"])
            for j, rj in enumerate(p["seg_ranks"]):
                scales.append(mhat[j] * r_out / jnp.float32(rj))
        scales = jnp.stack(scales)
        assert scales.shape[0] == n_scales

        outs = []
        for bi, b in enumerate(buckets):
            from repro.kernels.rbla_agg.ops import (packed_stack_inline,
                                                    packed_stack_ref)
            x = jnp.concatenate(
                [_pack_side(ab[s.pair_idx][s.side], s) for s in b.slots],
                axis=1) if len(b.slots) > 1 else _pack_side(
                    ab[b.slots[0].pair_idx][b.slots[0].side], b.slots[0])
            prev = None
            if bucket_meta[bi]["copies_prev"]:
                parts = []
                for s in b.slots:
                    p = plans[s.pair_idx]
                    if p["prev_r_st"]:
                        parts.append(_pack_prev_side(
                            prev_ab[s.pair_idx][s.side],
                            dataclasses.replace(
                                s, r_st=p["prev_r_st"],
                                rows=(s.rows // s.r_st) * p["prev_r_st"])))
                prev = (jnp.concatenate(parts, axis=0)
                        if len(parts) > 1 else parts[0])
            stack = (functools.partial(packed_stack_inline,
                                       interpret=spec.interpret)
                     if use_kernel[bi] else packed_stack_ref)
            outs.append(stack(
                x, scales, prev,
                copies_x=bucket_meta[bi]["copies_x"],
                copies_prev=bucket_meta[bi]["copies_prev"],
                out_rows=bucket_meta[bi]["out_rows"]))

        results: dict = {}
        for bi, b in enumerate(buckets):
            for s in b.slots:
                cap = plans[s.pair_idx]["cap"]
                y = outs[bi][s.offset:s.offset
                             + (s.rows // s.r_st) * cap]
                y = y.reshape(s.lead + (cap, s.width))
                if s.side == "B":
                    y = jnp.swapaxes(y, -1, -2)
                results[(s.pair_idx, s.side)] = y.astype(s.dtype)
        # over-cap pairs: reference SVD re-projection, same jitted round
        for pi in fallback:
            meta, p = spec.pairs[pi], plans[pi]
            pA = pB = None
            if spec.has_prev and p["prev_rank"]:
                pA, pB = prev_ab[pi]["A"], prev_ab[pi]["B"]
            A_out, B_out, _ = strategy._stack_pair(
                ab[pi]["A"], ab[pi]["B"], p["ranks"], wt, pA, pB,
                p["prev_rank"] or None, spec.r_max)
            results[(pi, "A")] = A_out
            results[(pi, "B")] = B_out
        return [{"A": results[(pi, "A")], "B": results[(pi, "B")]}
                for pi in range(len(spec.pairs))]

    fn = jax.jit(round_fn)
    fn_donate = jax.jit(round_fn, donate_argnums=(2,))
    rebuild = [None]
    has_prev = spec.has_prev

    def execute(stacked_tree, w, prev_tree, donate):
        if rebuild[0] is None:
            rebuild[0] = _make_rebuilder(stacked_tree)
        ab = _ab_list(stacked_tree)
        prev_ab = _ab_list(prev_tree) if has_prev else None
        run = fn_donate if (donate and has_prev) else fn
        outs = run(ab, w, prev_ab)
        pairs = [{"A": o["A"], "B": o["B"], "rank": rank_leaves[i]}
                 for i, o in enumerate(outs)]
        return rebuild[0](pairs)

    return CompiledRound(strategy, spec, "packed", execute,
                         n_kernel_launches=len(buckets) + len(fallback),
                         n_fallback_pairs=len(fallback),
                         n_pallas_launches=sum(use_kernel))


# ------------------------------------------------------ packed svd plans --
def _build_svd_round(strategy, spec: CohortSpec) -> CompiledRound:
    """svd's packed lowering: pairs bucket by (shape, dtype) and each
    bucket runs ONE batched factored SVD (``repro.core.lowrank``) inside
    a single jitted round -- same CompiledRound contract as the mean and
    stack modes.  Per-pair dense O(m*n*min(m,n)) SVDs become
    O((m+n)*k^2 + k^3) QR/core work, vmapped across the bucket's
    same-shape pairs, with no dense delta materialized.

    Scales (``r_out / rank_i``) enter as runtime data, so -- like the
    mean mode -- one compiled executor serves every rank multiset with
    this cohort layout; a new multiset builds a cheap plan, not a fresh
    XLA compile."""
    # ---- bucket pairs by full geometry (a batched SVD needs both sides
    # of a pair, so buckets key on pair shapes, not row width) ----------
    r_outs = []
    for meta in spec.pairs:
        r_st = meta.a_shape[-2]
        r_outs.append(r_st if spec.r_max is None
                      else min(spec.r_max, r_st))
    bucket_map: dict = {}
    for pi, meta in enumerate(spec.pairs):
        key = (meta.a_shape, meta.a_dtype, meta.b_shape, meta.b_dtype,
               meta.rank_shape, r_outs[pi])
        bucket_map.setdefault(key, []).append(pi)
    svd_buckets = list(bucket_map.values())
    rank_leaves = _out_rank_leaves(spec)

    # per-pair contributor scale tensors (r_out / rank), host-built from
    # the spec's concrete ranks but passed as data for executor reuse;
    # raw (n, *rank_lead) shapes -- svd_project_stacked owns the
    # trailing-lead-dim alignment
    scale_args = []
    for idxs in svd_buckets:
        per_pair = []
        for pi in idxs:
            meta = spec.pairs[pi]
            if spec.client_ranks is not None:
                rk = np.asarray(spec.client_ranks, np.float32)
            else:
                rk = meta.rank_values().astype(np.float32)
            per_pair.append(r_outs[pi] / np.maximum(rk, 1.0))
        scale_args.append(jnp.asarray(np.stack(per_pair), jnp.float32))

    # the engine knobs are traced into round_fn via strategy._project:
    # key them so even a direct (non-with_options) attribute assignment
    # cannot serve a stale executor
    exec_cache = strategy.__dict__.setdefault("_plan_exec_cache", {})
    key = ("svd", strategy.svd_method, strategy.rsvd_oversample,
           strategy.rsvd_power_iters, _shape_key(spec),
           tuple(tuple(idxs) for idxs in svd_buckets), tuple(r_outs))
    fn = exec_cache.get(key)
    if fn is None:
        def round_fn(ab, wt, scs):
            results: dict = {}
            for g, idxs in enumerate(svd_buckets):
                meta = spec.pairs[idxs[0]]
                r_st = meta.a_shape[-2]
                r_out = r_outs[idxs[0]]
                Bs = (jnp.stack([ab[pi]["B"] for pi in idxs])
                      if len(idxs) > 1 else ab[idxs[0]]["B"][None])
                As = (jnp.stack([ab[pi]["A"] for pi in idxs])
                      if len(idxs) > 1 else ab[idxs[0]]["A"][None])

                def project(b, a, sc, _r_out=r_out):
                    return strategy._project(b, a, wt, _r_out, sc)

                Bo, Ao = jax.vmap(project)(Bs, As, scs[g])
                for j, pi in enumerate(idxs):
                    results[(pi, "A")] = pad_to_rank(
                        Ao[j], -2, r_st).astype(meta.a_dtype)
                    results[(pi, "B")] = pad_to_rank(
                        Bo[j], -1, r_st).astype(meta.b_dtype)
            return [{"A": results[(pi, "A")], "B": results[(pi, "B")]}
                    for pi in range(len(spec.pairs))]

        fn = jax.jit(round_fn)
        exec_cache[key] = fn
    rebuild = [None]

    def execute(stacked_tree, w, prev_tree, donate):
        if rebuild[0] is None:
            rebuild[0] = _make_rebuilder(stacked_tree)
        ab = _ab_list(stacked_tree)
        outs = fn(ab, w, scale_args)
        pairs = [{"A": o["A"], "B": o["B"], "rank": rank_leaves[i]}
                 for i, o in enumerate(outs)]
        return rebuild[0](pairs)

    return CompiledRound(strategy, spec, "packed", execute,
                         n_kernel_launches=len(svd_buckets),
                         n_pallas_launches=0)


# ------------------------------------------------------------ eager plans --
def _build_eager_round(strategy, spec: CohortSpec) -> CompiledRound:
    """No-compilation wrapper over the reference math (unknown strategies
    whose leaf math we cannot assume, and paths that keep their own
    caches, e.g. flora's ragged-concat distributed round)."""
    cr = spec.client_ranks_array()

    def execute(stacked_tree, w, prev_tree, donate):
        from repro.lora import adapter_masks
        prev = prev_tree if strategy.retains_prev else None
        masks = adapter_masks(stacked_tree)
        if spec.kind == "distributed":
            out = strategy.aggregate_tree_distributed(
                stacked_tree, masks, w, prev, r_max=spec.r_max,
                client_ranks=cr, mesh=spec.mesh,
                client_axis=spec.client_axis)
        else:
            out = strategy.aggregate_tree(stacked_tree, masks, w, prev,
                                          r_max=spec.r_max,
                                          client_ranks=cr)
        return strategy.finalize_tree(out, spec.r_max)

    return CompiledRound(strategy, spec, "eager", execute)


# -------------------------------------------------------------- dispatch --
def build_plan(strategy, spec: CohortSpec) -> CompiledRound:
    """Build the right :class:`CompiledRound` for ``strategy`` x ``spec``.

    ``strategy.plan_mode`` declares how the strategy lowers:

    * ``"mean"`` -- packed masked-mean buckets (fedavg / zeropad / rbla /
      rbla_ranked) on every backend;
    * ``"mean_norm"`` -- ditto plus rbla_norm's per-row norm restore
      (scalar-rank pairs only; ref and pallas backends);
    * ``"stack"`` -- flora: packed copy/scale stacking on ref and pallas
      (fused XLA slice-updates where Pallas would be interpreted), the
      cached ragged-concat collective when distributed;
    * ``"svd"`` -- packed batched factored SVD (``repro.core.lowrank``):
      one batched QR-core-SVD per same-shape pair bucket on ref and
      pallas; the gathered-factor collective (its own cache) when
      distributed;
    * ``None`` -- eager reference execution (registered strategies we
      know nothing about).
    """
    mode = getattr(strategy, "plan_mode", None)
    if spec.codecs is not None and (mode not in ("mean", "mean_norm")
                                    or spec.kind == "distributed"):
        # per-client cohorts lower through the packed mean family only;
        # the caller decodes and stacks for stack/svd/eager/distributed
        raise PlanUnavailable(
            "per-client cohorts plan only on the mean family")
    try:
        if mode == "mean":
            return _build_mean_round(strategy, spec)
        if mode == "mean_norm":
            if spec.kind == "distributed" or any(
                    len(m.a_shape) != 3 for m in spec.pairs):
                if spec.codecs is not None:
                    raise PlanUnavailable(
                        "per-client mean_norm needs scalar-rank pairs")
                return _build_eager_round(strategy, spec)
            return _build_mean_round(strategy, spec, norm_restore=True)
        if mode == "stack":
            if spec.kind in ("pallas", "ref"):
                # both lower to the packed copy/scale round; the ref kind
                # (and interpret-mode pallas) uses the fused XLA stacking
                # instead of the kernel
                return _build_stack_round(strategy, spec)
            return _build_eager_round(strategy, spec)
        if mode == "svd":
            if spec.kind == "distributed":
                return _build_eager_round(strategy, spec)
            return _build_svd_round(strategy, spec)
    except PlanUnavailable:
        if spec.codecs is not None:
            # the eager round expects a stacked tree -- propagate so the
            # caller decodes, stacks and retries on the stacked path
            raise
        return _build_eager_round(strategy, spec)
    return _build_eager_round(strategy, spec)


# ------------------------------------------------------------- fold plans --
def build_fold_plan(strategy, spec: CohortSpec):
    """Packed per-update fold executor (the async hot path).

    Reuses the cohort packing for a 1-element 'cohort': the server state
    and the arriving update pack into the same (width, dtype) buckets and
    fold in **one fused** ``axpy_fold`` **launch per bucket** -- cost
    O(state), independent of how many pairs the tree has at the Python
    level.  Returns ``fold_fn(state_ab, upd_ab, row_mass, wa, rank_leaves)
    -> (new_ab, new_row_mass)`` (jitted; ``rank_leaves`` are the arriving
    update's per-pair rank leaves, traced so one compilation serves every
    client)."""
    buckets = _make_buckets(spec, use_mask=True)

    def fold_fn(state_ab, upd_ab, row_mass, wa, rank_leaves):
        from repro.kernels.rbla_agg.ops import axpy_fold_inline
        # per-pair owned-row indicators and packed alphas
        alphas = {}
        new_mass = []
        for pi, meta in enumerate(spec.pairs):
            r_st = meta.a_shape[-2]
            rank = jnp.asarray(rank_leaves[pi], jnp.int32)
            owned = (jax.lax.iota(jnp.int32, r_st)
                     < rank[..., None]).astype(jnp.float32)
            dmass = row_mass[pi]
            alphas[pi] = jnp.where(owned > 0, wa / (dmass + wa), 0.0)
            new_mass.append(dmass + wa * owned)
        outs = []
        for b in buckets:
            y_parts = [_pack_prev_side(state_ab[s.pair_idx][s.side], s)
                       for s in b.slots]
            x_parts = [_pack_prev_side(upd_ab[s.pair_idx][s.side], s)
                       for s in b.slots]
            y = (jnp.concatenate(y_parts, axis=0)
                 if len(y_parts) > 1 else y_parts[0])
            x = (jnp.concatenate(x_parts, axis=0)
                 if len(x_parts) > 1 else x_parts[0])
            a_parts = []
            for s in b.slots:
                al = alphas[s.pair_idx]
                mid = len(s.lead) - (al.ndim - 1)
                al = jnp.broadcast_to(
                    al.reshape(al.shape[:-1] + (1,) * mid + (al.shape[-1],)),
                    s.lead + (s.r_st,))
                a_parts.append(al.reshape(s.rows))
            a = (jnp.concatenate(a_parts)
                 if len(a_parts) > 1 else a_parts[0])
            outs.append(axpy_fold_inline(y, x, a,
                                         interpret=spec.interpret))
        new_ab = [
            {s.side: _unpack_slot(outs[bi], s, spec.pairs[s.pair_idx])
             for bi, b in enumerate(buckets) for s in b.slots
             if s.pair_idx == pi}
            for pi in range(len(spec.pairs))]
        return new_ab, new_mass

    return jax.jit(fold_fn), len(buckets)


def build_state_spec(adapters: PyTree, *, interpret=None) -> CohortSpec:
    """A :class:`CohortSpec` for a *server state* tree (no client axis):
    the fold plan's cache key.  Rank values are not part of the key --
    folds take them as data so one compiled fold serves every client."""
    pairs = []
    reads = 0
    for path, pair in _walk_pairs(adapters):
        A, B = pair["A"], pair["B"]
        if isinstance(A, jax.core.Tracer) or isinstance(B, jax.core.Tracer):
            raise PlanUnavailable("state leaves are traced")
        rk_shape = tuple(np.shape(jax.device_get(pair["rank"]))) \
            if not isinstance(pair["rank"], jax.core.Tracer) else None
        if rk_shape is None:
            raise PlanUnavailable("state rank leaf is traced")
        reads += isinstance(pair["rank"], jax.Array)
        pairs.append(PairMeta(
            path=path, a_shape=(1,) + tuple(A.shape), a_dtype=str(A.dtype),
            b_shape=(1,) + tuple(B.shape), b_dtype=str(B.dtype),
            rank_shape=(1,) + rk_shape,
            ranks=tuple(0 for _ in range(int(np.prod(rk_shape,
                                                     dtype=np.int64))))))
    _SYNCS_STATE.inc(reads)
    if not pairs:
        raise PlanUnavailable("no LoRA pairs in the state tree")
    return CohortSpec(n_clients=1, kind="pallas", r_max=None,
                      pairs=tuple(pairs), client_ranks=None,
                      has_prev=False, interpret=interpret)


__all__ = [
    "CohortSpec", "PairMeta", "CompiledRound", "PlanUnavailable",
    "BufferMemo",
    "build_cohort_spec", "build_encoded_cohort_spec", "build_plan",
    "build_fold_plan", "build_state_spec", "dispatch_counter",
    "DispatchCounter",
]
