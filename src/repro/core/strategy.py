"""First-class aggregation strategies: one pluggable API over every path.

The paper's contribution (RBLA vs zero-padding) plus every beyond-paper
variant used to live as string dispatch (``method == "rbla"`` / ...)
duplicated across the core, fl, kernels, and benchmark layers.  This module
makes each method a single :class:`AggregationStrategy` that owns

* (a) its **leaf math** (:meth:`AggregationStrategy.leaf`),
* (b) its **pytree traversal** including ``prev_global`` retention
  semantics (:meth:`AggregationStrategy.aggregate_tree`),
* (c) an optional **distributed** shard_map path
  (:meth:`AggregationStrategy.make_distributed_aggregator` /
  :meth:`AggregationStrategy.allreduce_leaf`),
* (d) its **compiled plan** (:meth:`AggregationStrategy.plan`, lowered
  by ``repro.core.plan`` per ``plan_mode``), and
* (e) a **per-update fold** for the async aggregation service
  (:meth:`AggregationStrategy.fold` + the ``supports_incremental``
  declaration; see ``repro.fl.async_agg`` and ``docs/async.md``),

behind a ``backend="auto" | "ref" | "pallas" | "distributed"`` selector that
picks the Pallas kernels on TPU/GPU and the XLA lowering on CPU.  The plan
lowers every backend; the per-leaf :meth:`AggregationStrategy.aggregate_tree`
is the float32 reference it is tested against.

Registering a new method is one class::

    from repro.core.strategy import AggregationStrategy, register_strategy

    @register_strategy
    class TrimmedMean(AggregationStrategy):
        name = "trimmed_mean"
        norm_by = "mask"

        def leaf(self, stacked, mask, weights, prev=None):
            ...  # (n_clients, *leaf) -> (*leaf)

after which ``FLConfig(method="trimmed_mean")``, the FL server, the
distributed aggregator factory, and the benchmarks all resolve it by name.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import get_registry as _obs_registry
from repro.obs import cohort_stacks, host_syncs, span

from .aggregation import _EPS, fedavg_leaf, rbla_leaf, zeropad_leaf
from .compat import shard_map_no_check
from .lowrank import product_factors, svd_project_stacked
from .masks import pad_to_rank
from .variants import rank_proportional_weights, rbla_norm_leaf

Array = jax.Array
PyTree = Any

BACKENDS = ("auto", "ref", "pallas", "distributed")

#: per-strategy-instance LRU bound on cached CompiledRounds (plans are
#: keyed by cohort rank multiset among other things, and a random-cohort
#: service sees many multisets; the expensive XLA executables underneath
#: are shared across multisets and are NOT evicted with the plan)
PLAN_CACHE_SIZE = 128

_PLAN_CACHE_HITS = _obs_registry().counter(
    "plan_cache_hits_total", "plan-cache hits, by strategy",
    labelnames=("strategy",))
_PLAN_CACHE_MISSES = _obs_registry().counter(
    "plan_cache_misses_total", "plan-cache misses (plan builds), by strategy",
    labelnames=("strategy",))
_SYNCS_FOLD_RANK = host_syncs("fold_rank")


# ------------------------------------------------------------ server state --
@dataclasses.dataclass
class ServerState:
    """The FL server's round state: what Alg. 1 carries between rounds.

    ``current_rank`` is the per-leaf *live* rank of ``adapters`` after the
    last aggregation: a pytree mirroring ``adapters`` with each LoRA pair
    replaced by its rank leaf.  For fixed-rank strategies it is ``r_max``
    everywhere; rank-changing strategies (``rank_contract="stacked"``)
    vary it round to round while the storage shape stays static.
    """
    adapters: PyTree | None            # global LoRA adapters (None in FFT)
    base_trainable: PyTree             # non-LoRA trainables (or full params)
    round: int = 0
    r_max: int | None = None
    client_ranks: Array | None = None  # ranks of the last participant cohort
    current_rank: PyTree | None = None  # per-leaf live rank of ``adapters``


@dataclasses.dataclass
class ClientUpdate:
    """One participant's upload for a round."""
    adapters: PyTree | None
    base_trainable: PyTree
    n_examples: float = 1.0
    rank: int | None = None


@dataclasses.dataclass
class FoldState:
    """Accumulator threaded through a sequence of per-update folds.

    The async aggregation service (:class:`repro.fl.AsyncAggregator`)
    folds one :class:`ClientUpdate` at a time instead of waiting for a
    cohort; this carries what the running aggregate needs between folds:

    ``mass``
        accumulated raw weight mass (the denominator of the running
        weighted mean for base trainables and ``norm_by="weight"``
        strategies).
    ``row_mass``
        per-pair per-rank-row owner mass (RBLA's Eq. 7 denominator in
        streaming form, where the *transformed* adapter masses
        accumulate): a pytree mirroring the adapters with each pair
        replaced by a ``rank_leaf_shape + (r_storage,)`` f32 array.
        ``None`` for strategies that don't need it.
    ``n_folds``
        how many updates have been folded since the anchor.
    ``extra``
        strategy-private streaming bookkeeping (flora keeps its stacked
        segment ledger here -- per-pair segment ranks, masses, and the
        B-column scales currently applied -- so folds can re-scale in
        place instead of replaying from the anchor).
    ``momentum``
        server momentum buffer (FedBuff/FedAvgM-style): a pytree
        mirroring the adapters' float leaves, or ``None`` when the
        service runs without momentum.  The fold path updates it as
        ``m <- beta * m + (s_new - s_old)`` and publishes
        ``s_old + m`` -- the buffer lives on aggregated state only, so
        secure-aggregation-compatible buffering is unaffected (no
        per-client data is retained).
    """
    mass: float = 0.0
    row_mass: PyTree | None = None
    n_folds: int = 0
    extra: Any = None
    momentum: PyTree | None = None


# ---------------------------------------------------------------- registry --
_REGISTRY: dict[str, "AggregationStrategy"] = {}


def register_strategy(cls):
    """Class decorator: instantiate ``cls`` and register it under
    ``cls.name`` (plus any ``cls.aliases``).  Returns ``cls`` unchanged.

    Duplicate names (or aliases colliding with existing names) raise: a
    silent overwrite would reroute every ``FLConfig(method=...)`` user of
    the shadowed strategy.
    """
    inst = cls()
    if not inst.name:
        raise ValueError(f"{cls.__name__} needs a non-empty .name")
    names = (inst.name,) + tuple(inst.aliases)
    taken = [n for n in names if n in _REGISTRY]
    if taken:
        raise ValueError(
            f"strategy name(s) {taken} already registered (by "
            f"{type(_REGISTRY[taken[0]]).__name__}); pick a unique .name / "
            ".aliases or remove the old entry explicitly")
    for n in names:
        _REGISTRY[n] = inst
    return cls


def get_strategy(name: "str | AggregationStrategy") -> "AggregationStrategy":
    """Resolve a strategy by registry name (or pass an instance through)."""
    if isinstance(name, AggregationStrategy):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregation strategy {name!r}; registered: "
            f"{list_strategies()}") from None


def list_strategies() -> list[str]:
    """Sorted primary names of every registered strategy."""
    return sorted({s.name for s in _REGISTRY.values()})


def resolve_backend(backend: str, strategy: "AggregationStrategy") -> str:
    """Map ``auto`` to ``pallas`` on TPU/GPU (when supported) else ``ref``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; options: {BACKENDS}")
    if backend == "auto":
        if strategy.supports_pallas and jax.default_backend() in ("tpu",
                                                                  "gpu"):
            return "pallas"
        return "ref"
    return backend


# ------------------------------------------------------------ tree helpers --
def stack_trees(trees: Sequence[PyTree]) -> PyTree:
    """Stack per-client pytrees leafwise into (n_clients, *leaf) arrays."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _squeeze_mask(m):
    """0-d mask means 'fully shared leaf' -> None (no rank masking)."""
    return None if (m is not None and getattr(m, "ndim", 1) == 0) else m


def _is_pair(node) -> bool:
    # mirrors repro.lora.is_pair deliberately: core cannot depend on lora
    # at import time (lora itself builds on repro.core.masks)
    return (isinstance(node, Mapping) and "A" in node and "B" in node
            and "rank" in node)


def _map_pairs(fn, tree, *rest, strict: bool = False):
    """Map ``fn`` over every LoRA pair of ``tree`` (and parallel ``rest``
    trees, which may be ``None``).  ``strict`` raises on bare array leaves
    so pair-only strategies fail loudly on generic leaf trees."""
    if _is_pair(tree):
        return fn(tree, *rest)
    if isinstance(tree, Mapping):
        return {k: _map_pairs(fn, v, *[None if r is None else r[k]
                                       for r in rest], strict=strict)
                for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(
            _map_pairs(fn, v, *[None if r is None else r[i] for r in rest],
                       strict=strict) for i, v in enumerate(tree))
    if strict and tree is not None:
        raise NotImplementedError(
            "this strategy aggregates whole LoRA pairs ({'A','B','rank'}); "
            f"got a bare leaf of type {type(tree).__name__}")
    return tree


def _flat_pair_values(tree: PyTree) -> list:
    """Values sitting at pair positions of a ``_map_pairs`` output whose
    pairs were replaced by bare values (e.g. a ``row_mass`` tree), in
    ``_map_pairs`` traversal order."""
    vals: list = []

    def go(t):
        if isinstance(t, Mapping) and not _is_pair(t):
            for v in t.values():
                go(v)
        elif isinstance(t, (tuple, list)):
            for v in t:
                go(v)
        elif t is not None:
            vals.append(t)
    go(tree)
    return vals


def _fix_rank(tree: PyTree, r_max: int | None) -> PyTree:
    """Reset every pair's live rank to r_max: the server keeps the full
    stack; clients re-slice per Alg. 2."""
    def fix(pair):
        p = dict(pair)
        rm = p["A"].shape[-2] if r_max is None else r_max
        p["rank"] = jnp.full_like(jnp.asarray(p["rank"], jnp.int32), rm)
        return p
    return _map_pairs(fix, tree)


def adapter_live_ranks(tree: PyTree) -> PyTree:
    """Per-leaf live-rank tree: every LoRA pair replaced by its rank leaf
    (what :class:`ServerState` carries as ``current_rank``)."""
    return _map_pairs(lambda p: jnp.asarray(p["rank"], jnp.int32), tree)


def _infer_ranks(stacked_tree: PyTree) -> Array | None:
    """Recover the per-client rank vector from a stacked adapter tree's
    first scalar-rank pair (None if there is none)."""
    found = []

    def visit(pair):
        r = jnp.asarray(pair["rank"])
        if r.ndim == 1:
            found.append(r.astype(jnp.int32))
        return pair
    _map_pairs(visit, stacked_tree)
    return found[0] if found else None


def _retain_prev(tree: PyTree, prev: PyTree, client_ranks: Array) -> PyTree:
    """Rank-rows owned by no participant keep the server's current value
    (RBLA's 'preserve unique layers' under partial participation).  Row r
    is owned iff r < max(participant ranks) -- equivalent to the per-element
    den > 0 test when masks are rank-row masks and weights are positive."""
    rmax_part = jnp.max(jnp.asarray(client_ranks, jnp.int32))

    def fix(pair, prev_pair):
        r_storage = pair["A"].shape[-2]
        owned = lax.iota(jnp.int32, r_storage) < rmax_part
        return {
            "A": jnp.where(owned[:, None], pair["A"],
                           prev_pair["A"].astype(pair["A"].dtype)),
            "B": jnp.where(owned[None, :], pair["B"],
                           prev_pair["B"].astype(pair["B"].dtype)),
            "rank": pair["rank"],
        }
    return _map_pairs(fix, tree, prev)


# ------------------------------------------------------------ the protocol --
class AggregationStrategy:
    """One server-side aggregation method, every execution path.

    Subclasses set the class attributes and implement :meth:`leaf` (or
    override :meth:`aggregate_tree` for pair-structured methods).  The
    compiled plan (``plan_mode``) lowers every backend from ``norm_by`` /
    ``use_mask``; :meth:`aggregate_tree` is the float32 reference, and
    runs a round only where the plan refuses the cohort.
    """
    name: str = ""
    aliases: tuple[str, ...] = ()
    #: denominator of the weighted mean: "mask" = sum_i w_i * delta_ir
    #: (RBLA Eq. 7), "weight" = sum_i w_i (zero-padding dilution / FedAvg)
    norm_by: str = "mask"
    #: apply delta_{i,r} rank-row masks at all (FedAvg turns this off)
    use_mask: bool = True
    #: rows no participant owns keep the previous global value
    retains_prev: bool = False
    supports_pallas: bool = False
    supports_distributed: bool = True
    #: declared output-rank contract: "fixed" = the aggregate's live rank
    #: is always r_max (the registry's historical assumption); "stacked" =
    #: the live rank varies with the cohort (e.g. flora) and callers must
    #: read it from the output pairs / ``ServerState.current_rank``
    rank_contract: str = "fixed"
    #: what a homogeneous-rank cohort degenerates to: "factors" = output
    #: factors equal FedAvg of the client factors, "product" = the served
    #: effective update equals the weighted mean of the clients' effective
    #: updates, None = intentionally neither (the property suite reads
    #: this; see tests/test_strategy_properties.py)
    fedavg_equivalence: str | None = "factors"
    #: incremental-capable declaration: True means folding a cohort's
    #: updates one at a time through :meth:`fold` (zero staleness,
    #: running-mass mixing) reproduces the one-shot ``aggregate`` of the
    #: same cohort on the ref backend, up to float reassociation.  False
    #: means :meth:`fold` is an approximation (FedAsync-style convex
    #: mixing) and exact async semantics need the replay path
    #: (:class:`repro.fl.AsyncAggregator` handles this automatically).
    supports_incremental: bool = False
    #: how :meth:`plan` lowers a round (see ``repro.core.plan``):
    #: "mean" = packed masked-mean buckets, "mean_norm" = + per-row norm
    #: restore, "stack" = flora's copy/scale stacking, "svd" = packed
    #: batched factored SVD (repro.core.lowrank), None = eager reference
    #: execution (the safe default for strategies whose leaf math the
    #: planner cannot assume)
    plan_mode: str | None = None
    #: Byzantine-robustness contract: "none" = plain (weighted-mean
    #: family, a single adversarial upload can move the aggregate
    #: arbitrarily far), "clipped" = per-row norm clipping bounds each
    #: client's displacement by ~clip_norm / (owner mass), "trimmed" /
    #: "median" = per-coordinate order statistics with breakdown point
    #: ~trim_frac (resp. 1/2) of a row's owners.  The property harness
    #: checks the declared contract with a 1e6x-norm adversary (see
    #: tests/test_strategy_properties.py).
    robustness: str = "none"

    def with_options(self, **options) -> "AggregationStrategy":
        """Return a configured copy of this strategy.

        Registered instances are shared singletons; per-run knobs (e.g.
        flora's ``stack_r_cap``) must never be set on them directly.  Only
        attributes the strategy already declares are accepted.
        """
        import copy
        inst = copy.copy(self)
        # compiled artifacts close over self and its options: never share
        for cached in ("_dist_agg_cache", "_plan_cache", "plan_stats",
                       "_fold_plan_cache", "_plan_exec_cache",
                       "_stack_memo", "_round_seq"):
            inst.__dict__.pop(cached, None)
        for k, v in options.items():
            if not hasattr(inst, k) or k.startswith("_"):
                raise ValueError(
                    f"strategy {self.name!r} has no option {k!r}")
            setattr(inst, k, v)
        return inst

    def server_storage_rank(self, r_max: int | None) -> int | None:
        """Storage rank the server should allocate for global adapters.
        Fixed-rank strategies store exactly ``r_max``; rank-growing ones
        (flora) need headroom up to their cap."""
        return r_max

    # ------------------------------------------------------ compiled plans --
    def plan(self, state, cohort_spec):
        """Compiled round for ``cohort_spec``: ``plan(state, spec) ->
        CompiledRound`` (see ``repro.core.plan``).

        The round packs the cohort's pairs into (width, dtype) buckets,
        lowers leaf math + prev retention + weight transform into one
        jitted function issuing one fused launch per bucket, and is
        cached on this instance keyed by the spec (tree structure, rank
        multiset, backend, mesh) -- :attr:`plan_stats` counts hits and
        misses.  The cache is a bounded LRU (`PLAN_CACHE_SIZE`): a
        long-lived service with random cohort selection sees a new rank
        multiset most rounds, and while plans are cheap (mean-mode XLA
        executables are shared across multisets -- owner masks are
        runtime data), their host-side mask matrices should not
        accumulate forever.  ``state`` may carry the server state whose
        adapters the round retains; the spec already encodes its layout,
        so ``None`` is accepted.  Unsupported backends raise
        ``NotImplementedError``.
        """
        from .plan import build_plan
        if cohort_spec.kind == "pallas" and not self.supports_pallas:
            raise NotImplementedError(
                f"strategy {self.name!r} has no Pallas kernel path; "
                "use backend='ref'")
        if (cohort_spec.kind == "distributed"
                and not self.supports_distributed):
            raise NotImplementedError(
                f"strategy {self.name!r} has no distributed path; "
                "use backend='ref'")
        from collections import OrderedDict
        cache = self.__dict__.setdefault("_plan_cache", OrderedDict())
        stats = self.__dict__.setdefault("plan_stats",
                                         {"hits": 0, "misses": 0})
        with span("round.plan"):
            got = cache.get(cohort_spec)
            if got is not None:
                stats["hits"] += 1
                _PLAN_CACHE_HITS.labels(strategy=self.name).inc()
                cache.move_to_end(cohort_spec)
                return got
            stats["misses"] += 1
            _PLAN_CACHE_MISSES.labels(strategy=self.name).inc()
            built = build_plan(self, cohort_spec)
            cache[cohort_spec] = built
            while len(cache) > PLAN_CACHE_SIZE:
                cache.popitem(last=False)
            return built

    def _plan_round(self, stacked, kind, *, r_max, client_ranks, prev,
                    mesh, client_axis, interpret):
        """Best-effort plan for an already-stacked cohort; ``None`` when
        the cohort cannot be described host-side (traced leaves, bare
        leaves) -- the caller then runs the reference path in the trace."""
        from .plan import PlanUnavailable, build_cohort_spec
        try:
            with span("round.spec"):
                spec = build_cohort_spec(
                    stacked, kind=kind, r_max=r_max,
                    client_ranks=client_ranks, prev_tree=prev,
                    interpret=interpret, mesh=mesh, client_axis=client_axis)
        except PlanUnavailable:
            return None
        return self.plan(None, spec)

    def _plan_encoded_round(self, client_adapters, codecs, kind, *, r_max,
                            client_ranks, prev, interpret,
                            client_axis="clients"):
        """Best-effort plan for a cohort of per-client trees, plain
        (``codecs`` all ``"none"``) or encoded -- never stacked; ``None``
        sends the caller to the decode-and-stack fallback.  Shares
        :meth:`plan`'s cache, so a codec mix change re-plans while a
        rank-multiset repeat under the same mix hits."""
        from .plan import PlanUnavailable, build_encoded_cohort_spec
        try:
            with span("round.spec"):
                spec = build_encoded_cohort_spec(
                    client_adapters, codecs, kind=kind, r_max=r_max,
                    client_ranks=client_ranks, prev_tree=prev,
                    interpret=interpret, client_axis=client_axis)
            return self.plan(None, spec)
        except PlanUnavailable:
            return None

    # ------------------------------------------------------ (a) leaf math --
    def leaf(self, stacked: Array, mask: Array | None, weights: Array,
             prev: Array | None = None) -> Array:
        """Aggregate one stacked leaf (n_clients, *shape) -> (*shape)."""
        raise NotImplementedError

    def transform_weights(self, weights: Array,
                          client_ranks: Array | None = None) -> Array:
        """Hook: reweight clients before aggregation (rbla_ranked)."""
        return weights

    def _combine(self, num: Array, den_mask: Array | None,
                 den_w: Array | None) -> Array:
        """Numerator/denominator combine shared by the psum paths."""
        if self.norm_by == "mask":
            return jnp.where(den_mask > 0, num / (den_mask + _EPS), 0.0)
        return num / (den_w + _EPS)

    # ------------------------------------------------- (b) tree traversal --
    def aggregate_tree(self, stacked_tree: PyTree, mask_tree: PyTree,
                       weights: Array, prev_tree: PyTree | None = None, *,
                       r_max: int | None = None,
                       client_ranks: Array | None = None) -> PyTree:
        """Reference path: leafwise map over stacked (n, *leaf) trees.

        ``mask_tree`` leaves broadcast against the stacked leaves; 0-d
        leaves mean fully shared.  ``prev_tree`` is honored only by
        strategies with ``retains_prev``.
        """
        w = self.transform_weights(jnp.asarray(weights, jnp.float32),
                                   client_ranks)
        if prev_tree is not None and self.retains_prev:
            return jax.tree.map(
                lambda x, m, p: self.leaf(x, _squeeze_mask(m), w, p),
                stacked_tree, mask_tree, prev_tree,
                is_leaf=lambda v: v is None)
        return jax.tree.map(
            lambda x, m: self.leaf(x, _squeeze_mask(m), w),
            stacked_tree, mask_tree, is_leaf=lambda v: v is None)

    # ---------------------------------------------- (c) distributed path --
    def allreduce_leaf(self, local: Array, mask: Array | None, weight: Array,
                       axis_name: str) -> Array:
        """Aggregate one shard's leaf with all peers over ``axis_name``
        (for use inside shard_map bodies; one client per shard)."""
        if not self.supports_distributed:
            raise NotImplementedError(
                f"strategy {self.name!r} has no distributed path")
        x = local.astype(jnp.float32)
        w = jnp.asarray(weight, jnp.float32)
        mask = _squeeze_mask(mask) if self.use_mask else None
        m = (jnp.ones_like(x) if mask is None
             else jnp.broadcast_to(mask.astype(jnp.float32), x.shape))
        num = lax.psum(w * m * x, axis_name)
        den_mask = (lax.psum(w * m, axis_name)
                    if self.norm_by == "mask" else None)
        den_w = lax.psum(w, axis_name) if self.norm_by == "weight" else None
        return self._combine(num, den_mask, den_w).astype(local.dtype)

    def aggregate_tree_distributed(self, stacked_tree: PyTree,
                                   mask_tree: PyTree, weights: Array,
                                   prev_tree: PyTree | None = None, *,
                                   r_max: int | None = None,
                                   client_ranks: Array | None = None,
                                   mesh=None,
                                   client_axis: str = "clients") -> PyTree:
        """Distributed path over an already-stacked tree.

        Transforms the weights host-side (a shard never sees the global
        rank vector), runs the shard_map aggregator, and re-applies
        ``prev_global`` retention.  Rank-changing strategies override this
        wholesale (their collective is a ragged concat, not a psum).
        """
        wt = self.transform_weights(jnp.asarray(weights, jnp.float32),
                                    client_ranks)
        out = self._aggregate_distributed(stacked_tree, mask_tree, wt, mesh,
                                          client_axis)
        if (prev_tree is not None and self.retains_prev
                and client_ranks is not None):
            out = _retain_prev(out, prev_tree, client_ranks)
        return out

    def make_distributed_aggregator(self, mesh, client_axis: str = "data"):
        """Build a jitted SPMD aggregator over ``client_axis`` of ``mesh``.

        Inputs are sharded pytrees whose leading axis enumerates clients
        (one or more clients per shard); local clients are reduced locally
        (masked partial sums) then combined with psum -- a two-level tree
        reduction.  Weights must already be transformed
        (:meth:`transform_weights` needs the global rank vector, which a
        shard does not see).
        """
        if not self.supports_distributed:
            raise NotImplementedError(
                f"strategy {self.name!r} has no distributed path; "
                "use backend='ref'")
        cache = self.__dict__.setdefault("_dist_agg_cache", {})
        if (mesh, client_axis) in cache:    # one trace+compile per mesh,
            return cache[(mesh, client_axis)]   # not one per FL round
        from jax.sharding import PartitionSpec as P

        def body(stacked_tree, mask_tree, weights):
            wf = weights.astype(jnp.float32)

            def agg_leaf(x, m):
                m = _squeeze_mask(m) if self.use_mask else None
                xf = x.astype(jnp.float32)
                w = wf.reshape(wf.shape + (1,) * (xf.ndim - 1))
                mf = (jnp.ones_like(xf) if m is None
                      else jnp.broadcast_to(m.astype(jnp.float32), xf.shape))
                num = lax.psum(jnp.sum(w * mf * xf, axis=0), client_axis)
                den_mask = (lax.psum(jnp.sum(w * mf, axis=0), client_axis)
                            if self.norm_by == "mask" else None)
                den_w = (lax.psum(jnp.sum(wf), client_axis)
                         if self.norm_by == "weight" else None)
                return self._combine(num, den_mask, den_w).astype(x.dtype)

            return jax.tree.map(agg_leaf, stacked_tree, mask_tree,
                                is_leaf=lambda v: v is None)

        fn = jax.jit(shard_map_no_check(
            body, mesh, in_specs=(P(client_axis), P(client_axis),
                                  P(client_axis)),
            out_specs=P()))
        cache[(mesh, client_axis)] = fn
        return fn

    # ----------------------------------------------------- mid-level API --
    def aggregate_adapters(self, client_adapters: Sequence[PyTree],
                           weights: Array, *, r_max: int | None = None,
                           client_ranks: Array | None = None,
                           prev_global: PyTree | None = None,
                           backend: str = "auto", mesh=None,
                           client_axis: str = "clients",
                           interpret: bool | None = None,
                           donate: bool = False) -> PyTree:
        """Aggregate per-client adapter trees into the global adapter.

        Routes the round through a cached
        :class:`~repro.core.plan.CompiledRound` (packed buffers, one
        fused launch per bucket -- see :meth:`plan`).  The mean family
        (``plan_mode`` ``"mean"``/``"mean_norm"``) on the ref and pallas
        backends plans the per-client trees directly, plain or encoded
        (``repro.core.codec``): one jitted pack writes the client leaves
        straight into the bucket buffers, and the cohort is never
        stacked.  Every other path -- distributed, the stack/svd/eager
        plans, cohorts the per-client walk cannot describe -- decodes and
        stacks the uploads first (``cohort_stacks_total`` counts those
        cohorts).  Where the plan refuses the cohort (leaves or ranks
        hidden by jit tracing, bare leaves), the round runs the reference
        :meth:`aggregate_tree` (or, distributed,
        :meth:`aggregate_tree_distributed`).  ``donate=True`` donates
        ``prev_global``'s A/B buffers to the round -- the caller must not
        touch them after.

        Output rank bookkeeping follows :meth:`finalize_tree`: fixed-rank
        strategies reset the live rank to ``r_max`` (clients re-slice,
        Alg. 2), while rank-changing ones (``rank_contract="stacked"``)
        keep the live rank their aggregation wrote -- read it from the
        output pairs.

        When the same cohort re-participates on consecutive rounds (the
        same client arrays resubmitted -- benchmarks, replay,
        weight-only re-aggregation), the packing is skipped: uploads are
        fingerprinted by buffer identity (jax arrays are immutable) and
        the packed buckets, or on the stacking paths the stacked tree,
        are reused (see ``plan_stats['pack_reuses']``).
        """
        from repro.lora import adapter_masks

        from .codec import cohort_codecs, decode_adapters
        from .plan import BufferMemo
        # the call's sequence number on this instance tags its spans
        seq = self.__dict__.setdefault("_round_seq", itertools.count())
        with span("round", round=next(seq)):
            codecs = cohort_codecs(client_adapters)
            kind = resolve_backend(backend, self)
            prev = prev_global if self.retains_prev else None
            if (kind in ("ref", "pallas")
                    and getattr(self, "plan_mode", None) in ("mean",
                                                             "mean_norm")
                    and (codecs is None or "mixed" not in codecs)):
                # per-client trees, packed inside the plan's one jitted
                # pack: no stacked copy, and encoded uploads keep their
                # wire dtypes (dequant fused into the packed kernels)
                round_ = self._plan_encoded_round(
                    client_adapters,
                    codecs or ("none",) * len(client_adapters), kind,
                    r_max=r_max, client_ranks=client_ranks, prev=prev,
                    interpret=interpret, client_axis=client_axis)
                if round_ is not None:
                    return round_(client_adapters, weights, prev,
                                  donate=donate)
            if codecs is not None:
                client_adapters = [decode_adapters(a)
                                   for a in client_adapters]

            leaves = [leaf for ad in client_adapters
                      for leaf in jax.tree.leaves(ad)]
            memo = self.__dict__.get("_stack_memo")
            if memo is None:
                # require_repeat: a normal FL loop (fresh uploads every
                # round) must retain only a fingerprint between rounds,
                # not a cohort-sized stacked copy
                memo = self.__dict__["_stack_memo"] = BufferMemo(
                    require_repeat=True)
            with span("round.stack"):
                stacked = memo.lookup(leaves)
                if stacked is None:
                    stacked = stack_trees(client_adapters)
                    cohort_stacks(self.name).inc()
                    # identity-memoized only for immutable non-traced jax
                    # buffers seen on consecutive rounds, released as soon
                    # as the uploads die -- the BufferMemo invariants
                    memo.store(leaves, stacked)
            if client_ranks is None:
                client_ranks = _infer_ranks(stacked)
            w = jnp.asarray(weights, jnp.float32)
            round_ = self._plan_round(
                stacked, kind, r_max=r_max, client_ranks=client_ranks,
                prev=prev, mesh=mesh, client_axis=client_axis,
                interpret=interpret)
            if round_ is not None:
                return round_(stacked, w, prev, donate=donate)
            masks = adapter_masks(stacked)
            if kind == "distributed":
                out = self.aggregate_tree_distributed(
                    stacked, masks, w, prev, r_max=r_max,
                    client_ranks=client_ranks, mesh=mesh,
                    client_axis=client_axis)
            else:
                out = self.aggregate_tree(stacked, masks, w, prev,
                                          r_max=r_max,
                                          client_ranks=client_ranks)
            return self.finalize_tree(out, r_max)

    def finalize_tree(self, out: PyTree, r_max: int | None) -> PyTree:
        """Post-aggregation rank bookkeeping.  Fixed-rank strategies reset
        every pair's live rank to ``r_max`` (the server keeps the full
        stack; clients re-slice per Alg. 2).  Rank-changing strategies
        override this to a no-op: their aggregation already wrote the new
        live rank into each pair."""
        return _fix_rank(out, r_max)

    def _aggregate_distributed(self, stacked, masks, w, mesh, client_axis):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .plan import default_client_mesh

        n = int(w.shape[0])
        if mesh is None:
            mesh = default_client_mesh(n, client_axis)
        agg = self.make_distributed_aggregator(mesh, client_axis)
        # 0-d "fully shared" masks can't shard over clients: materialize
        full_masks = jax.tree.map(
            lambda x, m: (jnp.ones(x.shape, jnp.float32) if m.ndim == 0
                          else jnp.broadcast_to(m.astype(jnp.float32),
                                                x.shape)),
            stacked, masks)
        sh = NamedSharding(mesh, P(client_axis))
        return agg(jax.device_put(stacked, sh),
                   jax.device_put(full_masks, sh), jax.device_put(w, sh))

    # ---------------------------------------------------- high-level API --
    def aggregate(self, state: ServerState,
                  client_updates: Sequence[ClientUpdate],
                  weights: Array | None = None, *, backend: str = "auto",
                  mesh=None, client_axis: str = "clients",
                  donate: bool = False,
                  interpret: bool | None = None) -> ServerState:
        """One server round: fold a participant cohort into ``state``.

        Non-LoRA trainables are FedAvg'd; adapters go through this
        strategy on the selected backend.  ``weights`` defaults to the
        updates' ``n_examples``.  ``donate=True`` donates the incoming
        ``state.adapters`` buffers to the round (callers must not read
        the old state afterwards -- the FL server loop holds only the
        returned state).  Returns the next round's state.
        """
        updates = list(client_updates)
        if weights is None:
            weights = [u.n_examples for u in updates]
        w = jnp.asarray(weights, jnp.float32)
        # this cohort's ranks; None (inferred from the pairs downstream)
        # if any update omits its rank -- never a stale previous cohort's
        got = [u.rank for u in updates]
        ranks = (jnp.asarray(got, jnp.int32)
                 if updates and all(r is not None for r in got) else None)

        new_base = state.base_trainable
        base_trees = [u.base_trainable for u in updates]
        if updates and jax.tree.leaves(base_trees[0]):
            new_base = jax.tree.map(lambda x: fedavg_leaf(x, w),
                                    stack_trees(base_trees))

        new_adapters = state.adapters
        ad_trees = [u.adapters for u in updates]
        if (state.adapters is not None and updates
                and all(a is not None for a in ad_trees)):
            new_adapters = self.aggregate_adapters(
                ad_trees, w, r_max=state.r_max, client_ranks=ranks,
                prev_global=state.adapters, backend=backend, mesh=mesh,
                client_axis=client_axis, donate=donate, interpret=interpret)

        current_rank = (adapter_live_ranks(new_adapters)
                        if new_adapters is not None else state.current_rank)
        return ServerState(adapters=new_adapters, base_trainable=new_base,
                           round=state.round + 1, r_max=state.r_max,
                           client_ranks=(ranks if ranks is not None
                                         else state.client_ranks),
                           current_rank=current_rank)

    # ---------------------------------------------------- per-update fold --
    def init_fold(self, state: ServerState) -> FoldState:
        """Fresh accumulator for a sequence of :meth:`fold` calls anchored
        at ``state`` (strategies that stream per-row mass override this to
        allocate it)."""
        return FoldState()

    def fold(self, state: ServerState, update: ClientUpdate,
             weight: float | None = None, *,
             fold_state: FoldState | None = None, backend: str = "auto",
             interpret: bool | None = None
             ) -> tuple[ServerState, FoldState]:
        """Fold ONE arriving update into ``state`` (the async hot path).

        ``weight`` is the update's *effective mass* -- its ``n_examples``
        already scaled by any staleness discount (defaults to plain
        ``n_examples``).  The strategy's own weight semantics (masks,
        ``transform_weights``, prev retention) apply underneath.

        Default implementation: the update is aggregated as a
        single-element cohort through :meth:`aggregate` (so every
        strategy-specific transform runs), then convex-mixed into the
        current state with mixing rate ``alpha = w / (mass + w)`` -- a
        running weighted mean in the style of FedAsync (Xie et al., 2019),
        whose constant-rate variant the caller gets by managing ``mass``.
        On ``backend="pallas"`` the mix is the ``axpy_fold`` kernel (one
        O(size) pass per update, independent of cohort size).

        Exact-incremental strategies (``supports_incremental=True``)
        guarantee that folding a cohort one update at a time reproduces
        the one-shot cohort :meth:`aggregate`; for the rest this default
        is an approximation and :class:`repro.fl.AsyncAggregator` replays
        the buffered cohort instead.  Returns ``(new_state, fold_state)``.
        """
        fs = fold_state if fold_state is not None else self.init_fold(state)
        w = float(update.n_examples if weight is None else weight)
        if w <= 0:
            raise ValueError(f"fold needs a positive weight, got {w}")
        agg = self.aggregate(state, [update], weights=[w], backend=backend,
                             interpret=interpret)
        alpha = w / (fs.mass + w)
        kind = resolve_backend(backend, self)
        new_adapters = state.adapters
        if state.adapters is not None and agg.adapters is not None:
            new_adapters = _mix_trees(state.adapters, agg.adapters, alpha,
                                      kind=kind, interpret=interpret)
        new_base = _mix_trees(state.base_trainable, agg.base_trainable,
                              alpha, kind=kind, interpret=interpret)
        new_fs = FoldState(mass=fs.mass + w, row_mass=fs.row_mass,
                           n_folds=fs.n_folds + 1)
        current_rank = (adapter_live_ranks(new_adapters)
                        if new_adapters is not None else state.current_rank)
        return ServerState(
            adapters=new_adapters, base_trainable=new_base,
            round=state.round + 1, r_max=state.r_max,
            client_ranks=agg.client_ranks,
            current_rank=current_rank), new_fs


def _mix_leaf(old: Array, new: Array, alpha, *, kind: str = "ref",
              interpret: bool | None = None) -> Array:
    """One fold step on one leaf: ``old + alpha * (new - old)``.

    ``alpha`` may be a scalar (uniform server mixing) or broadcastable
    per-row (RBLA's per-rank-row running mean).  ``kind="pallas"``
    dispatches 2-D leaves with vector alpha (or any >=1-D leaf with
    scalar alpha) to the ``axpy_fold`` kernel.
    """
    if not jnp.issubdtype(jnp.asarray(old).dtype, jnp.floating):
        return new                      # int bookkeeping (rank leaves)
    a = jnp.asarray(alpha, jnp.float32)
    if kind == "pallas" and old.ndim >= 1 and a.ndim <= 1:
        from repro.kernels.rbla_agg.ops import axpy_fold
        return axpy_fold(old, new, a, interpret=interpret)
    of = old.astype(jnp.float32)
    a = a.reshape(a.shape + (1,) * (old.ndim - a.ndim))
    return (of + a * (new.astype(jnp.float32) - of)).astype(old.dtype)


def _mix_trees(old: PyTree, new: PyTree, alpha, *, kind: str = "ref",
               interpret: bool | None = None) -> PyTree:
    """Leafwise :func:`_mix_leaf` over parallel pytrees (scalar alpha)."""
    return jax.tree.map(
        lambda o, n: _mix_leaf(o, n, alpha, kind=kind, interpret=interpret),
        old, new)


# --------------------------------------------------------- the strategies --
@register_strategy
class FedAvgStrategy(AggregationStrategy):
    """Plain weighted mean (non-LoRA leaves and the FFT baseline)."""
    name = "fedavg"
    aliases = ("fft",)
    norm_by = "weight"
    use_mask = False
    supports_pallas = True
    # the default fold IS the exact streaming form of a weighted mean
    supports_incremental = True
    plan_mode = "mean"

    def leaf(self, stacked, mask, weights, prev=None):
        return fedavg_leaf(stacked, weights)


@register_strategy
class ZeropadStrategy(AggregationStrategy):
    """HetLoRA-style zero-padding baseline (paper Eq. 1-5): mask values,
    normalize by total weight mass -- missing rows dilute toward zero."""
    name = "zeropad"
    norm_by = "weight"
    supports_pallas = True
    plan_mode = "mean"
    # zeropad = weighted mean of masked uploads, so the default fold's
    # running mix streams it exactly (a single-element aggregate is the
    # masked upload; rows nobody owns stay exactly zero through mixing)
    supports_incremental = True

    def leaf(self, stacked, mask, weights, prev=None):
        return zeropad_leaf(stacked, mask, weights)


@register_strategy
class RBLAStrategy(AggregationStrategy):
    """Rank-Based LoRA Aggregation (paper Eq. 7 / Alg. 1): per rank-row
    weighted mean over owners; unowned rows keep the previous global."""
    name = "rbla"
    norm_by = "mask"
    retains_prev = True
    supports_pallas = True
    supports_incremental = True
    plan_mode = "mean"

    def leaf(self, stacked, mask, weights, prev=None):
        return rbla_leaf(stacked, mask, weights, prev)

    # ---------------------------------------------------- streaming fold --
    def _fold_adapter_weight(self, update: ClientUpdate, w: float,
                             rank: int) -> float:
        """Hook: the mass this update's adapter rows enter with (the
        streaming analogue of :meth:`transform_weights`; ``rbla_ranked``
        scales it by the client's rank)."""
        return w

    def init_fold(self, state: ServerState) -> FoldState:
        if state.adapters is None:
            return FoldState()

        def zeros(pair):
            r_storage = pair["A"].shape[-2]
            shape = jnp.asarray(pair["rank"]).shape + (r_storage,)
            return jnp.zeros(shape, jnp.float32)
        return FoldState(row_mass=_map_pairs(zeros, state.adapters))

    def _packed_fold(self, adapters, upd, row_mass, wa, interpret):
        """Fold via the packed layout: the state's pairs bucket by
        (width, dtype) exactly like a cohort plan, and the whole update
        folds in one jitted call issuing one fused ``axpy_fold`` per
        bucket -- instead of two launches per pair.  Returns
        ``(new_adapters, new_row_mass)`` or ``None`` when the layout
        cannot be packed (the per-pair path handles everything)."""
        from .plan import (PlanUnavailable, _make_rebuilder, _walk_pairs,
                           build_fold_plan, build_state_spec)
        try:
            with span("fold.state_spec"):
                spec = build_state_spec(adapters, interpret=interpret)
            state_pairs = list(_walk_pairs(adapters))
            upd_pairs = list(_walk_pairs(upd))
        except PlanUnavailable:
            return None
        if len(state_pairs) != len(upd_pairs) or any(
                sp["A"].shape != up["A"].shape
                or sp["B"].shape != up["B"].shape
                for (_, sp), (_, up) in zip(state_pairs, upd_pairs)):
            return None
        from repro.kernels.runtime import count_dispatch

        from .compat import count_primitive
        cache = self.__dict__.setdefault("_fold_plan_cache", {})
        entry = cache.get(spec)
        if entry is None:
            entry = cache[spec] = [build_fold_plan(self, spec)[0], None]
        fold_fn = entry[0]
        with span("fold.dispatch"):
            state_ab = [{"A": p["A"], "B": p["B"]} for _, p in state_pairs]
            upd_ab = [{"A": p["A"], "B": p["B"]} for _, p in upd_pairs]
            rank_leaves = [jnp.asarray(p["rank"], jnp.int32)
                           for _, p in upd_pairs]
            args = (state_ab, upd_ab, _flat_pair_values(row_mass),
                    jnp.float32(wa), rank_leaves)
            if entry[1] is None:
                # the kernel launches of one fold, read off its program
                entry[1] = count_primitive(fold_fn.trace(*args).jaxpr,
                                           "pallas_call")
            new_ab, new_mass = fold_fn(*args)
            count_dispatch(entry[1], kernel="axpy_fold")
            rebuild = _make_rebuilder(adapters)
            new_adapters = rebuild(
                [{"A": o["A"], "B": o["B"], "rank": p["rank"]}
                 for o, (_, p) in zip(new_ab, state_pairs)])
            return new_adapters, rebuild(new_mass)

    def fold(self, state, update, weight=None, *, fold_state=None,
             backend="auto", interpret=None):
        """Exact streaming RBLA: Eq. 7's per-rank-row weighted mean in
        running form.  Row ``rho`` of the accumulated owner mass ``d``
        gives the arriving update mixing rate ``w / (d_rho + w)`` on the
        rows it owns and 0 elsewhere, so rows no client has touched keep
        the anchor value (retention for free) and folding a cohort one
        update at a time reproduces the one-shot cohort aggregate.
        """
        fs = fold_state if fold_state is not None else self.init_fold(state)
        w = float(update.n_examples if weight is None else weight)
        if w <= 0:
            raise ValueError(f"fold needs a positive weight, got {w}")
        kind = resolve_backend(backend, self)
        if kind == "distributed":       # one update: nothing to distribute
            kind = "ref"

        new_adapters, new_row_mass = state.adapters, fs.row_mass
        rank_seen = update.rank
        wa = w
        packed = None
        if state.adapters is not None and update.adapters is not None:
            upd = update.adapters
            if rank_seen is None:
                leaves = []
                _map_pairs(lambda p: leaves.append(p["rank"]) or p, upd)
                ranks = [int(np.max(np.asarray(jax.device_get(x))))
                         for x in leaves]
                _SYNCS_FOLD_RANK.inc(
                    sum(isinstance(x, jax.Array) for x in leaves))
                rank_seen = max(ranks) if ranks else None
            wa = self._fold_adapter_weight(update, w, int(rank_seen or 1))
            if kind == "pallas":
                # packed hot path: one fused axpy_fold launch per
                # (width, dtype) bucket instead of two per pair
                packed = self._packed_fold(state.adapters, upd,
                                           fs.row_mass, wa, interpret)
        if packed is not None:
            new_adapters, new_row_mass = packed
        elif state.adapters is not None and update.adapters is not None:
            masses: list[Array] = []

            def fold_pair(pair, upd_pair, dmass):
                r_storage = pair["A"].shape[-2]
                rank = jnp.asarray(upd_pair["rank"], jnp.int32)
                owned = (lax.iota(jnp.int32, r_storage)
                         < rank[..., None]).astype(jnp.float32)
                alpha = jnp.where(owned > 0, wa / (dmass + wa), 0.0)
                masses.append(dmass + wa * owned)
                if (kind == "pallas" and pair["A"].ndim == 2
                        and alpha.ndim == 1):
                    from repro.kernels.rbla_agg.ops import axpy_fold
                    A = axpy_fold(pair["A"], upd_pair["A"], alpha,
                                  interpret=interpret)
                    B = jnp.swapaxes(
                        axpy_fold(jnp.swapaxes(pair["B"], 0, 1),
                                  jnp.swapaxes(upd_pair["B"], 0, 1),
                                  alpha, interpret=interpret), 0, 1)
                else:
                    A = _mix_leaf(pair["A"], upd_pair["A"],
                                  alpha[..., :, None])
                    B = _mix_leaf(pair["B"], upd_pair["B"],
                                  alpha[..., None, :])
                return {"A": A, "B": B, "rank": pair["rank"]}

            new_adapters = _map_pairs(fold_pair, state.adapters, upd,
                                      fs.row_mass, strict=True)
            mass_it = iter(masses)      # same traversal order as above
            new_row_mass = _map_pairs(lambda p: next(mass_it),
                                      state.adapters)

        new_base = state.base_trainable
        if jax.tree.leaves(update.base_trainable):
            new_base = _mix_trees(state.base_trainable,
                                  update.base_trainable,
                                  w / (fs.mass + w), kind=kind,
                                  interpret=interpret)

        new_fs = FoldState(mass=fs.mass + w, row_mass=new_row_mass,
                           n_folds=fs.n_folds + 1)
        current_rank = (adapter_live_ranks(new_adapters)
                        if new_adapters is not None else state.current_rank)
        return ServerState(
            adapters=new_adapters, base_trainable=new_base,
            round=state.round + 1, r_max=state.r_max,
            client_ranks=(jnp.asarray([rank_seen], jnp.int32)
                          if rank_seen is not None else state.client_ranks),
            current_rank=current_rank), new_fs


@register_strategy
class RBLARankedStrategy(RBLAStrategy):
    """RBLA with rank-proportional client weights (HetLoRA-flavoured)."""
    name = "rbla_ranked"

    def _fold_adapter_weight(self, update, w, rank):
        # streaming analogue of rank_proportional_weights: a masked
        # weighted mean depends only on weight *ratios*, so the global
        # (1/max_rank)^alpha scale and the renormalization constant both
        # cancel and w * rank is exact (alpha=1, the aggregate default)
        return w * float(max(rank, 1))

    def transform_weights(self, weights, client_ranks=None):
        if client_ranks is None:
            raise ValueError("rbla_ranked needs client_ranks to reweight "
                             "clients by rank; pass client_ranks (or use "
                             "aggregate_adapters on adapter trees, which "
                             "infers them)")
        return rank_proportional_weights(weights,
                                         jnp.asarray(client_ranks))

    def allreduce_leaf(self, local, mask, weight, axis_name):
        raise NotImplementedError(
            "rbla_ranked cannot reweight inside a shard_map body (a shard "
            "never sees the global rank vector); apply "
            "rank_proportional_weights to the weights first and use the "
            "'rbla' strategy")


@register_strategy
class RBLANormStrategy(AggregationStrategy):
    """RBLA + per-row update-norm preservation (pair-structured: the row
    axis differs between A and B, so it traverses whole pairs)."""
    name = "rbla_norm"
    norm_by = "mask"
    supports_pallas = True             # packed_agg(norm_restore=True)
    supports_distributed = False
    # homogeneous cohorts do NOT degenerate to FedAvg: the per-row norm
    # restoration rescales even fully-shared rows (that is the point)
    fedavg_equivalence = None
    # packed masked mean + per-row norm restore on ref AND pallas;
    # layer-stacked pairs stay on the (refusing) reference path
    plan_mode = "mean_norm"

    def leaf(self, stacked, mask, weights, prev=None):
        return rbla_leaf(stacked, mask, weights, prev)

    def aggregate_tree(self, stacked_tree, mask_tree, weights,
                       prev_tree=None, *, r_max=None, client_ranks=None):
        w = jnp.asarray(weights, jnp.float32)

        def agg_pair(pair, masks):
            if pair["A"].ndim != 3 or pair["B"].ndim != 3:
                raise NotImplementedError(
                    "rbla_norm supports scalar-rank pairs (got "
                    f"A.ndim={pair['A'].ndim}); the per-row norm target "
                    "needs a per-layer loop for layer-stacked pairs")
            return {
                "A": rbla_norm_leaf(pair["A"], masks["A"], w, row_axis=0),
                "B": rbla_norm_leaf(pair["B"], masks["B"], w, row_axis=1),
                "rank": pair["rank"][0],
            }
        return _map_pairs(agg_pair, stacked_tree, mask_tree, strict=True)


class RobustRBLAStrategy(AggregationStrategy):
    """Byzantine-tolerant RBLA family (pair-structured): the masked
    rank-row aggregation of Eq. 7 with the weighted mean replaced by a
    robust reduction over each row's owners.  Three registered variants
    share this base:

    * ``rbla_clipped`` -- every client rank-row is L2-clipped to
      ``clip_norm`` before the standard masked weighted mean; honest
      well-scaled uploads (norms under the clip) aggregate *exactly* like
      ``rbla``, an adversary's displacement is bounded by
      ``clip_norm * w_adv / (owner mass)``.
    * ``rbla_trimmed`` -- per-coordinate trimmed mean over a row's
      owners: drop ``k = min(floor(trim_frac * c), (c-1)//2)`` smallest
      and largest values among the ``c`` owners.  Breakdown point
      ~``trim_frac``.
    * ``rbla_median`` -- coordinate-wise median over a row's owners
      (even ``c``: mean of the middle two).  Breakdown point 1/2.

    Trimmed/median are *unweighted* over owners: example counts are
    client-reported and therefore adversary-controlled, so order
    statistics run on values, not masses.  Rows with no owner retain the
    previous global, exactly like ``rbla``.  All three lower through the
    packed mean-family plan (one fused ``packed_robust`` launch per
    (width, dtype) bucket); there is no distributed path -- order
    statistics need every client's value on one device, and clipping
    needs whole rows (``use backend='ref'`` or ``'pallas'``).  Folding is
    non-incremental by construction (a robust reduction is not a running
    mean), so the async service uses the exact replay path.
    """
    norm_by = "mask"
    use_mask = True
    retains_prev = True
    supports_pallas = True
    supports_distributed = False
    # robust reductions intentionally are not weighted means, so no
    # FedAvg degeneracy is declared (clipped matches rbla only while
    # every row norm is under the clip)
    fedavg_equivalence = None
    supports_incremental = False
    plan_mode = "mean"                 # packed buckets + robust combine
    #: L2 clip applied per (client, rank-row) by "clipped"
    clip_norm: float = 100.0
    #: per-end trim fraction of a row's owners used by "trimmed"
    trim_frac: float = 0.2

    def leaf(self, stacked, mask, weights, prev=None):
        # non-pair leaves (base trainables) have no rank-row structure to
        # defend; they keep the plain masked mean
        return rbla_leaf(stacked, mask, weights, prev)

    def aggregate_tree(self, stacked_tree, mask_tree, weights,
                       prev_tree=None, *, r_max=None, client_ranks=None):
        from repro.kernels.rbla_agg.ref import packed_robust_ref
        from .masks import stacked_rank_masks
        w = jnp.asarray(weights, jnp.float32)
        ranks = (None if client_ranks is None
                 else jnp.asarray(client_ranks, jnp.int32))

        def agg(x, masks, prev):
            return packed_robust_ref(x, masks, w, prev,
                                     mode=self.robustness,
                                     clip_norm=self.clip_norm,
                                     trim_frac=self.trim_frac)

        def agg_pair(pair, prev_pair):
            A, B = pair["A"], pair["B"]
            pranks = ranks
            if pranks is None and jnp.asarray(pair["rank"]).ndim == 1:
                pranks = jnp.asarray(pair["rank"], jnp.int32)
            if A.ndim != 3 or B.ndim != 3 or pranks is None:
                raise NotImplementedError(
                    f"{self.name} supports scalar-rank pairs (got "
                    f"A.ndim={A.ndim}); layer-stacked pairs lower through "
                    "the compiled plan, which packs per-layer rows")
            masks = stacked_rank_masks(A.shape[-2], pranks)
            pA = pB = None
            if prev_pair is not None:
                pA, pB = prev_pair["A"], prev_pair["B"].T
            outA = agg(A, masks, pA)
            outB = agg(jnp.swapaxes(B, 1, 2), masks, pB).T
            return {"A": outA.astype(A.dtype), "B": outB.astype(B.dtype),
                    "rank": pair["rank"][0]}
        return _map_pairs(agg_pair, stacked_tree, prev_tree, strict=True)


@register_strategy
class RBLAClippedStrategy(RobustRBLAStrategy):
    name = "rbla_clipped"
    aliases = ("clipped",)
    robustness = "clipped"


@register_strategy
class RBLATrimmedStrategy(RobustRBLAStrategy):
    name = "rbla_trimmed"
    aliases = ("trimmed",)
    robustness = "trimmed"


@register_strategy
class RBLAMedianStrategy(RobustRBLAStrategy):
    name = "rbla_median"
    aliases = ("median",)
    robustness = "median"


@register_strategy
class SVDStrategy(AggregationStrategy):
    """Product-space aggregation: weighted-average the effective updates
    ``(r_out / rank_i) * B_i @ A_i`` (no dilution -- products are dense),
    truncated-SVD back to rank-``r_out`` factors, re-pad to storage rank.

    The ``r_out / rank_i`` scale matches effective updates under the
    ``alpha / rank`` LoRA convention: serving the aggregate at ``r_max``
    reproduces the weighted mean of the clients' effective deltas.

    The truncation runs through the factored low-rank engine
    (``repro.core.lowrank``): the weighted product mean is itself a
    product of concatenated factors, so the server cost is
    O((out + in) * k^2 + k^3) with k = n * r_storage -- no dense
    (out, in) delta is ever materialized -- instead of the
    O(out * in * min(out, in)) the paper flags.  Layer-stacked
    (leading-dim) pairs batch through the same engine.  ``svd_method``
    and the ``rsvd_*`` knobs (``with_options``-able) route the engine:
    "auto" is exact (factored while k <= min(out, in), dense beyond),
    "randomized" trades exactness for the range-finder sketch.
    """
    name = "svd"
    norm_by = "mask"
    supports_pallas = True             # engine math IS the kernel path
    supports_distributed = True        # gathered factors, replicated SVD
    plan_mode = "svd"                  # packed batched factored SVD
    # FedAvg-equivalence holds in product space only when the truncated
    # SVD is lossless (sum of client ranks <= r_out), which a random
    # cohort does not guarantee -- declared None; the exactness case is
    # covered by test_svd_single_client_preserves_effective_update
    fedavg_equivalence = None
    #: lowrank engine knobs: "auto" | "factored" | "dense" | "randomized"
    svd_method: str = "auto"
    rsvd_oversample: int = 8
    rsvd_power_iters: int = 2

    def _pair_scales(self, pranks, r_out: int):
        """Per-contributor ``r_out / rank`` scales, raw (n, *rank_lead)
        shape -- ``svd_project_stacked`` owns the broadcast alignment
        against the pair's leading dims."""
        return (jnp.float32(r_out) /
                jnp.maximum(jnp.asarray(pranks, jnp.float32), 1.0))

    def _project(self, B, A, w, r_out: int, scales):
        return svd_project_stacked(B, A, w, r_out, scales=scales,
                                   method=self.svd_method,
                                   oversample=self.rsvd_oversample,
                                   power_iters=self.rsvd_power_iters)

    def aggregate_tree(self, stacked_tree, mask_tree, weights,
                       prev_tree=None, *, r_max=None, client_ranks=None):
        w = jnp.asarray(weights, jnp.float32)

        def agg_pair(pair, _masks):
            A, B = pair["A"], pair["B"]
            r_storage = A.shape[-2]
            r_out = r_storage if r_max is None else min(r_max, r_storage)
            pranks = jnp.asarray(pair["rank"] if client_ranks is None
                                 else client_ranks, jnp.int32)
            scales = self._pair_scales(pranks, r_out)
            Bo, Ao = self._project(B, A, w, r_out, scales)
            return {"A": pad_to_rank(Ao.astype(A.dtype), -2, r_storage),
                    "B": pad_to_rank(Bo.astype(B.dtype), -1, r_storage),
                    "rank": pair["rank"][0]}
        return _map_pairs(agg_pair, stacked_tree, mask_tree, strict=True)

    # ---------------------------------------------- (c) distributed path --
    def make_distributed_aggregator(self, mesh, client_axis: str = "data"):
        raise NotImplementedError(
            "svd's distributed path gathers the low-rank factors "
            "(all_gather moves (out+in)*r per client; a dense out*in "
            "delta psum would defeat the factored engine) and projects "
            "replicated -- use aggregate_tree_distributed / "
            "aggregate_adapters(backend='distributed') instead")

    def aggregate_tree_distributed(self, stacked_tree, mask_tree, weights,
                                   prev_tree=None, *, r_max=None,
                                   client_ranks=None, mesh=None,
                                   client_axis: str = "clients"):
        """Gathered-factor collective: each shard all_gathers the
        cohort's low-rank factors and rank vector -- O((out + in) * r)
        bytes per client on the wire, never a dense delta -- and runs
        the factored projection replicated.  Ranks ride as runtime data
        (the output storage is static), so one compiled round serves
        every rank multiset of this cohort shape."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .plan import default_client_mesh

        w = jnp.asarray(weights, jnp.float32)
        n = int(w.shape[0])
        if mesh is None:
            mesh = default_client_mesh(n, client_axis)
        cr = (None if client_ranks is None
              else jnp.asarray(client_ranks, jnp.int32))
        cache = self.__dict__.setdefault("_dist_agg_cache", {})
        key = (mesh, client_axis, r_max, cr is not None, self.svd_method,
               self.rsvd_oversample, self.rsvd_power_iters)
        fn = cache.get(key)
        if fn is None:
            has_cr = cr is not None

            def body(st, wv, crv):
                wf = lax.all_gather(wv, client_axis, tiled=True)
                crf = (lax.all_gather(crv, client_axis, tiled=True)
                       if has_cr else None)

                def agg_pair(pair):
                    Ag = lax.all_gather(pair["A"], client_axis, tiled=True)
                    Bg = lax.all_gather(pair["B"], client_axis, tiled=True)
                    rg = lax.all_gather(
                        jnp.asarray(pair["rank"], jnp.int32), client_axis,
                        tiled=True)
                    r_storage = Ag.shape[-2]
                    r_out = (r_storage if r_max is None
                             else min(r_max, r_storage))
                    pranks = crf if has_cr else rg
                    scales = self._pair_scales(pranks, r_out)
                    Bo, Ao = self._project(Bg, Ag, wf, r_out, scales)
                    return {"A": pad_to_rank(Ao.astype(Ag.dtype), -2,
                                             r_storage),
                            "B": pad_to_rank(Bo.astype(Bg.dtype), -1,
                                             r_storage),
                            "rank": rg[0]}
                return _map_pairs(agg_pair, st, strict=True)

            fn = jax.jit(shard_map_no_check(
                body, mesh,
                in_specs=(P(client_axis), P(client_axis),
                          P(client_axis) if has_cr else P()),
                out_specs=P()))
            cache[key] = fn
        sh = NamedSharding(mesh, P(client_axis))
        return fn(jax.device_put(stacked_tree, sh), jax.device_put(w, sh),
                  jax.device_put(cr, sh) if cr is not None else
                  jnp.zeros((n,), jnp.int32))


@register_strategy
class FloraStrategy(AggregationStrategy):
    """FLoRA-style *stacking* aggregation (Wang et al., 2024).

    Instead of averaging factors row-by-row, the participating clients'
    A/B factors are concatenated along the rank axis, so the aggregate is
    **noise-free** (no cross-client interference) but **rank-growing**:
    the output's live rank is the sum of the contributors' ranks.  The
    previous global is retained by treating it as one more stacked
    contributor (mass ``prev_weight`` x the mean client weight), ILoRA-
    style concatenation plumbing serves the result.

    Scaling: contributor ``i`` (normalized mass ``m_i``, rank ``r_i``)
    enters with ``s_i = m_i * R_out / r_i`` folded into its B columns, so
    that serving the aggregate at rank ``R_out`` under the ``alpha/rank``
    LoRA convention reproduces the convex combination of the
    contributors' effective updates ``sum_i m_i * (alpha/r_i) B_i A_i``
    *exactly*.  A rows pass through verbatim.

    Rank cap: storage is padded to ``stack_r_cap`` (default ``2*r_max``).
    When the stacked rank would exceed the cap, the contributors are
    SVD re-projected back to ``r_max`` in product space instead (same
    math as the ``svd`` strategy, but over the ragged contributor list),
    and rank growth restarts from there next round.

    All paths need **concrete** client ranks: the stack/reproject
    decision and the concat offsets depend on their sum, which cannot be
    resolved under tracing.  Aggregate outside jit (the FL server does).
    """
    name = "flora"
    aliases = ("stacking",)
    rank_contract = "stacked"
    fedavg_equivalence = "product"
    retains_prev = True
    supports_pallas = True
    supports_distributed = True
    norm_by = "weight"
    plan_mode = "stack"
    # exact streaming below the cap: fold keeps a per-pair segment ledger
    # (FoldState.extra) and re-scales B columns in place, so one-at-a-time
    # folding reproduces the one-shot cohort stack bit-for-allclose; at a
    # cap crossing it re-projects in product space (see fold's docstring)
    supports_incremental = True
    stack_r_cap: int | None = None     # None -> 2 * r_max at aggregation
    prev_weight: float = 1.0           # prev global mass / mean client mass

    # ------------------------------------------------------ rank plumbing --
    def resolve_cap(self, r_max: int | None,
                    r_storage: int | None = None) -> int:
        if self.stack_r_cap is not None:
            return int(self.stack_r_cap)
        base = r_max if r_max is not None else r_storage
        if base is None:
            raise ValueError("flora needs r_max (or an explicit "
                             "stack_r_cap) to size the stacked storage")
        return 2 * int(base)

    def server_storage_rank(self, r_max: int | None) -> int | None:
        cap = self.resolve_cap(r_max)
        self._validate_cap(cap, np.zeros(0, np.int64), r_max)  # fail fast
        return cap

    @staticmethod
    def _concrete_ranks(ranks) -> np.ndarray:
        if ranks is None:
            raise ValueError(
                "flora needs the client ranks (pass client_ranks, or "
                "aggregate adapter trees whose pairs carry scalar ranks)")
        if isinstance(ranks, jax.core.Tracer):
            raise NotImplementedError(
                "flora stacking needs concrete client ranks: the output "
                "rank is their sum, which cannot be decided under "
                "jit tracing -- aggregate outside jit")
        arr = np.asarray(jax.device_get(ranks)).astype(np.int64)
        if arr.ndim == 2:            # layer-stacked (n, L): must be uniform
            if not np.all(arr == arr[:, :1]):
                raise NotImplementedError(
                    "flora supports layer-stacked pairs only when each "
                    "client's rank is uniform across layers")
            arr = arr[:, 0]
        return arr.reshape(-1)

    def _validate_cap(self, cap: int, ranks: np.ndarray,
                      r_max: int | None) -> None:
        mx = int(ranks.max()) if ranks.size else 0
        if cap < mx:
            raise ValueError(
                f"flora: stack_r_cap={cap} < max client rank {mx}; a "
                "single contributor would not fit the stacked storage -- "
                "raise stack_r_cap to at least the largest client rank")
        if r_max is not None and cap < r_max:
            raise ValueError(
                f"flora: stack_r_cap={cap} < r_max={r_max}: the SVD "
                "re-projection target would not fit the stacked storage")

    # -------------------------------------------------------- core pair op --
    def _stack_pair(self, A: Array, B: Array, ranks: np.ndarray, w: Array,
                    prev_A: Array | None, prev_B: Array | None,
                    prev_rank: int | None, r_max: int | None):
        """Stack (or SVD-reproject) one gathered pair.

        ``A``: (n, *lead, r_st, fan_in); ``B``: (n, *lead, fan_out, r_st).
        ``ranks``/``prev_rank`` are host ints (static); ``w`` may be
        traced.  Returns (A_out, B_out, r_out) at ``stack_r_cap`` storage.
        Contributor order is prev-first, so the leading rows of the new
        global continue the old one (clients that re-slice the top rows
        keep maximal continuity).
        """
        n = A.shape[0]
        cap = self.resolve_cap(r_max, r_storage=A.shape[-2])
        self._validate_cap(cap, ranks, r_max)
        wf = jnp.asarray(w, jnp.float32)

        seg_ranks: list[int] = []
        A_parts, B_parts, masses = [], [], []
        if prev_A is not None and prev_rank:
            seg_ranks.append(int(prev_rank))
            A_parts.append(prev_A[..., :int(prev_rank), :])
            B_parts.append(prev_B[..., :int(prev_rank)])
            masses.append(self.prev_weight * jnp.mean(wf))
        for i in range(n):
            r_i = int(ranks[i])
            if r_i <= 0:
                continue
            seg_ranks.append(r_i)
            A_parts.append(A[i][..., :r_i, :])
            B_parts.append(B[i][..., :, :r_i])
            masses.append(wf[i])
        if not seg_ranks:
            raise ValueError("flora: empty cohort (all ranks are zero)")
        m = jnp.stack(masses)
        mhat = m / (jnp.sum(m) + _EPS)
        r_total = int(sum(seg_ranks))

        if r_total <= cap:
            r_out = r_total
            scales = mhat * (jnp.float32(r_out) /
                             jnp.asarray(seg_ranks, jnp.float32))
            A_out = jnp.concatenate([a.astype(jnp.float32)
                                     for a in A_parts], axis=-2)
            B_out = jnp.concatenate(
                [b.astype(jnp.float32) * scales[i]
                 for i, b in enumerate(B_parts)], axis=-1)
        else:
            # over the cap: product-space re-projection back to r_max,
            # in factored form (repro.core.lowrank) -- the convex sum of
            # contributor products is a product of concatenated factors,
            # so no dense (out, in) delta is built (batched over any
            # leading layer/expert dims)
            r_out = min(int(r_max if r_max is not None else A.shape[-2]),
                        cap)
            B_cat = jnp.concatenate(
                [b.astype(jnp.float32)
                 * (mhat[i] * (jnp.float32(r_out)
                               / jnp.float32(seg_ranks[i])))
                 for i, b in enumerate(B_parts)], axis=-1)
            A_cat = jnp.concatenate([a.astype(jnp.float32)
                                     for a in A_parts], axis=-2)
            B_out, A_out = product_factors(B_cat, A_cat, r_out)
        A_out = pad_to_rank(A_out.astype(A.dtype), -2, cap)
        B_out = pad_to_rank(B_out.astype(B.dtype), -1, cap)
        return A_out, B_out, r_out

    def _pair_ranks(self, pair, client_ranks) -> np.ndarray:
        got = (pair["rank"] if client_ranks is None else client_ranks)
        return self._concrete_ranks(got)

    @staticmethod
    def _out_rank_leaf(stacked_rank_leaf, r_out: int) -> Array:
        # drop the client axis: scalar-rank -> (), layer-stacked -> (L,)
        shape = jnp.asarray(stacked_rank_leaf).shape[1:]
        return jnp.full(shape, r_out, jnp.int32)

    @staticmethod
    def _prev_rank_of(prev_pair) -> int | None:
        if prev_pair is None:
            return None
        return int(np.max(np.asarray(jax.device_get(prev_pair["rank"]))))

    def finalize_tree(self, out: PyTree, r_max: int | None) -> PyTree:
        return out                       # live ranks already written

    # ---------------------------------------------------- per-update fold --
    def init_fold(self, state: ServerState) -> FoldState:
        """Open a per-pair segment ledger anchored at ``state``: the
        anchor enters the stream as the prev contributor (its B columns
        currently carry scale 1)."""
        if state.adapters is None:
            return FoldState()
        pairs = []

        def grab(pair):
            r_live = int(np.max(np.asarray(jax.device_get(pair["rank"]))))
            pairs.append({
                "prev_rank": r_live,       # anchor segment rows
                "seg_ranks": [],           # client segment ranks, in order
                "seg_w": [],               # client segment masses
                # applied B-column scales, [prev] + clients, aligned with
                # the segment order; the anchor starts unscaled
                "applied": [1.0] if r_live else [],
                "anchor_mass": None,       # set after a cap re-projection
            })
            return pair
        _map_pairs(grab, state.adapters)
        return FoldState(extra={"w_list": [], "pairs": pairs})

    def fold(self, state, update, weight=None, *, fold_state=None,
             backend="auto", interpret=None):
        """Exact streaming stack (below the cap): every contributor owns
        a disjoint B-column segment, and the one-shot scales
        ``m_i_hat * R_out / r_i`` change *multiplicatively* as the cohort
        grows -- so the fold keeps a per-pair ledger of segment ranks,
        masses, and currently-applied scales (:class:`FoldState.extra`)
        and re-scales existing columns by ``desired / applied`` before
        writing the arriving client's rows at the next static offset.
        Folding a cohort one update at a time therefore reproduces the
        one-shot cohort :meth:`aggregate` exactly (the anchor's mass is
        re-derived as ``prev_weight x mean of the weights seen so far``,
        which at the last fold equals the one-shot bookkeeping).

        A stale update is *down-weighted* -- its small effective mass
        shrinks its segment's scale -- never dropped.

        When a fold would cross ``stack_r_cap``, the ledgered stack is
        re-projected in product space back to ``r_max`` (the same SVD the
        one-shot over-cap path runs, on the mathematically identical
        matrix) and the re-projected state becomes a fresh anchor whose
        mass is everything folded so far; streaming after a mid-stream
        crossing can differ from a one-shot that truncated only once.
        """
        fs = fold_state if fold_state is not None else self.init_fold(state)
        if fs.extra is None:
            fs = dataclasses.replace(self.init_fold(state), mass=fs.mass,
                                     n_folds=fs.n_folds)
        w = float(update.n_examples if weight is None else weight)
        if w <= 0:
            raise ValueError(f"fold needs a positive weight, got {w}")

        new_adapters = state.adapters
        extra = fs.extra
        rank_seen = update.rank
        if state.adapters is not None and update.adapters is not None:
            w_list = extra["w_list"] + [w]
            mean_w = sum(w_list) / len(w_list)
            idx = [0]
            new_pairs = []

            def fold_pair(pair, upd_pair):
                meta = extra["pairs"][idx[0]]
                idx[0] += 1
                rk = np.asarray(jax.device_get(upd_pair["rank"]))
                if rk.size > 1 and not np.all(rk == rk.flat[0]):
                    # same contract the one-shot path enforces in
                    # _concrete_ranks: segment offsets must be shared
                    # across layers
                    raise NotImplementedError(
                        "flora supports layer-stacked pairs only when "
                        "each client's rank is uniform across layers")
                r_upd = int(rk.max()) if rk.size else 0
                storage = pair["A"].shape[-2]
                cap = self.resolve_cap(state.r_max, r_storage=storage)
                self._validate_cap(cap, np.asarray([r_upd]), state.r_max)
                prev_rank = meta["prev_rank"]
                prev_mass = (meta["anchor_mass"]
                             if meta["anchor_mass"] is not None
                             else self.prev_weight * mean_w)
                seg_ranks = (([prev_rank] if prev_rank else [])
                             + meta["seg_ranks"]
                             + ([r_upd] if r_upd else []))
                masses = (([prev_mass] if prev_rank else [])
                          + meta["seg_w"] + ([w] if r_upd else []))
                if not seg_ranks:
                    raise ValueError("flora: empty fold (rank 0 update "
                                     "into an empty state)")
                r_out = int(sum(seg_ranks))
                m = np.asarray(masses, np.float64)
                mhat = m / (m.sum() + _EPS)
                A, B = pair["A"], pair["B"]
                off = r_out - r_upd        # the new segment's row offset

                if r_out <= cap:
                    desired = mhat * (float(r_out)
                                      / np.asarray(seg_ranks, np.float64))
                    # re-scale every existing segment's B columns in place
                    applied = meta["applied"] + ([1.0] if r_upd else [])
                    colscale = np.ones(storage, np.float32)
                    o = 0
                    for j, rj in enumerate(seg_ranks):
                        colscale[o:o + rj] = desired[j] / applied[j]
                        o += rj
                    B = B.astype(jnp.float32) * jnp.asarray(colscale)
                    if r_upd:
                        B = B.at[..., :, off:off + r_upd].set(
                            jnp.float32(desired[-1])
                            * upd_pair["B"][..., :, :r_upd].astype(
                                jnp.float32))
                        A = A.at[..., off:off + r_upd, :].set(
                            upd_pair["A"][..., :r_upd, :].astype(A.dtype))
                    new_pairs.append({
                        "prev_rank": prev_rank,
                        "seg_ranks": meta["seg_ranks"]
                        + ([r_upd] if r_upd else []),
                        "seg_w": meta["seg_w"] + ([w] if r_upd else []),
                        "applied": list(desired),
                        "anchor_mass": meta["anchor_mass"],
                    })
                    rank_out = r_out
                else:
                    # cap crossing: product-space re-projection to r_max,
                    # over the mathematically identical matrix the
                    # one-shot over-cap path builds -- factored, so the
                    # ledgered stack plus the arriving segment concatenate
                    # into (storage + r_upd)-wide factors and no dense
                    # (out, in) delta is ever materialized
                    r_t = min(int(state.r_max if state.r_max is not None
                                  else storage), cap)
                    desired = mhat * (float(r_t)
                                      / np.asarray(seg_ranks, np.float64))
                    applied = meta["applied"] + ([1.0] if r_upd else [])
                    colscale = np.zeros(storage, np.float32)
                    o = 0
                    n_old = len(seg_ranks) - (1 if r_upd else 0)
                    for j in range(n_old):
                        rj = seg_ranks[j]
                        colscale[o:o + rj] = desired[j] / applied[j]
                        o += rj
                    B_cat = B.astype(jnp.float32) * jnp.asarray(colscale)
                    A_cat = A.astype(jnp.float32)
                    if r_upd:
                        B_cat = jnp.concatenate(
                            [B_cat, jnp.float32(desired[-1])
                             * upd_pair["B"][..., :, :r_upd].astype(
                                 jnp.float32)], axis=-1)
                        A_cat = jnp.concatenate(
                            [A_cat, upd_pair["A"][..., :r_upd, :].astype(
                                jnp.float32)], axis=-2)
                    B_new, A_new = product_factors(B_cat, A_cat, r_t)
                    B = pad_to_rank(B_new.astype(B.dtype), -1, storage)
                    A = pad_to_rank(A_new.astype(A.dtype), -2, storage)
                    new_pairs.append({
                        "prev_rank": r_t, "seg_ranks": [], "seg_w": [],
                        "applied": [1.0],
                        "anchor_mass": float(m.sum()),
                    })
                    rank_out = r_t
                return {"A": A, "B": B.astype(pair["B"].dtype),
                        "rank": jnp.full_like(
                            jnp.asarray(pair["rank"], jnp.int32),
                            rank_out)}

            new_adapters = _map_pairs(fold_pair, state.adapters,
                                      update.adapters, strict=True)
            extra = {"w_list": w_list, "pairs": new_pairs}
            if rank_seen is None:
                rank_seen = max((p["seg_ranks"][-1] for p in new_pairs
                                 if p["seg_ranks"]), default=None)

        kind = resolve_backend(backend, self)
        if kind == "distributed":      # one update: nothing to distribute
            kind = "ref"
        new_base = state.base_trainable
        if jax.tree.leaves(update.base_trainable):
            new_base = _mix_trees(state.base_trainable,
                                  update.base_trainable,
                                  w / (fs.mass + w), kind=kind,
                                  interpret=interpret)

        new_fs = FoldState(mass=fs.mass + w, n_folds=fs.n_folds + 1,
                           extra=extra)
        current_rank = (adapter_live_ranks(new_adapters)
                        if new_adapters is not None else state.current_rank)
        return ServerState(
            adapters=new_adapters, base_trainable=new_base,
            round=state.round + 1, r_max=state.r_max,
            client_ranks=(jnp.asarray([rank_seen], jnp.int32)
                          if rank_seen is not None else state.client_ranks),
            current_rank=current_rank), new_fs

    # ------------------------------------------------- (b) tree traversal --
    def aggregate_tree(self, stacked_tree, mask_tree, weights,
                       prev_tree=None, *, r_max=None, client_ranks=None):
        w = jnp.asarray(weights, jnp.float32)

        def agg_pair(pair, _masks, prev_pair):
            ranks = self._pair_ranks(pair, client_ranks)
            pA = prev_pair["A"] if prev_pair is not None else None
            pB = prev_pair["B"] if prev_pair is not None else None
            A_out, B_out, r_out = self._stack_pair(
                pair["A"], pair["B"], ranks, w, pA, pB,
                self._prev_rank_of(prev_pair), r_max)
            return {"A": A_out, "B": B_out,
                    "rank": self._out_rank_leaf(pair["rank"], r_out)}
        return _map_pairs(agg_pair, stacked_tree, mask_tree, prev_tree,
                          strict=True)

    # ---------------------------------------------- (c) distributed path --
    def make_distributed_aggregator(self, mesh, client_axis: str = "data"):
        raise NotImplementedError(
            "flora's distributed path is a ragged concat "
            "(gather-then-stack), not a uniform masked psum -- the base "
            "leafwise aggregator would silently average the stacked "
            "factors; use aggregate_tree_distributed / "
            "aggregate_adapters(backend='distributed') instead")

    def aggregate_tree_distributed(self, stacked_tree, mask_tree, weights,
                                   prev_tree=None, *, r_max=None,
                                   client_ranks=None, mesh=None,
                                   client_axis: str = "clients"):
        """Ragged-concat collective: ranks differ per client, so there is
        no uniform psum.  Each shard all-gathers the cohort's factors
        (gather-then-stack) and computes the stacked pair replicated; the
        concat offsets are static (host-known ranks) so the gathered
        layout compiles to plain slices."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        w = jnp.asarray(weights, jnp.float32)
        ranks = self._concrete_ranks(
            client_ranks if client_ranks is not None
            else _infer_ranks(stacked_tree))
        n = int(w.shape[0])
        if mesh is None:
            from .plan import default_client_mesh
            mesh = default_client_mesh(n, client_axis)
        prev_rank_tree = (None if prev_tree is None else
                          _map_pairs(self._prev_rank_of, prev_tree))

        # one trace+compile per (mesh, cohort rank multiset, prev ranks,
        # r_max), not one per FL round: the closure is static in exactly
        # these values (jit itself re-traces on leaf-shape changes)
        cache = self.__dict__.setdefault("_dist_agg_cache", {})
        prev_leaves, prev_def = jax.tree.flatten(prev_rank_tree)
        key = (mesh, client_axis, tuple(int(r) for r in ranks), r_max,
               tuple(prev_leaves), prev_def)
        fn = cache.get(key)
        if fn is None:
            def body(st, wv, pv):
                wf = lax.all_gather(wv, client_axis, tiled=True)

                def agg_pair(pair, prev_pair, prev_rank):
                    Ag = lax.all_gather(pair["A"], client_axis, tiled=True)
                    Bg = lax.all_gather(pair["B"], client_axis, tiled=True)
                    pA = prev_pair["A"] if prev_pair is not None else None
                    pB = prev_pair["B"] if prev_pair is not None else None
                    A_out, B_out, r_out = self._stack_pair(
                        Ag, Bg, ranks, wf, pA, pB, prev_rank, r_max)
                    shape = pair["rank"].shape[1:]
                    return {"A": A_out, "B": B_out,
                            "rank": jnp.full(shape, r_out, jnp.int32)}
                return _map_pairs(agg_pair, st, pv, prev_rank_tree,
                                  strict=True)

            fn = jax.jit(shard_map_no_check(
                body, mesh,
                in_specs=(P(client_axis), P(client_axis), P()),
                out_specs=P()))
            cache[key] = fn
        sh = NamedSharding(mesh, P(client_axis))
        return fn(jax.device_put(stacked_tree, sh),
                  jax.device_put(w, sh), prev_tree)


__all__ = [
    "AggregationStrategy", "ServerState", "ClientUpdate", "FoldState",
    "BACKENDS",
    "register_strategy", "get_strategy", "list_strategies",
    "resolve_backend", "stack_trees", "adapter_live_ranks",
    "FedAvgStrategy", "ZeropadStrategy", "RBLAStrategy",
    "RBLARankedStrategy", "RBLANormStrategy", "RobustRBLAStrategy",
    "RBLAClippedStrategy", "RBLATrimmedStrategy", "RBLAMedianStrategy",
    "SVDStrategy", "FloraStrategy",
]
