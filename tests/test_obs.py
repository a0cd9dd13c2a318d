"""The `repro.obs` subsystem: registry semantics, exporters, spans,
zero-retrace guarantees, and the `ServiceHealth` acceptance snapshot.

What is pinned here (see ``docs/observability.md``):

* histogram bucket-edge (`le`) semantics and percentile reads,
* the Prometheus text export round-trips through its own parser,
* `snapshot()` stays consistent under concurrent writers,
* enabling/disabling metrics never retraces a warm plan or serving
  executable (the zero-retrace guarantee the CI bench smoke also gates),
* the deprecation shims (`dispatch_counter`, `plan_stats`,
  `trace_counts`) keep their pre-registry behavior,
* a 128-client mixed-codec async run yields a `ServiceHealth.snapshot()`
  with every section populated (the PR acceptance criterion).
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.core.strategy import ClientUpdate, ServerState, get_strategy
from repro.fl import AsyncAggregator
from repro.fl.async_agg import REJECT_REASONS
from repro.lora import init_adapters
from repro.obs import (MetricsRegistry, ServiceHealth, get_registry,
                       parse_prometheus, set_enabled, span, to_prometheus,
                       write_jsonl_snapshot)

from _cohorts import R_MAX, SPECS, hetero_cohort, mixed_codec_cohort


# ------------------------------------------------------- registry model ----
def test_histogram_bucket_edge_semantics():
    """Prometheus `le` semantics: a value v lands in the first bucket
    whose upper edge e satisfies v <= e; above the last edge it lands in
    the overflow bucket."""
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0,          # both <= 1.0 -> bucket 0
              1.0001, 2.0,       # bucket 1
              4.0,               # exactly the last edge -> bucket 2
              4.0001, 100.0):    # overflow
        h.observe(v)
    sample = h.samples()[""]
    assert sample["buckets"] == [[1.0, 2], [2.0, 2], [4.0, 1]]
    assert sample["overflow"] == 2
    assert sample["count"] == 7
    assert sample["max"] == 100.0
    assert np.isclose(sample["sum"], 0.5 + 1.0 + 1.0001 + 2.0 + 4.0
                      + 4.0001 + 100.0)
    # percentile reads the bucket upper edge; overflow reports the max
    assert h.percentile(0.0) == 1.0
    assert h.percentile(0.5) == 2.0
    assert h.percentile(1.0) == 100.0
    assert reg.histogram("empty", buckets=(1.0,)).percentile(0.5) is None


def test_histogram_rejects_bad_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("a", buckets=(1.0, 1.0))
    with pytest.raises(ValueError, match="finite"):
        reg.histogram("b", buckets=(1.0, float("inf")))
    with pytest.raises(ValueError, match="at least one"):
        reg.histogram("c", buckets=())


def test_counter_monotone_and_label_model():
    reg = MetricsRegistry()
    c = reg.counter("evts_total", labelnames=("reason",))
    c.labels(reason="x").inc()
    c.labels(reason="x").inc(2)
    c.labels(reason="y").inc()
    assert c.samples() == {"reason=x": 3.0, "reason=y": 1.0}
    with pytest.raises(ValueError, match="labels"):
        c.inc()                       # labelled family needs .labels()
    with pytest.raises(ValueError, match="monotone"):
        c.labels(reason="x").inc(-1)
    with pytest.raises(ValueError, match="missing label"):
        c.labels(nope="x")
    # re-registration returns the same instrument; conflicts raise
    assert reg.counter("evts_total", labelnames=("reason",)) is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("evts_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("evts_total", labelnames=("other",))


def test_disabled_registry_is_a_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    h = reg.histogram("h", buckets=(1.0,))
    g = reg.gauge("g")
    c.inc(5)
    h.observe(0.5)
    g.set(3.0)
    assert c.value == 0.0 and h.count == 0 and g.value == 0.0


def test_scoped_window_saves_and_restores():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    c.inc(7)
    with reg.scoped():
        assert c.value == 0.0         # zeroed inside the window
        c.inc(2)
        assert c.value == 2.0
    assert c.value == 7.0             # restored, window discarded
    reg.reset()
    assert c.value == 0.0             # cached handle survives reset


def test_snapshot_consistent_under_concurrent_writers():
    """`snapshot()` while worker threads fold into the same registry:
    no exceptions, monotone counter reads, and exact final totals."""
    reg = MetricsRegistry()
    c = reg.counter("folds_total")
    h = reg.histogram("lat", buckets=(1.0, 2.0))
    n_threads, n_iters = 4, 1000
    start = threading.Barrier(n_threads + 1)

    def fold():
        start.wait()
        for i in range(n_iters):
            c.inc()
            h.observe(float(i % 3))

    workers = [threading.Thread(target=fold) for _ in range(n_threads)]
    for w in workers:
        w.start()
    start.wait()
    seen = 0.0
    for _ in range(50):
        snap = reg.snapshot()
        v = snap["counters"]["folds_total"][""]
        assert v >= seen, "counter went backwards across snapshots"
        seen = v
        hs = snap["histograms"]["lat"][""]
        # each child is read under its family lock: internally consistent
        assert sum(n for _, n in hs["buckets"]) + hs["overflow"] \
            == hs["count"]
    for w in workers:
        w.join()
    assert c.value == n_threads * n_iters
    assert h.count == n_threads * n_iters


# ------------------------------------------------------------- exporters ----
def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(41)
    reg.counter("rej_total", labelnames=("reason",)) \
        .labels(reason="nan_tensor").inc(3)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.5):
        h.observe(v)
    return reg


def test_prometheus_export_round_trips():
    reg = _populated_registry()
    text = to_prometheus(reg)
    assert "# TYPE req_total counter" in text
    assert "# TYPE lat_seconds histogram" in text
    parsed = parse_prometheus(text)
    assert parsed["req_total"][frozenset()] == 41.0
    assert parsed["rej_total"][frozenset({("reason", "nan_tensor")})] == 3.0
    assert parsed["depth"][frozenset()] == 7.0
    # histogram series expand cumulatively, with the implicit +Inf bucket
    b = parsed["lat_seconds_bucket"]
    assert b[frozenset({("le", "0.1")})] == 1.0
    assert b[frozenset({("le", "1")})] == 2.0
    assert b[frozenset({("le", "+Inf")})] == 3.0
    assert parsed["lat_seconds_count"][frozenset()] == 3.0
    assert np.isclose(parsed["lat_seconds_sum"][frozenset()], 3.05)


def test_jsonl_snapshot_appends_parseable_records(tmp_path):
    reg = _populated_registry()
    path = tmp_path / "metrics.jsonl"
    write_jsonl_snapshot(path, reg, phase="warm")
    reg.counter("req_total").inc()
    write_jsonl_snapshot(path, reg, phase="steady")
    records = [json.loads(line)
               for line in path.read_text().splitlines()]
    assert [r["phase"] for r in records] == ["warm", "steady"]
    assert records[0]["metrics"]["counters"]["req_total"][""] == 41.0
    assert records[1]["metrics"]["counters"]["req_total"][""] == 42.0


# ----------------------------------------------------------------- spans ----
def test_span_times_into_stage_histogram():
    reg = MetricsRegistry()
    with span("fold", registry=reg) as sp:
        sp.block(jnp.ones((4,)) * 2)
    hist = reg.get("obs_span_seconds")
    assert hist._children[("fold",)].count == 1
    assert sp.duration_s is not None and sp.duration_s >= 0.0


def test_span_is_inert_under_jit_tracing():
    """A span opened while jax is tracing must be a no-op: nothing
    observed, no Python timestamps baked into the jaxpr."""
    reg = MetricsRegistry()

    @jax.jit
    def f(x):
        with span("fold", registry=reg):
            return x * 2

    np.testing.assert_array_equal(np.asarray(f(jnp.ones((3,)))),
                                  np.full((3,), 2.0))
    hist = reg.get("obs_span_seconds")
    assert hist is None or ("fold",) not in hist._children


def _obs_events(log_dir):
    """``(stage, start_ns, end_ns, thread, metadata)`` of every span
    annotation (``obs.*``) in the profiler trace under ``log_dir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs."):
                    out.append((e.name.split("#")[0][len("obs."):],
                                e.start_ns, e.start_ns + e.duration_ns,
                                (plane.name, line.name), dict(e.stats)))
    return out


def _nested(events, child, parent) -> bool:
    """Every ``child`` span lies inside a ``parent`` span on its thread."""
    kids = [e for e in events if e[0] == child]
    return bool(kids) and all(
        any(p[0] == parent and p[3] == k[3] and p[1] <= k[1]
            and k[2] <= p[2] for p in events) for k in kids)


def test_spans_write_nested_annotations_into_the_profiler_trace(tmp_path):
    """A round's and an upload's spans reach the profiler trace as
    ``obs.<stage>`` annotations, each inside its parent, and the
    outermost carries the request's sequence number."""
    adapters, ranks, w = _warm_cohort(seed=21)
    prev = init_adapters(jax.random.PRNGKey(5), SPECS, R_MAX, R_MAX)
    s = get_strategy("rbla").with_options()
    agg = AsyncAggregator(
        get_strategy("rbla").with_options(),
        ServerState(adapters=init_adapters(jax.random.PRNGKey(6), SPECS,
                                           R_MAX, R_MAX),
                    base_trainable={}, r_max=R_MAX),
        backend="pallas", interpret=True)

    def run():
        out = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                   client_ranks=ranks, prev_global=prev,
                                   backend="pallas", interpret=True)
        # the distributed plan still stacks the cohort: ``round.stack``
        ref = s.aggregate_adapters(adapters, w, r_max=R_MAX,
                                   client_ranks=ranks, prev_global=prev,
                                   backend="distributed")
        agg.submit(ClientUpdate(adapters=adapters[0], base_trainable={},
                                n_examples=2.0, rank=int(ranks[0])))
        jax.block_until_ready((out, ref, agg.state.adapters))
    run()                                   # compiles stay out of the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    ev = _obs_events(tmp_path)
    for stage in ("stack", "spec", "plan", "pack", "combine"):
        assert _nested(ev, "round." + stage, "round"), stage
    assert _nested(ev, "submit.validate", "submit")
    assert _nested(ev, "fold", "flush")
    for stage in ("state_spec", "dispatch"):
        assert _nested(ev, "fold." + stage, "fold"), stage
    assert [set(e[4]) for e in ev if e[0] in ("round", "submit")] == [
        {"round"}, {"round"}, {"upload"}]


# ---------------------------------------------------------- zero-retrace ----
def _warm_cohort(n=4, seed=11):
    adapters, ranks, w = hetero_cohort(n, seed=seed)
    return adapters, ranks, w


ROUND_SPANS = ("round", "round.stack", "round.spec", "round.plan",
               "round.pack", "round.combine")


def test_metrics_toggle_never_retraces_warm_plan_path():
    """Every span of a round -- plain and encoded rounds pack per client,
    the distributed round stacks -- runs on the warm path without a new
    executor or trace."""
    from repro.kernels.runtime import trace_counts
    adapters, ranks, w = _warm_cohort()
    enc = [codec.encode_adapters(a, "int8") for a in adapters]
    s = get_strategy("rbla").with_options()

    def run():
        for cohort, backend in ((adapters, "ref"), (enc, "ref"),
                                (adapters, "distributed")):
            jax.block_until_ready(jax.tree.leaves(s.aggregate_adapters(
                cohort, w, r_max=R_MAX, client_ranks=ranks,
                backend=backend)))
    run()                                                # warm
    execs = len(s.__dict__.get("_plan_exec_cache", {}))
    traces = dict(trace_counts)
    hist = get_registry().get("obs_span_seconds")
    seen = {st: hist.labels(stage=st).count for st in ROUND_SPANS}
    prev = set_enabled(True)
    try:
        for enabled in (True, False, True):
            set_enabled(enabled)
            run()
    finally:
        set_enabled(prev)
    assert len(s.__dict__.get("_plan_exec_cache", {})) == execs
    assert dict(trace_counts) == traces
    # two enabled passes of three rounds each: two packed per client,
    # one stacked onto the distributed plan (no pack/combine spans)
    assert {st: hist.labels(stage=st).count - seen[st]
            for st in ROUND_SPANS} == {
        **{st: 4 for st in ROUND_SPANS}, "round": 6, "round.stack": 2,
        "round.spec": 6, "round.plan": 6}


def test_metrics_toggle_never_retraces_warm_fold_path():
    """The upload's and the packed fold's spans run on the warm path
    without a new fold executor or kernel trace."""
    from repro.kernels.runtime import trace_counts
    adapters, ranks, w = _warm_cohort(seed=13)
    s = get_strategy("rbla").with_options()
    agg = AsyncAggregator(
        s, ServerState(adapters=init_adapters(jax.random.PRNGKey(3), SPECS,
                                              R_MAX, R_MAX),
                       base_trainable={}, r_max=R_MAX),
        backend="pallas", interpret=True)

    def submit(i):
        agg.submit(ClientUpdate(adapters=adapters[i], base_trainable={},
                                n_examples=float(w[i]), rank=int(ranks[i])))
        jax.block_until_ready(agg.state.adapters)
    submit(0)                                            # warm
    plans = len(s.__dict__["_fold_plan_cache"])
    traces = dict(trace_counts)
    stages = ("submit", "submit.validate", "fold", "fold.state_spec",
              "fold.dispatch")
    hist = get_registry().get("obs_span_seconds")
    seen = {st: hist.labels(stage=st).count for st in stages}
    prev = set_enabled(True)
    try:
        for i, enabled in enumerate((True, False, True), start=1):
            set_enabled(enabled)
            submit(i)
    finally:
        set_enabled(prev)
    assert len(s.__dict__["_fold_plan_cache"]) == plans
    assert dict(trace_counts) == traces
    assert {st: hist.labels(stage=st).count - seen[st]
            for st in stages} == {st: 2 for st in stages}


def test_metrics_toggle_never_retraces_warm_serving_path():
    from repro.kernels.runtime import trace_counts
    from repro.serving import AdapterStore, ServingEngine
    rng = np.random.default_rng(0)
    specs = {"proj": (16, 16)}
    store = AdapterStore(specs, r_max=4)
    engine = ServingEngine(
        {"proj": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)},
        store)
    for t in range(4):
        store.register(f"t{t}", rank=1 + t % 4)
    engine.publish(init_adapters(jax.random.PRNGKey(0), specs, 4, 4))
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(1, 5, 8), jnp.int32)
    jax.block_until_ready(engine.apply("proj", x, ids))   # warm
    traces = trace_counts.get("batched_lora_matmul", 0)
    prev = set_enabled(True)
    try:
        for enabled in (True, False, True):
            set_enabled(enabled)
            mix = jnp.asarray(rng.integers(1, 5, 8), jnp.int32)
            jax.block_until_ready(engine.apply("proj", x, mix))
    finally:
        set_enabled(prev)
    assert trace_counts.get("batched_lora_matmul", 0) == traces


# ------------------------------------------------------ deprecation shims ----
def test_dispatch_counter_shim_still_windows():
    from repro.core.plan import dispatch_counter
    from repro.kernels.runtime import count_dispatch
    dispatch_counter.reset()
    count_dispatch(kernel="shim_probe")
    count_dispatch(n=2, kernel="shim_probe")
    assert dispatch_counter.reset() == 3          # windowed read-and-zero
    assert dispatch_counter.count == 0
    # the cumulative registry series kept counting across the reset
    total = get_registry().get("kernel_dispatches_total")
    assert total.samples()["entry=shim_probe"] >= 3.0


def test_plan_stats_shim_mirrors_into_registry():
    adapters, ranks, w = _warm_cohort(seed=12)
    s = get_strategy("zeropad").with_options()
    for _ in range(3):
        s.aggregate_adapters(adapters, w, r_max=R_MAX,
                             client_ranks=ranks, backend="ref")
    stats = s.__dict__["plan_stats"]              # the public shim dict
    assert stats["misses"] == 1 and stats["hits"] == 2
    hits = get_registry().get("plan_cache_hits_total")
    assert hits.samples().get("strategy=zeropad", 0) >= 2.0


# ---------------------------------------------- per-reason rejection view ----
def test_service_health_rejections_match_reason_catalog():
    s = get_strategy("rbla")
    state = ServerState(
        adapters=init_adapters(jax.random.PRNGKey(1), SPECS, R_MAX, R_MAX),
        base_trainable={}, r_max=R_MAX)
    agg = AsyncAggregator(s, state, registry=MetricsRegistry())
    health = ServiceHealth(aggregator=agg)
    assert health.rejections() == {}
    adapters, ranks, w = _warm_cohort(2, seed=5)
    good = ClientUpdate(adapters=adapters[0], base_trainable={},
                        n_examples=2.0, rank=int(ranks[0]))
    with pytest.raises(ValueError):
        agg.submit(ClientUpdate(adapters=adapters[0], base_trainable={},
                                n_examples=-1.0, rank=int(ranks[0])))
    assert health.rejections() == {"bad_mass": 1.0}
    agg.submit(good)
    assert health.rejections() == {"bad_mass": 1.0}   # accepts don't count
    assert set(health.rejections()) <= set(REJECT_REASONS)


# ------------------------------------------- the acceptance-criterion run ----
def test_service_health_snapshot_128_client_mixed_codec_run():
    """The PR acceptance criterion: a 128-client mixed-codec async run
    (buffered mini-cohorts, publishes into a live serving store) yields
    a `ServiceHealth.snapshot()` where every section is populated."""
    from repro.serving import AdapterStore, ServingEngine
    n = 128
    encoded, _, ranks, w, codecs = mixed_codec_cohort(n, seed=2)
    rng = np.random.default_rng(3)
    store = AdapterStore(SPECS, r_max=R_MAX, init_pages=8,
                         init_tenant_capacity=8)
    weights = {p: jnp.asarray(rng.normal(size=(fi, fo)), jnp.float32)
               for p, (fo, fi) in SPECS.items()}
    engine = ServingEngine(weights, store)
    for t in range(4):
        store.register(f"tenant-{t}", rank=1 + t % R_MAX)

    # with_options copy: plan_stats on the shared registered instance
    # accumulates across the whole test process
    s = get_strategy("rbla").with_options()
    state = ServerState(
        adapters=init_adapters(jax.random.PRNGKey(9), SPECS, R_MAX, R_MAX),
        base_trainable={}, r_max=R_MAX)
    agg = AsyncAggregator(s, state, buffer_size=16, backend="ref",
                          on_publish=engine.publisher(),
                          registry=MetricsRegistry())
    for i in range(n):
        agg.submit(ClientUpdate(adapters=encoded[i], base_trainable={},
                                n_examples=float(w[i]), rank=int(ranks[i])),
                   model_version=max(agg.version - i % 5, 0))
    x = jnp.asarray(rng.normal(size=(8, SPECS["fc1"][1])), jnp.float32)
    jax.block_until_ready(
        engine.apply("fc1", x, jnp.asarray([1, 2, 3, 4, 0, 1, 2, 3],
                                           jnp.int32)))

    health = ServiceHealth(aggregator=agg, engine=engine)
    snap = health.snapshot()

    svc = snap["service"]
    assert svc["n_received"] == n and svc["n_dropped"] == 0
    assert svc["version"] == n // 16 and svc["buffer_depth"] == 0
    assert svc["wire_bytes_received"] > 0

    assert snap["codec_mix"] == {
        c: float(sum(1 for cc in codecs if cc == c))
        for c in ("int8", "bf16", "none")}
    assert snap["rejections"] == {}

    stale = snap["staleness"]
    assert stale["count"] == n and stale["p99"] is not None

    lat = snap["latency"]
    for stage in ("submit", "flush", "fold"):
        assert lat[stage] is not None and lat[stage]["count"] > 0, stage
    assert lat["publish"] is not None            # on_publish wired in
    for view in (lat["submit"], lat["fold"]):
        assert view["p50"] <= view["p99"]

    pc = snap["plan_cache"]
    # every mini-cohort here has a distinct rank multiset, so each of
    # the 8 flushes compiles its own plan -- what matters is that the
    # section reports live numbers, not a particular hit rate
    assert pc["hits"] + pc["misses"] == svc["n_flushes"]
    assert pc["hit_rate"] is not None

    st = snap["store"]
    assert st["version"] > 0 and st["n_tenants"] == 4
    assert st["pinned_snapshots"] == 0
    occ = st["page_occupancy"]
    assert occ and all({"pages", "pages_used", "page_rows"} <= set(v)
                       for v in occ.values())
    json.dumps(snap)                             # plain-JSON payload
