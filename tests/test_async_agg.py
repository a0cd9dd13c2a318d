"""Async aggregation service tests.

The load-bearing guarantee is the **parity gate**: for every registered
strategy, folding a cohort's updates one at a time through the
:class:`AsyncAggregator` with zero staleness reproduces the one-shot
``aggregate(state, updates, weights)`` -- exactly (up to float
reassociation) on the ref backend, and within the strategy parity
tolerance or with the documented refusal on pallas/distributed.  Plus:
staleness schedules are monotone discounts, the semi-async buffer
flushes on K and on deadline, staleness actually down-weights (flora
keeps the stale contributor), and the event-driven simulator is finite
and deterministic.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategy import ClientUpdate, ServerState, get_strategy
from repro.fl import (AsyncAggregator, AsyncFLConfig, STALENESS_SCHEDULES,
                      UpdateBuffer, make_staleness_fn, run_async_simulation,
                      run_simulation)
from repro.fl.simulator import FLConfig
from repro.lora import init_adapters

from _cohorts import R_MAX, SPECS, assert_trees_close, hetero_cohort

jax.config.update("jax_platform_name", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_state(strategy, seed=99):
    r_storage = strategy.server_storage_rank(R_MAX) or R_MAX
    prev = init_adapters(jax.random.PRNGKey(seed), SPECS, r_storage, R_MAX)
    base = {"b": jnp.zeros((4,), jnp.float32)}
    return ServerState(adapters=prev, base_trainable=base, r_max=R_MAX)


def configured(method):
    s = get_strategy(method)
    if s.rank_contract == "stacked":
        s = s.with_options(stack_r_cap=256)   # wide: no mid-test reproject
    return s


# ------------------------------------------------------------ parity gate --
ALL_METHODS = ["rbla", "zeropad", "fedavg", "rbla_ranked", "rbla_norm",
               "svd", "flora", "rbla_clipped", "rbla_trimmed",
               "rbla_median"]


def fold_cohort(strategy, backend):
    """Fold the cohort one update at a time; return (async, sync) states."""
    adapters, ranks, w, bases = hetero_cohort(5, seed=3, with_bases=True)
    updates = [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                            n_examples=float(w[i]), rank=int(ranks[i]))
               for i in range(len(ranks))]
    sync = strategy.aggregate(make_state(strategy), updates, weights=w,
                              backend=backend)
    agg = AsyncAggregator(strategy, make_state(strategy),
                          staleness="constant", backend=backend)
    for u in updates:
        agg.submit(u)                      # model_version=None: staleness 0
    return agg.state, sync


@pytest.mark.parametrize("method", ALL_METHODS)
def test_zero_staleness_fold_matches_sync_aggregate_ref(method):
    """THE parity gate (ref backend, tight tolerance): one-at-a-time
    folding with zero staleness == the one-shot cohort aggregate."""
    got, want = fold_cohort(configured(method), "ref")
    assert_trees_close(got.adapters, want.adapters, 2e-5, 2e-6, method)
    assert_trees_close(got.base_trainable, want.base_trainable,
                       2e-5, 2e-6, method)


@pytest.mark.parametrize("method", ALL_METHODS)
@pytest.mark.parametrize("backend", ["pallas", "distributed"])
def test_fold_backend_parity_or_documented_refusal(method, backend):
    """Parity-or-refusal, matching the registry convention: backends the
    strategy supports agree within parity tolerance; unsupported ones
    raise the documented NotImplementedError."""
    s = configured(method)
    supported = (s.supports_pallas if backend == "pallas"
                 else s.supports_distributed)
    if not supported:
        with pytest.raises(NotImplementedError, match=method):
            fold_cohort(s, backend)
        return
    got, want = fold_cohort(s, backend)
    assert_trees_close(got.adapters, want.adapters, 1e-4, 1e-5,
                       f"{method}/{backend}")


def test_fold_hook_direct_matches_async_service():
    """The strategy-level fold hook is what the service drives: calling
    it directly reproduces the AsyncAggregator's fully-async state."""
    s = get_strategy("rbla")
    adapters, ranks, w, bases = hetero_cohort(4, seed=7, with_bases=True)
    updates = [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                            n_examples=float(w[i]), rank=int(ranks[i]))
               for i in range(len(ranks))]
    agg = AsyncAggregator(s, make_state(s), staleness="constant")
    st, fs = make_state(s), s.init_fold(make_state(s))
    for u in updates:
        agg.submit(u)
        st, fs = s.fold(st, u, fold_state=fs, backend="ref")
    assert_trees_close(agg.state.adapters, st.adapters, 1e-6, 1e-7)


# ------------------------------------------------------ staleness schedules --
@pytest.mark.parametrize("name", sorted(STALENESS_SCHEDULES))
def test_staleness_schedules_are_monotone_discounts(name):
    """Every schedule: s(0) == 1, s in (0, 1], monotone non-increasing."""
    fn = make_staleness_fn(name, a=0.5, b=4.0)
    taus = np.arange(0, 50)
    vals = np.asarray([fn(float(t)) for t in taus])
    assert vals[0] == pytest.approx(1.0)
    assert np.all(vals > 0) and np.all(vals <= 1.0)
    assert np.all(np.diff(vals) <= 1e-12)


def test_polynomial_and_hinge_shapes():
    poly = make_staleness_fn("polynomial", a=0.5)
    assert poly(3.0) == pytest.approx((1 + 3.0) ** -0.5)
    hinge = make_staleness_fn("hinge", a=2.0, b=4.0)
    assert hinge(4.0) == pytest.approx(1.0)      # inside the grace period
    assert hinge(6.0) == pytest.approx(1.0 / (2.0 * 2.0 + 1.0))


def test_unknown_schedule_and_bad_params_raise():
    with pytest.raises(ValueError, match="unknown staleness"):
        make_staleness_fn("exponential_not_a_schedule")
    with pytest.raises(ValueError, match="decay"):
        make_staleness_fn("polynomial", a=0.0)
    fn = make_staleness_fn(lambda tau: 0.5)      # callables pass through
    assert fn(0) == 0.5


def test_stale_update_moves_state_less_than_fresh():
    """The same update folded at staleness 10 must move the server less
    than at staleness 0 (the whole point of the discount)."""
    s = get_strategy("rbla")
    adapters, ranks, w, bases = hetero_cohort(2, seed=11, r_lo=R_MAX,
                                              with_bases=True)
    mk = lambda: make_state(s)
    upd = ClientUpdate(adapters=adapters[1], base_trainable=bases[1],
                       n_examples=4.0, rank=int(ranks[1]))
    warm = ClientUpdate(adapters=adapters[0], base_trainable=bases[0],
                        n_examples=4.0, rank=int(ranks[0]))

    def drift(tau):
        agg = AsyncAggregator(s, mk(), staleness="polynomial",
                              staleness_a=0.5)
        agg.submit(warm)                          # version -> 1
        before = agg.state.adapters["fc1"]["A"]
        # a client that pulled at version 1 - tau reports now
        agg.submit(upd, model_version=agg.version - int(tau))
        return float(jnp.linalg.norm(agg.state.adapters["fc1"]["A"]
                                     - before))
    assert drift(10) < drift(0)


def test_flora_stale_contributor_downweighted_not_dropped():
    """flora's async contract: a stale client still lands in the stack
    (rank grows by its rank) but its B-column mass shrinks."""
    s = configured("flora")
    adapters, ranks, w, bases = hetero_cohort(2, seed=13, r_lo=2, r_hi=4,
                                              with_bases=True)
    upd = ClientUpdate(adapters=adapters[1], base_trainable=bases[1],
                       n_examples=1.0, rank=int(ranks[1]))
    warm = ClientUpdate(adapters=adapters[0], base_trainable=bases[0],
                        n_examples=1.0, rank=int(ranks[0]))

    def stacked_mass(tau):
        agg = AsyncAggregator(s, make_state(s), staleness="polynomial",
                              staleness_a=1.0)
        agg.submit(warm)
        r_before = int(agg.state.adapters["fc1"]["rank"])
        agg.submit(upd, model_version=agg.version - int(tau))
        r_after = int(agg.state.adapters["fc1"]["rank"])
        assert r_after == r_before + int(ranks[1])     # stacked, not dropped
        # the stale contributor's rows are the trailing ones (arrival order)
        B = agg.state.adapters["fc1"]["B"]
        return float(jnp.linalg.norm(B[:, r_before:r_after]))
    assert stacked_mass(20) < stacked_mass(0)


def test_flora_direct_fold_streaming_form():
    """FloraStrategy.fold called directly (the documented streaming
    approximation): every fold stacks the arrival (rank grows by its
    rank), and with uniform masses the prev bookkeeping coincides with
    the one-shot cohort aggregate, so streaming == joint exactly."""
    s = configured("flora")
    adapters, ranks, w, bases = hetero_cohort(3, seed=23, with_bases=True)
    updates = [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                            n_examples=2.0, rank=int(ranks[i]))
               for i in range(len(ranks))]
    st, fs = make_state(s), s.init_fold(make_state(s))
    live = R_MAX                               # prev global's live rank
    for i, u in enumerate(updates):
        st, fs = s.fold(st, u, fold_state=fs, backend="ref")
        live += int(ranks[i])
        assert int(st.adapters["fc1"]["rank"]) == live
        assert fs.n_folds == i + 1
    want = s.aggregate(make_state(s), updates, weights=[2.0] * 3,
                       backend="ref")
    assert_trees_close(st.adapters, want.adapters, 1e-5, 1e-6,
                       "flora streaming vs joint (uniform masses)")


def test_flora_streams_without_replay():
    """flora declares supports_incremental now: the service streams its
    fold (segment-ledger re-scaling) instead of replaying from the
    anchor, and the zero-staleness gate above holds exactly."""
    s = configured("flora")
    assert s.supports_incremental
    adapters, ranks, w, bases = hetero_cohort(4, seed=29, with_bases=True)
    agg = AsyncAggregator(s, make_state(s), staleness="constant")
    for i in range(4):
        agg.submit(ClientUpdate(adapters=adapters[i],
                                base_trainable=bases[i],
                                n_examples=float(w[i]),
                                rank=int(ranks[i])))
    assert agg.n_folded == 4 and len(agg._replay) == 0


# ------------------------------------------------- wall-clock staleness ----
def test_wall_clock_staleness_discounts_by_elapsed_time():
    """staleness_clock='wall': the same upload moves the server less the
    longer ago its global was pulled, regardless of version churn."""
    s = get_strategy("rbla")
    adapters, ranks, w, bases = hetero_cohort(2, seed=31, r_lo=R_MAX,
                                              with_bases=True)
    warm = ClientUpdate(adapters=adapters[0], base_trainable=bases[0],
                        n_examples=4.0, rank=int(ranks[0]))
    upd = ClientUpdate(adapters=adapters[1], base_trainable=bases[1],
                       n_examples=4.0, rank=int(ranks[1]))

    def drift(age_s):
        agg = AsyncAggregator(s, make_state(s), staleness="polynomial",
                              staleness_a=0.5, staleness_clock="wall")
        agg.submit(warm, now=100.0, pulled_at=100.0)
        before = agg.state.adapters["fc1"]["A"]
        agg.submit(upd, now=100.0, pulled_at=100.0 - age_s)
        return float(jnp.linalg.norm(agg.state.adapters["fc1"]["A"]
                                     - before))
    drifts = [drift(a) for a in (0.0, 5.0, 50.0)]
    assert drifts[0] > drifts[1] > drifts[2]


@pytest.mark.parametrize("clock", ["version", "wall"])
def test_staleness_schedule_monotone_in_both_clocks(clock):
    """The effective weight s(tau) * n is monotone non-increasing in tau
    whichever clock measures tau."""
    s = get_strategy("fedavg")
    base = {"b": jnp.zeros((4,), jnp.float32)}
    upd = ClientUpdate(adapters=None, base_trainable={"b": jnp.ones(4)},
                       n_examples=2.0)
    weights = []
    for tau in range(0, 30, 3):
        agg = AsyncAggregator(
            s, ServerState(adapters=None, base_trainable=base, round=50),
            staleness="polynomial", staleness_a=0.7, staleness_clock=clock)
        if clock == "version":
            weights.append(agg.staleness_weight(
                agg.version - (agg.version - tau)))
        else:
            weights.append(agg.staleness_weight(float(tau)))
        if clock == "wall":     # exercises the submit-side tau path too
            agg.submit(upd, now=float(tau), pulled_at=0.0)
    assert all(a >= b for a, b in zip(weights, weights[1:]))
    assert weights[0] == pytest.approx(1.0)


def test_unknown_staleness_clock_raises():
    s = get_strategy("rbla")
    with pytest.raises(ValueError, match="staleness_clock"):
        AsyncAggregator(s, make_state(s), staleness_clock="lamport")


def test_wall_clock_skew_clamps_staleness_at_zero():
    """Regression: a client whose pull timestamp is *ahead* of the server
    clock (clock skew) must be treated as fresh -- negative tau would
    feed s(tau) > 1 into the weight (inflating the skewed client) and
    trip the schedule range check."""
    s = get_strategy("rbla")
    adapters, ranks, w, bases = hetero_cohort(2, seed=41, r_lo=R_MAX,
                                              with_bases=True)
    upd = ClientUpdate(adapters=adapters[1], base_trainable=bases[1],
                       n_examples=4.0, rank=int(ranks[1]))

    def folded(pulled_at):
        agg = AsyncAggregator(s, make_state(s), staleness="polynomial",
                              staleness_a=0.5, staleness_clock="wall")
        agg.submit(upd, now=100.0, pulled_at=pulled_at)
        assert agg.staleness_sum >= 0.0
        return np.asarray(agg.state.adapters["fc1"]["A"])
    # skewed (pulled "in the future") == fresh, bit-for-bit
    np.testing.assert_array_equal(folded(150.0), folded(100.0))


# ------------------------------------------------- ingestion validation ----
def _one_update(seed=43):
    adapters, ranks, w, bases = hetero_cohort(2, seed=seed, r_lo=R_MAX,
                                              with_bases=True)
    return ClientUpdate(adapters=adapters[0], base_trainable=bases[0],
                        n_examples=4.0, rank=int(ranks[0]))


@pytest.mark.parametrize("n_examples", [0.0, -3.0, float("nan"),
                                        float("inf")])
def test_submit_rejects_invalid_example_counts(n_examples):
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s))
    upd = dataclasses.replace(_one_update(), n_examples=n_examples)
    with pytest.raises(ValueError, match="n_examples"):
        agg.submit(upd)
    assert agg.n_received == 0 and len(agg.buffer) == 0
    assert agg.version == 0


@pytest.mark.parametrize("poison", [float("nan"), float("inf")])
def test_submit_rejects_non_finite_tensors(poison):
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s))
    upd = _one_update()
    bad = jax.tree.map(lambda x: x, upd.adapters)
    bad["fc1"]["A"] = bad["fc1"]["A"].at[0, 0].set(poison)
    with pytest.raises(ValueError, match="non-finite"):
        agg.submit(dataclasses.replace(upd, adapters=bad))
    base = {"b": jnp.full((4,), poison, jnp.float32)}
    with pytest.raises(ValueError, match="non-finite"):
        agg.submit(dataclasses.replace(upd, base_trainable=base))
    assert agg.n_received == 0 and len(agg.buffer) == 0


def test_zero_mass_flush_is_a_noop():
    """A batch whose staleness-discounted masses sum to 0 has no convex
    combination: the flush must drop it without advancing (or NaN-ing)
    the state."""
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s), buffer_size=2, deadline=1.0)
    before = np.asarray(agg.state.adapters["fc1"]["A"])
    upd = _one_update()
    agg.buffer.add(upd, weight=0.0, now=0.0)     # mass underflowed to 0
    agg.buffer.add(upd, weight=0.0, now=0.0)
    assert agg.buffer.total_weight() == 0.0
    agg.flush(now=10.0)
    assert agg.version == 0 and agg.n_flushes == 0
    assert agg.n_dropped == 2 and len(agg.buffer) == 0
    np.testing.assert_array_equal(
        before, np.asarray(agg.state.adapters["fc1"]["A"]))
    assert np.isfinite(np.asarray(agg.state.adapters["fc1"]["A"])).all()


# --------------------------------------------------- quantized transport ----
def _encoded_update(codec_name, seed=43):
    from repro.core import codec
    return codec.encode_update(_one_update(seed=seed), codec_name)


@pytest.mark.parametrize("poison", [float("nan"), 0.0, -1.0])
def test_submit_rejects_bad_quantization_scales(poison):
    """Scale sanity sits next to the NaN/inf gate: non-finite or
    non-positive scales name the scale (not the generic tensor message)
    and leave every service counter untouched."""
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s))
    upd = _encoded_update("int8")
    bad = {k: dict(v) for k, v in upd.adapters.items()}
    bad["fc1"]["A_scale"] = bad["fc1"]["A_scale"].at[0].set(poison)
    with pytest.raises(ValueError, match="scale"):
        agg.submit(dataclasses.replace(upd, adapters=bad))
    assert agg.n_received == 0 and len(agg.buffer) == 0
    assert agg.wire_bytes_received == 0 and agg.version == 0


def test_submit_rejects_overflowing_decoded_norm():
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s))
    upd = _encoded_update("int8")
    bad = {k: dict(v) for k, v in upd.adapters.items()}
    bad["fc2"]["B_scale"] = bad["fc2"]["B_scale"].at[0].set(3.0e36)
    with pytest.raises(ValueError, match="overflow"):
        agg.submit(dataclasses.replace(upd, adapters=bad))
    assert agg.n_received == 0 and len(agg.buffer) == 0


def test_codec_negotiation_rejects_unlisted_wire_formats():
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s), codecs="none")
    with pytest.raises(ValueError, match="codec"):
        agg.submit(_encoded_update("int8"))
    with pytest.raises(ValueError, match="codec"):
        agg.submit(_encoded_update("bf16"))
    assert agg.n_received == 0 and len(agg.buffer) == 0
    agg.submit(_encoded_update("none"))             # negotiated: accepted
    assert agg.n_received == 1
    with pytest.raises(ValueError, match="codec"):
        AsyncAggregator(s, make_state(s), codecs=("none", "fp4"))
    with pytest.raises(ValueError, match="accum_dtype"):
        AsyncAggregator(s, make_state(s), accum_dtype="float16")


@pytest.mark.parametrize("wire", ["int8", "bf16"])
@pytest.mark.parametrize("buffer_size", [1, 5])
def test_quantized_uploads_track_plain_folds(wire, buffer_size):
    """The full service path (incremental fold and buffered mini-cohort)
    under quantized uploads stays within the codec's tolerance of the
    fp32 run, and wire accounting reflects the compression."""
    adapters, ranks, w, bases = hetero_cohort(5, seed=3, with_bases=True)
    updates = [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                            n_examples=float(w[i]), rank=int(ranks[i]))
               for i in range(len(ranks))]
    from repro.core import codec
    s = get_strategy("rbla")
    plain = AsyncAggregator(s, make_state(s), buffer_size=buffer_size)
    quant = AsyncAggregator(get_strategy("rbla"), make_state(s),
                            buffer_size=buffer_size)
    for u in updates:
        plain.submit(u)
        quant.submit(codec.encode_update(u, wire))
    tol = 2e-2 if wire == "int8" else 8e-3
    assert_trees_close(plain.state.adapters, quant.state.adapters,
                       rtol=0.1, atol=tol, msg=f"{wire}/K={buffer_size}")
    assert quant.wire_bytes_received < plain.wire_bytes_received
    ratio = plain.wire_bytes_received / quant.wire_bytes_received
    assert ratio > (2.5 if wire == "int8" else 1.5)


def _rejection_counts(agg):
    metric = agg.obs_registry.get("fl_updates_rejected_total")
    if metric is None:
        return {}
    return {key.partition("=")[2]: int(v)
            for key, v in metric.samples().items() if v}


@pytest.mark.parametrize("reason", ["bad_mass", "nan_tensor", "bad_scale",
                                    "overflow", "codec_not_allowed",
                                    "zero_mass_flush"])
def test_each_rejection_path_increments_exactly_its_own_counter(reason):
    """Satellite regression for the per-reason rejection split: every
    ingestion/flush rejection path bumps ``fl_updates_rejected_total``
    under its own reason label and nothing else (catalog in
    ``docs/observability.md``)."""
    from repro.obs import MetricsRegistry
    s = get_strategy("rbla")
    codecs = "none" if reason == "codec_not_allowed" else ("none", "int8")
    agg = AsyncAggregator(s, make_state(s), codecs=codecs,
                          buffer_size=2, deadline=1.0,
                          registry=MetricsRegistry())
    if reason == "zero_mass_flush":
        upd = _one_update()
        agg.buffer.add(upd, weight=0.0, now=0.0)
        agg.buffer.add(upd, weight=0.0, now=0.0)
        agg.flush(now=10.0)
        assert _rejection_counts(agg) == {"zero_mass_flush": 2}
        assert agg.n_dropped == 2
        return
    if reason == "bad_mass":
        bad = dataclasses.replace(_one_update(), n_examples=0.0)
    elif reason == "nan_tensor":
        upd = _one_update()
        adapters = jax.tree.map(lambda x: x, upd.adapters)
        adapters["fc1"]["A"] = adapters["fc1"]["A"].at[0, 0].set(
            float("nan"))
        bad = dataclasses.replace(upd, adapters=adapters)
    else:
        bad = _encoded_update("int8")
        if reason == "bad_scale":
            adapters = {k: dict(v) for k, v in bad.adapters.items()}
            adapters["fc1"]["A_scale"] = \
                adapters["fc1"]["A_scale"].at[0].set(float("nan"))
            bad = dataclasses.replace(bad, adapters=adapters)
        elif reason == "overflow":
            adapters = {k: dict(v) for k, v in bad.adapters.items()}
            adapters["fc2"]["B_scale"] = \
                adapters["fc2"]["B_scale"].at[0].set(3.0e36)
            bad = dataclasses.replace(bad, adapters=adapters)
    with pytest.raises(ValueError):
        agg.submit(bad)
    assert _rejection_counts(agg) == {reason: 1}
    assert agg.n_received == 0 and len(agg.buffer) == 0


@pytest.mark.parametrize("case", ["none", "int8", "base_trainable"])
def test_submit_checks_an_upload_in_one_program_and_one_read(
        case, monkeypatch):
    """Every device check of an upload (finiteness of each float leaf of
    both trees, both flags of each int8 scale plane) runs in one compiled
    program whose flags the host reads with one ``jax.device_get``; a
    second upload of the same shapes traces nothing.  Rejections still
    name what the per-leaf checks named: a NaN scale next to a NaN
    payload is ``bad_scale``, a NaN only in ``base_trainable`` names
    ``base_trainable``."""
    from jax._src.dispatch import JAXPR_TRACE_EVENT
    from repro.core import codec
    from repro.obs import MetricsRegistry

    def upload(seed):
        u = _one_update(seed=seed)
        if case == "none":
            return dataclasses.replace(u, base_trainable={})
        return codec.encode_update(u, "int8") if case == "int8" else u

    s = get_strategy("rbla")
    # buffered, never due: submit validates and folds nothing
    agg = AsyncAggregator(s, make_state(s), buffer_size=8,
                          registry=MetricsRegistry())
    agg.submit(upload(43))                 # compiles this signature
    calls, traces = [], []
    get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or get(x))

    def listen(event, _secs, **_):
        if event == JAXPR_TRACE_EVENT:
            traces.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        agg.submit(upload(44))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(calls) == 1 and traces == [] and agg.n_received == 2

    bad = upload(45)
    adapters = {k: dict(v) for k, v in bad.adapters.items()}
    if case == "int8":
        adapters["fc1"]["A_scale"] = \
            adapters["fc1"]["A_scale"].at[0].set(float("nan"))
        # a plain pair after it, its payload NaN: the scale is named
        adapters["fc2"] = _one_update(seed=45).adapters["fc2"]
        adapters["fc2"]["A"] = adapters["fc2"]["A"].at[0, 0].set(
            float("nan"))
        reason, match = "bad_scale", "scale in fc1.A_scale"
    elif case == "none":
        adapters["fc1"]["A"] = adapters["fc1"]["A"].at[0, 0].set(
            float("nan"))
        reason, match = "nan_tensor", "non-finite values in adapters"
    else:
        bad = dataclasses.replace(
            bad, base_trainable={"b": jnp.full((4,), jnp.nan)})
        reason, match = "nan_tensor", "non-finite values in base_trainable"
    with pytest.raises(ValueError, match=match):
        agg.submit(dataclasses.replace(bad, adapters=adapters))
    assert _rejection_counts(agg) == {reason: 1}
    assert len(calls) == 2 and agg.n_received == 2


def test_upload_on_two_devices_is_checked_with_one_read():
    """Leaves committed to different devices cannot meet in one jitted
    program: the check runs once per device set and the host still reads
    every flag with one ``jax.device_get`` (two host CPU devices, in a
    child process so this suite keeps its single device)."""
    code = r"""
import jax, jax.numpy as jnp
from repro.core import ClientUpdate, ServerState, get_strategy
from repro.core.codec import encode_adapters
from repro.fl import AsyncAggregator
from repro.lora import init_adapters

d0, d1 = jax.devices()[:2]
specs = {"fc1": (12, 16), "fc2": (10, 12)}
state = ServerState(adapters=init_adapters(jax.random.PRNGKey(0), specs,
                                           8, 8),
                    base_trainable={}, r_max=8)
agg = AsyncAggregator(get_strategy("rbla"), state, buffer_size=8)
up = init_adapters(jax.random.PRNGKey(1), specs, 8, 8)
up = {"fc1": jax.device_put(up["fc1"], d0),
      "fc2": jax.device_put(up["fc2"], d1)}
calls = []
get = jax.device_get
jax.device_get = lambda x: calls.append(1) or get(x)
upd = lambda a: ClientUpdate(adapters=a, base_trainable={}, n_examples=2.0,
                             rank=8)
agg.submit(upd(up))
assert len(calls) == 1 and agg.n_received == 1, calls
bad = {"fc1": up["fc1"],
       "fc2": dict(up["fc2"], A=up["fc2"]["A"].at[0, 0].set(jnp.nan))}
try:
    agg.submit(upd(bad))
    raise SystemExit("a NaN on the second device was accepted")
except ValueError as e:
    assert "non-finite values in adapters" in str(e), e
enc = encode_adapters(up, "int8")
enc["fc2"]["B_scale"] = enc["fc2"]["B_scale"].at[0].set(-1.0)
try:
    agg.submit(upd(enc))
    raise SystemExit("a negative scale on the second device was accepted")
except ValueError as e:
    assert "scale in fc2.B_scale" in str(e), e
assert len(calls) == 3 and enc["fc2"]["B_scale"].devices() == {d1}
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0 and "OK" in res.stdout, res.stderr[-2000:]


def test_buffer_wire_byte_accounting():
    from repro.core import codec
    from repro.fl.comm import tree_bytes
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s), buffer_size=3)
    upds = [_encoded_update("int8"), _encoded_update("none", seed=44)]
    for u in upds:
        agg.submit(u)
    expect = sum(tree_bytes(u.adapters) + tree_bytes(u.base_trainable)
                 for u in upds)
    assert agg.buffer.total_wire_bytes() == expect
    assert agg.wire_bytes_received == expect
    agg.submit(_encoded_update("bf16", seed=45))    # 3rd arrival flushes
    assert len(agg.buffer) == 0 and agg.buffer.total_wire_bytes() == 0
    assert agg.wire_bytes_received > expect         # lifetime counter


# ----------------------------------------------------- bf16 accumulators ----
def _fold_many(accum, seed, n_folds=100, beta=0.0):
    adapters, ranks, w, bases = hetero_cohort(10, seed=5, with_bases=True)
    s = get_strategy("rbla")
    agg = AsyncAggregator(s, make_state(s), accum_dtype=accum, seed=seed,
                          server_momentum=beta)
    for i in range(n_folds):
        j = i % len(ranks)
        agg.submit(ClientUpdate(adapters=adapters[j],
                                base_trainable=bases[j],
                                n_examples=float(w[j]), rank=int(ranks[j])))
    return agg


def test_bf16_accumulator_deterministic_under_fixed_seed():
    a = _fold_many("bfloat16", seed=7, n_folds=20)
    b = _fold_many("bfloat16", seed=7, n_folds=20)
    for x, y in zip(jax.tree.leaves(a.state.adapters),
                    jax.tree.leaves(b.state.adapters)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    assert jnp.asarray(a.state.adapters["fc1"]["A"]).dtype == jnp.bfloat16
    c = _fold_many("bfloat16", seed=8, n_folds=20)
    diff = max(float(jnp.max(jnp.abs(
        jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32))))
        for x, y in zip(jax.tree.leaves(a.state.adapters),
                        jax.tree.leaves(c.state.adapters)))
    assert diff > 0.0        # the noise really is seeded, not constant


@pytest.mark.parametrize("beta", [0.0, 0.9])
def test_bf16_accumulator_100_fold_drift_regression(beta):
    """100 folds (with and without server momentum) in bf16 storage with
    stochastic rounding must track the fp32 run: SR errors are unbiased,
    so drift grows like a sqrt(n)-step random walk of half-ulp steps,
    nowhere near the linear pile-up of round-to-nearest."""
    fp32 = _fold_many(None, seed=0, n_folds=100, beta=beta)
    bf16 = _fold_many("bfloat16", seed=0, n_folds=100, beta=beta)
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(fp32.state.adapters),
                    jax.tree.leaves(bf16.state.adapters)):
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            continue
        d = jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32)
        num += float(jnp.sum(d * d))
        den += float(jnp.sum(jnp.asarray(x, jnp.float32) ** 2))
    rel = (num / max(den, 1e-30)) ** 0.5
    # ~sqrt(100) * 2^-9 ~ 2% if every fold moved every value a half-ulp;
    # well under 5%, vs ~100 * 2^-9 ~ 20% for a biased rounder
    assert rel < 0.05, f"beta={beta}: bf16 accumulator drifted {rel:.3f}"
    assert fp32.n_folded == bf16.n_folded == 100
    # masses are denominators and must never be quantized
    assert bf16._fold_state.row_mass is None or all(
        jnp.asarray(v).dtype == jnp.float32
        for v in jax.tree.leaves(bf16._fold_state.row_mass))


# ------------------------------------------------------- server momentum ----
def test_server_momentum_zero_is_exact_noop():
    s = get_strategy("rbla")
    upd = _one_update()
    plain = AsyncAggregator(s, make_state(s))
    mom = AsyncAggregator(s, make_state(s), server_momentum=0.0)
    for _ in range(3):
        plain.submit(upd)
        mom.submit(upd)
    np.testing.assert_array_equal(
        np.asarray(plain.state.adapters["fc1"]["A"]),
        np.asarray(mom.state.adapters["fc1"]["A"]))


def test_server_momentum_accelerates_a_consistent_direction():
    """Folding the same update repeatedly: momentum accumulates the
    per-fold displacement, so the published state moves further toward
    the (consistent) client than the momentum-free service."""
    s = get_strategy("rbla")
    upd = _one_update()
    start = np.asarray(make_state(s).adapters["fc1"]["A"])

    def run(beta):
        agg = AsyncAggregator(s, make_state(s), server_momentum=beta)
        for _ in range(4):
            agg.submit(upd)
        out = np.asarray(agg.state.adapters["fc1"]["A"])
        assert np.isfinite(out).all()
        return float(np.linalg.norm(out - start))
    assert run(0.5) > run(0.0)


def test_server_momentum_buffer_survives_semiasync_reanchor():
    s = get_strategy("rbla")
    upd = _one_update()
    agg = AsyncAggregator(s, make_state(s), buffer_size=2,
                          server_momentum=0.5)
    agg.submit(upd)
    agg.submit(upd)                              # flush + re-anchor
    assert agg.n_flushes == 1
    assert agg._fold_state.momentum is not None
    m0 = np.asarray(agg._fold_state.momentum["fc1"]["A"])
    agg.submit(upd)
    agg.submit(upd)
    m1 = np.asarray(agg._fold_state.momentum["fc1"]["A"])
    assert not np.array_equal(m0, m1)            # still accumulating


def test_server_momentum_requires_fixed_rank_contract():
    s = configured("flora")
    with pytest.raises(ValueError, match="fixed-rank"):
        AsyncAggregator(s, make_state(s), server_momentum=0.5)
    with pytest.raises(ValueError, match="server_momentum"):
        AsyncAggregator(get_strategy("rbla"), make_state(get_strategy(
            "rbla")), server_momentum=1.5)


@pytest.mark.parametrize("method", ["rbla_clipped", "rbla_trimmed",
                                    "rbla_median"])
def test_robust_strategies_use_exact_replay_path(method):
    """Robust reductions are not running means: the service must replay
    them (supports_incremental=False), keeping sequential folds exactly
    equal to the one-shot aggregate (the parity gate above)."""
    s = get_strategy(method)
    assert not s.supports_incremental
    adapters, ranks, w, bases = hetero_cohort(3, seed=47, with_bases=True)
    agg = AsyncAggregator(s, make_state(s))
    for i in range(3):
        agg.submit(ClientUpdate(adapters=adapters[i],
                                base_trainable=bases[i],
                                n_examples=float(w[i]),
                                rank=int(ranks[i])))
    assert agg.n_folded == 3 and len(agg._replay) == 3


def test_async_simulation_wall_clock_smoke_and_determinism():
    cfg = AsyncFLConfig(method="rbla", staleness="polynomial",
                        staleness_clock="wall", staleness_a=0.3,
                        **ASYNC_SMOKE_KW)
    h = run_async_simulation(cfg)
    assert len(h.test_acc) == 2
    assert np.isfinite(h.train_loss).all()
    assert all(t >= 0 for t in h.mean_staleness)
    # wall staleness is measured in simulated seconds since pull, so it
    # tracks the latency distribution (order ~ the 1s median), not folds
    h2 = run_async_simulation(cfg)
    assert h.test_acc == h2.test_acc


# ------------------------------------------------------- semi-async buffer --
def test_update_buffer_flushes_on_size_and_deadline():
    buf = UpdateBuffer(size=3, deadline=5.0)
    buf.add("u1", weight=1.0, now=0.0)
    assert not buf.due(now=1.0)
    buf.add("u2", weight=1.0, now=1.0)
    assert not buf.due(now=2.0)
    assert buf.due(now=5.0)                  # oldest waited >= deadline
    buf.add("u3", weight=1.0, now=2.0)
    assert buf.due(now=2.0)                  # size reached
    items = buf.pop()
    assert [b.update for b in items] == ["u1", "u2", "u3"]
    assert len(buf) == 0 and not buf.due(now=100.0)
    with pytest.raises(ValueError, match="size"):
        UpdateBuffer(size=0)
    with pytest.raises(ValueError, match="deadline"):
        UpdateBuffer(size=2, deadline=-1.0)


def test_semiasync_single_flush_is_one_sync_round():
    """buffer_size == cohort size, zero staleness: the one flush must be
    exactly the classic synchronous aggregate."""
    s = get_strategy("rbla")
    adapters, ranks, w, bases = hetero_cohort(4, seed=17, with_bases=True)
    updates = [ClientUpdate(adapters=adapters[i], base_trainable=bases[i],
                            n_examples=float(w[i]), rank=int(ranks[i]))
               for i in range(len(ranks))]
    want = s.aggregate(make_state(s), updates, weights=w, backend="ref")
    agg = AsyncAggregator(s, make_state(s), buffer_size=len(updates),
                          backend="ref")
    for u in updates[:-1]:
        assert not agg.submit(u)             # buffering, no state change
        assert agg.version == 0
    assert agg.submit(updates[-1])           # K reached -> flush
    assert agg.version == 1 and agg.n_flushes == 1
    assert_trees_close(agg.state.adapters, want.adapters, 1e-6, 1e-7)


def test_replay_window_reanchors():
    """Non-incremental strategies re-anchor after replay_window folds and
    keep folding from the accumulated state (bounded memory)."""
    s = configured("flora")
    adapters, ranks, w, bases = hetero_cohort(5, seed=19, r_lo=1, r_hi=2, with_bases=True)
    agg = AsyncAggregator(s, make_state(s), replay_window=2)
    for i in range(len(ranks)):
        agg.submit(ClientUpdate(adapters=adapters[i],
                                base_trainable=bases[i],
                                n_examples=float(w[i]),
                                rank=int(ranks[i])))
    assert agg.n_folded == 5 and agg.version == 5
    assert len(agg._replay) <= 2
    assert np.isfinite(np.asarray(agg.state.adapters["fc1"]["A"])).all()


# --------------------------------------------------- event-driven simulator --
ASYNC_SMOKE_KW = dict(dataset="mnist", model="mlp", rounds=2, n_clients=3,
                      n_per_class=12, n_test_per_class=6, batch_size=16,
                      r_max=4, lr=0.01, seed=42)


@pytest.mark.parametrize("method", ["rbla", "zeropad", "flora", "fft"])
def test_async_simulation_smoke_and_determinism(method):
    extra = {"stack_r_cap": 16} if method == "flora" else {}
    cfg = AsyncFLConfig(method=method, staleness="polynomial", **extra,
                        **ASYNC_SMOKE_KW)
    h = run_async_simulation(cfg)
    assert len(h.test_acc) == 2              # rounds * n_clients uploads,
    assert len(h.sim_time_s) == 2            # eval every n_clients
    assert np.isfinite(h.train_loss).all()
    assert all(0.0 <= a <= 1.0 for a in h.test_acc)
    assert all(t >= 0 for t in h.mean_staleness)
    assert h.sim_time_s == sorted(h.sim_time_s)
    h2 = run_async_simulation(cfg)
    assert h.test_acc == h2.test_acc, "same seed must be bit-identical"


def test_async_vs_sync_same_config_both_learn():
    """Async folding with a straggler distribution must not wreck the
     3-round tiny run the sync path survives (same budget of uploads)."""
    sync = run_simulation(FLConfig(method="rbla", **ASYNC_SMOKE_KW))
    async_h = run_async_simulation(
        AsyncFLConfig(method="rbla", straggler_sigma=1.5,
                      **ASYNC_SMOKE_KW))
    assert np.isfinite(async_h.train_loss).all()
    assert async_h.test_acc[-1] >= sync.test_acc[-1] - 0.25


def test_client_latency_model_straggler_tail_and_determinism():
    from repro.fl import ClientLatencyModel
    lat = ClientLatencyModel(8, median_s=1.0, sigma=0.25,
                             straggler_sigma=1.0, seed=0)
    lat2 = ClientLatencyModel(8, median_s=1.0, sigma=0.25,
                              straggler_sigma=1.0, seed=0)
    draws = [lat.sample(i) for i in range(8)]
    assert draws == [lat2.sample(i) for i in range(8)]   # per-client streams
    assert all(d > 0 for d in draws)
    med = lat.client_median_s
    assert med.max() / med.min() > 2.0       # heterogeneity is real
