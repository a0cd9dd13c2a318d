"""The program's spans in the trace, on the CPU: the reduction's summary
does not move when they are in it, idle time goes to the innermost span
over it, and the host-sync reader reads the program's counter."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import spans  # noqa: E402
import tracing  # noqa: E402
from test_perfbench_yardstick import HOST, ann, op, small_trace  # noqa: E402


def span(stage, start, dur):
    return tracing.Event(HOST, "python", "obs." + stage, start, dur)


def test_program_spans_leave_the_summary_as_it_was():
    nested = small_trace() + [
        span("round", 1, 45), span("round.stack", 2, 20),
        span("round.spec", 25, 10), span("submit", 80, 15),
        span("submit.validate", 81, 10)]
    assert tracing.reduce(nested) == tracing.reduce(small_trace())


def test_idle_goes_to_the_innermost_span_then_to_the_phase():
    """Ops at [10, 20) and [60, 70); ``round.stack`` starts with its
    parent and ends first, so it is the inner one."""
    ev = [ann("window", 0, 100), ann("call", 0, 50), ann("block", 50, 30),
          ann("generate", 80, 20), op("k", 10, 10), op("k", 60, 10),
          span("round", 2, 46), span("round.stack", 2, 28),
          span("round.spec", 30, 10)]
    idle = spans.idle_by_span(ev)
    assert idle == {"call": pytest.approx(4e-9),            # [0,2) [48,50)
                    "call/round.stack": pytest.approx(18e-9),
                    "call/round.spec": pytest.approx(10e-9),
                    "call/round": pytest.approx(8e-9),
                    "block": pytest.approx(20e-9),
                    "generate": pytest.approx(20e-9)}
    s = tracing.reduce(ev)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_idle_outside_every_phase_is_other():
    ev = [ann("window", 0, 100), op("k", 0, 10), span("fold", 40, 20)]
    assert spans.idle_by_span(ev) == {"other": pytest.approx(70e-9),
                                      "other/fold": pytest.approx(20e-9)}


def test_the_split_of_a_small_trace_sums_to_its_idle_time():
    ev = small_trace() + [span("round", 1, 45), span("round.spec", 25, 10)]
    idle = spans.idle_by_span(ev)
    s = tracing.reduce(ev)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # the phases' own split is the same, spans only refine it
    by_phase = {}
    for key, sec in idle.items():
        by_phase[key.split("/")[0]] = by_phase.get(key.split("/")[0], 0) + sec
    assert by_phase == pytest.approx(s["idle_by_phase"])


def test_load_reads_the_program_spans_of_a_real_trace(tmp_path):
    import jax
    from repro.obs import span as obs_span
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.phase("window"):
            with obs_span("round", round=3):
                with obs_span("round.spec"):
                    pass
    finally:
        jax.profiler.stop_trace()
    ev = spans.load(tracing.find_xplane(str(tmp_path)))
    assert sorted(e.name for e in ev if e.name.startswith("obs.")) == [
        "obs.round", "obs.round.spec"]
    assert [e.name for e in ev if e.name.startswith("pb.")] == ["pb.window"]


def _run(mode, steps):
    traffic = ({"mode": "sync", "warmup_rounds": 2} if mode == "sync"
               else {"mode": "async", "warmup_folds": 4})
    cell = harness.Cell(name="c", chips=1, config={}, traffic=traffic,
                        end_to_end=[], per_layer=[])
    return harness.Run(cell=cell, seed=0, steps=steps)


@pytest.mark.parametrize("mode,warm", [("sync", 2), ("async", 4)])
def test_host_syncs_per_step_reads_the_counter_over_every_step(mode, warm):
    from repro.obs import get_registry, host_syncs
    read = harness.load_reader("host_syncs_per_step")
    with get_registry().scoped():
        host_syncs("cohort_spec").inc(14 * (warm + 6))
        assert read(_run(mode, 6)) == pytest.approx(14.0)
        assert read(_run(mode, 0)) is None


def test_host_syncs_per_step_is_silent_without_the_counter(monkeypatch):
    import repro.obs
    from repro.obs import MetricsRegistry
    monkeypatch.setattr(repro.obs, "get_registry", MetricsRegistry)
    assert harness.load_reader("host_syncs_per_step")(_run("sync", 6)) is None
