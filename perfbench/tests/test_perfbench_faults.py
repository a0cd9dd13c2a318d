"""A whole run of each loop on the CPU at a tiny size, past the harness's
look for a chip: sound runs come out correct, the bfloat16 control reads
above every limit, and a timed path broken underneath (a state left
unchanged, half the cohort left out, an altered answer, the previous
global lost) comes out not correct.  The traffic files and their limits
are the committed ones; only the widths, the depth and the pool are cut.
The ``moe-`` cases run a two-stage MLA + MoE tree, whose expert pairs
carry an expert axis unlike the depth, through the same loops."""
import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from tiny_configs import TINY, TINY_MOE  # noqa: E402

DENSE_PROBE = (("stages", 0, "b0", "mix/q"), (0, 0, 0))
# the last expert of the last MoE layer
MOE_PROBE = (("stages", 1, "b0", "ffn/experts/gate"), (3, 7, 0, 0))
#: case -> (config, traffic file, its cut, the pair and entry a fault alters)
TRAFFIC = {"sync": (TINY, "sync-rbla-f32.n32", {"cohort": 8}, DENSE_PROBE),
           "async": (TINY, "async-rbla.ring64", {"ring": 8}, DENSE_PROBE),
           # no client at r_max: the top rows keep the chained global
           "sync-retain": (TINY, "sync-rbla-f32.n24-retain", {"cohort": 8},
                           DENSE_PROBE),
           "moe-sync": (TINY_MOE, "sync-rbla-f32.n32", {"cohort": 8},
                        MOE_PROBE),
           "moe-async": (TINY_MOE, "async-rbla.ring64", {"ring": 8},
                         MOE_PROBE)}
MODES = list(TRAFFIC)


def tiny_cell(mode):
    config, name, cut, _ = TRAFFIC[mode]
    traffic = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    traffic.update(cut)
    family = harness.load_traffic(traffic)[1]
    e2e = ["uploads_per_s", "setup_s",
           "round_ms_p95" if family == "sync" else "fold_ms_p95"]
    return harness.Cell(name=f"tiny-{mode}", chips=1, config=config,
                        traffic=traffic,
                        end_to_end=[{"name": n, "unit": "u"} for n in e2e],
                        per_layer=[])


def run(mode, control=False):
    return harness.run(f"tiny-{mode}", 2 ** 33 + 17, 0.3, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       cell=tiny_cell(mode), control=control)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


@pytest.mark.parametrize("mode", MODES)
def test_sound_run_is_correct_and_the_control_is_not(mode):
    result, lines, control = run(mode, control=True)
    assert result["correct"], lines
    assert set(result["metrics"]) == {m["name"] for m in
                                      tiny_cell(mode).end_to_end}
    limit = result["checks"]["answer_gap"]["limit"]
    assert result["checks"]["answer_gap"]["value"] < limit
    assert max(g for _, g, _ in control) > limit
    assert list(result)[-1] == "checks"


def _alter(adapters, probe, side):
    """Add 1 to one entry of one pair side, where it is produced."""
    path, index = probe
    pair = adapters
    for k in path:
        pair = pair[k]
    pair[side] = pair[side].at[index].add(1.0)


def _sync_fault(kind, probe=DENSE_PROBE):
    import jax
    import jax.numpy as jnp
    from repro.core.strategy import AggregationStrategy
    real = AggregationStrategy.aggregate_adapters

    def broken(self, uploads, weights, **kw):
        if kind == "state_unchanged":
            return kw["prev_global"]
        if kind == "prev_dropped":
            kw["prev_global"] = None
        if kind == "prev_zeroed":
            kw["prev_global"] = jax.tree.map(jnp.zeros_like,
                                             kw["prev_global"])
        if kind == "half_batch":
            h = len(uploads) // 2
            kw["client_ranks"] = kw["client_ranks"][:h]
            return real(self, uploads[:h], weights[:h], **kw)
        out = real(self, uploads, weights, **kw)
        _alter(out, probe, "A")
        return out
    return AggregationStrategy, "aggregate_adapters", broken


def _async_fault(kind, probe):
    from repro.fl import AsyncAggregator
    real = AsyncAggregator.submit
    calls = []

    def broken(self, update, **kw):
        calls.append(1)
        if kind == "state_unchanged" or (kind == "half_batch"
                                         and len(calls) % 2):
            return True
        advanced = real(self, update, **kw)
        _alter(self.state.adapters, probe, "B")
        return advanced
    return AsyncAggregator, "submit", broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
@pytest.mark.parametrize("mode", MODES)
def test_broken_timed_path_is_not_correct(mode, kind, monkeypatch):
    family = harness.load_traffic(tiny_cell(mode).traffic)[1]
    cls, attr, broken = (_async_fault if family == "async"
                         else _sync_fault)(kind, TRAFFIC[mode][3])
    monkeypatch.setattr(cls, attr, broken)
    result, lines, _ = run(mode)
    assert not result["correct"], lines
    gap = result["checks"]["answer_gap"]
    assert gap["value"] > gap["limit"], lines


@pytest.mark.parametrize("kind", ["prev_dropped", "prev_zeroed"])
def test_a_round_that_loses_the_previous_global_is_not_correct(kind,
                                                              monkeypatch):
    """Rows no client of the cohort owns must keep the chained global."""
    monkeypatch.setattr(*_sync_fault(kind))
    result, lines, _ = run("sync-retain")
    assert not result["correct"], lines
    gap = result["checks"]["answer_gap"]
    assert gap["value"] > gap["limit"], lines
