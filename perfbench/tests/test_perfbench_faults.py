"""A whole run of each loop on the CPU at a tiny size, past the harness's
look for a chip: sound runs come out correct, the bfloat16 control reads
above every limit, and a timed path broken underneath (a state left
unchanged, half the cohort left out, an altered answer, the previous
global lost) comes out not correct.  The traffic files and their limits are the committed ones;
only the widths, the depth and the pool are cut."""
import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402

D, F = 128, 256
TINY = {
    "hidden_size": D, "num_hidden_layers": 1,
    "adapter": {"arch": "h2o-danube-3-4b", "layers_key": "num_hidden_layers",
                "r_max": 8,
                "targets": {"mix/q": [D, D], "mix/k": [64, D],
                            "mix/v": [64, D], "mix/o": [D, D],
                            "ffn/gate": [F, D], "ffn/up": [F, D],
                            "ffn/down": [D, F]}},
    "program_overrides": {"d_model": D, "n_heads": 2, "n_kv_heads": 1,
                          "head_dim": 64, "d_ff": F},
}
TRAFFIC = {"sync": ("sync-rbla-f32.n32", {"cohort": 8}),
           "async": ("async-rbla.ring64", {"ring": 8}),
           # no client at r_max: the top rows keep the chained global
           "sync-retain": ("sync-rbla-f32.n24-retain", {"cohort": 8})}
MODES = list(TRAFFIC)


def tiny_cell(mode):
    name, cut = TRAFFIC[mode]
    traffic = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    traffic.update(cut)
    e2e = ["uploads_per_s", "setup_s",
           "round_ms_p95" if traffic["mode"] == "sync" else "fold_ms_p95"]
    return harness.Cell(name=f"tiny-{mode}", chips=1, config=TINY,
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


def _sync_fault(kind):
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
        pair = out["stages"][0]["b0"]["mix/q"]
        pair["A"] = pair["A"].at[0, 0, 0].add(1.0)
        return out
    return AggregationStrategy, "aggregate_adapters", broken


def _async_fault(kind):
    from repro.fl import AsyncAggregator
    real = AsyncAggregator.submit
    calls = []

    def broken(self, update, **kw):
        calls.append(1)
        if kind == "state_unchanged" or (kind == "half_batch"
                                         and len(calls) % 2):
            return True
        advanced = real(self, update, **kw)
        pair = self.state.adapters["stages"][0]["b0"]["mix/q"]
        pair["B"] = pair["B"].at[0, 0, 0].add(1.0)
        return advanced
    return AsyncAggregator, "submit", broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
@pytest.mark.parametrize("mode", MODES)
def test_broken_timed_path_is_not_correct(mode, kind, monkeypatch):
    cls, attr, broken = (_async_fault if mode == "async"
                         else _sync_fault)(kind)
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
