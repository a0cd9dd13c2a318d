"""The benchmark's yardstick on the CPU: the trace reduction on small
hand-built traces, the necessary-work functions, and BENCHMARK.json's
wiring to its files.  Nothing here touches a TPU."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import gen  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


KERNEL = ('%combine_fn.4 = f32[64,128]{1,0} custom-call(f32[8]{0} %w), '
          'custom_call_target="tpu_custom_call"')


def op(name, start, dur, plane=DEV):
    return tracing.Event(plane, tracing.OPS_LINE, name, start, dur)


def prog(name, start, dur, plane=DEV):
    """A program run on the device; ops inside its interval belong to it."""
    return tracing.Event(plane, tracing.MODULES_LINE,
                         f"{name}(1234567)", start, dur)


def ann(name, start, dur):
    return tracing.Event(HOST, "python", "pb." + name, start, dur)


def small_trace():
    """A 100 ns window: ops at [10, 30) and [20, 40) overlap, [60, 70)
    stands alone, [90, 120) runs past the window's end and [-10, 5)
    starts before it."""
    return [
        ann("window", 0, 100),
        ann("call", 0, 50), ann("block", 50, 30), ann("generate", 80, 20),
        prog("jit_stack", -10, 15), op("%early = f32[8] copy(f32[8] %a)",
                                       -10, 15),
        prog("jit_combine_fn", 10, 20), op(KERNEL, 10, 20),
        prog("jit_pack_fn", 20, 20), op("%fusion.3 = f32[8] fusion()", 20,
                                       20),
        prog("jit_combine_fn", 60, 10), op(KERNEL, 60, 10),
        prog("jit_stack", 90, 30), op("%copy.1 = f32[8] copy(f32[8] %b)",
                                      90, 30),
    ]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = tracing.reduce(small_trace())
    # [0, 5) + [10, 40) + [60, 70) + [90, 100)
    assert s["busy_s"] == pytest.approx(55e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["n_chips"] == 1


def test_kernel_and_module_time_are_sums_of_their_events():
    s = tracing.reduce(small_trace())
    sec, n = tracing.op_seconds(s, "combine_fn", tracing.PALLAS_KERNEL)
    assert (sec, n) == (pytest.approx(30e-9), 2)
    assert tracing.module_seconds(s, "pack_fn|stack") == pytest.approx(
        (20 + 10 + 5) * 1e-9)


def test_a_kernel_is_the_mosaic_custom_call_of_its_program():
    other = ('%custom-call.9 = f32[6,64]{1,0} custom-call(f32[2,64]{1,0} '
             '%s), custom_call_target="ConcatBitcast"')
    ev = [ann("window", 0, 100), prog("jit_fold_fn", 0, 60),
          op(KERNEL, 0, 40), op(other, 40, 10)]
    s = tracing.reduce(ev)
    assert tracing.op_seconds(s, "fold_fn", tracing.PALLAS_KERNEL) == (
        pytest.approx(40e-9), 1)
    assert tracing.op_seconds(s, "combine_fn", tracing.PALLAS_KERNEL)[1] == 0


def test_an_op_belongs_to_the_program_it_starts_in():
    s = tracing.reduce([ann("window", 0, 100), prog("jit_a", 0, 10),
                        op("%x = f32[] copy()", 5, 6),
                        op("%y = f32[] copy()", 20, 10)])
    assert s["modules"] == {"jit_a": pytest.approx(6e-9),
                            "": pytest.approx(10e-9)}
    assert tracing.short_op(KERNEL) == "combine_fn.4 custom-call"


def test_idle_time_is_split_over_the_host_phases_that_overlap_it():
    s = tracing.reduce(small_trace())
    idle = s["idle_by_phase"]
    # gaps [5, 10), [40, 50) under call; [50, 60), [70, 80) under block;
    # [80, 90) under generate
    assert idle["call"] == pytest.approx(15e-9)
    assert idle["block"] == pytest.approx(20e-9)
    assert idle["generate"] == pytest.approx(10e-9)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_idle_time_outside_every_phase_is_other():
    ev = [ann("window", 0, 100), ann("call", 0, 40), op("k", 0, 10)]
    idle = tracing.reduce(ev)["idle_by_phase"]
    assert idle == {"call": pytest.approx(30e-9),
                    "other": pytest.approx(60e-9)}


def test_busy_is_averaged_over_chips():
    ev = [ann("window", 0, 100), op("k", 0, 40),
          op("k", 0, 20, plane="/device:TPU:1")]
    assert tracing.reduce(ev)["busy_s"] == pytest.approx(30e-9)


def test_breakdown_keeps_the_largest_ten_entries():
    ev = [ann("window", 0, 1000), prog("jit_combine_fn", 0, 1000)] + [
        op(f"%op{i} = f32[] add()", 10 * i, i) for i in range(1, 15)]
    b = tracing.breakdown(tracing.reduce(ev))
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0] == ["jit_combine_fn/op14 add",
                                  pytest.approx(14e-9)]
    json.dumps(b)


def test_a_trace_without_one_window_is_refused():
    with pytest.raises(RuntimeError, match="pb.window"):
        tracing.reduce([op("k", 0, 10)])


# ------------------------------------------------------------------ work --
WIDTHS = {"q": (96, 64), "down": (64, 160)}       # fan_out, fan_in


def pairs(layers):
    """``(fan_out, fan_in, lead)`` of WIDTHS' pairs at ``layers`` deep."""
    return [(fo, fi, (layers,)) for fo, fi in WIDTHS.values()]


def by_hand(ranks, layers, wire, scales):
    """Live rows x row width per side, counted pair by pair."""
    nbytes = 0
    for fo, fi in WIDTHS.values():
        for width in (fi, fo):
            for k in ranks:
                nbytes += layers * k * (width * wire + 4 * scales)
            nbytes += layers * max(ranks) * width * 4
    return nbytes


@pytest.mark.parametrize("codec,wire,scales", [("none", 4, 0),
                                               ("int8", 1, 1)])
def test_round_bytes_count_live_rows_of_a_mixed_rank_cohort(codec, wire,
                                                            scales):
    ranks = [2, 2, 4, 8]
    got = work.round_work(pairs(3), 8, ranks, codec)
    assert got["bytes"] == by_hand(ranks, 3, wire, scales)


@pytest.mark.parametrize("kind", ["round", "fold"])
def test_padding_rows_add_no_bytes(kind):
    """The same live ranks stored at a larger r_max need the same work."""
    if kind == "round":
        small = work.round_work(pairs(2), 8, [2, 4], "none")
        large = work.round_work(pairs(2), 64, [2, 4], "none")
    else:
        small = work.fold_work(pairs(2), 8, 4, "none")
        large = work.fold_work(pairs(2), 64, 4, "none")
    assert small == large


def test_fold_bytes_read_the_upload_and_read_and_write_the_state():
    got = work.fold_work([(96, 64, (2,))], 8, 4, "none")
    rows = 2 * 4
    assert got["bytes"] == rows * (64 + 96) * 12 + 2 * rows * 8
    assert got["flops"] == rows * (64 + 96) * 3


def test_least_time_is_bound_by_the_larger_term():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"flops": 50, "bytes": 20}, peaks,
                              "bf16_flops_per_s") == 2.0
    assert work.least_seconds({"flops": 500, "bytes": 20}, peaks,
                              "bf16_flops_per_s") == 5.0


# ------------------------------------------------------- BENCHMARK.json --
def bench():
    return json.loads((HERE.parent / "BENCHMARK.json").read_text())


def test_every_cell_resolves_to_its_files():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        assert (HERE.parent / configs[w["config"]]["file"]).is_file()
        t = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        harness.load_traffic(t)         # its loop and its reference
        assert t["limits"]["answer_gap"] > 0


def traffic(name="sync-rbla-f32.n32"):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("key,value", [("backend", "distributed"),
                                       ("buffer_size", 10)])
def test_a_knob_the_loop_does_not_read_is_refused(key, value):
    t = traffic("sync-rbla-f32.n32" if key == "backend"
                else "async-rbla.ring64")
    t[key] = value
    with pytest.raises(ValueError, match=key):
        harness.load_traffic(t)


@pytest.mark.parametrize("key,value", [("mode", "open_loop"),
                                       ("strategy", "flora")])
def test_a_mode_or_strategy_without_its_file_is_refused(key, value):
    t = traffic()
    t[key] = value
    with pytest.raises(ValueError, match=value):
        harness.load_traffic(t)


@pytest.mark.parametrize("family", [None, "open_loop"])
def test_a_mode_without_a_family_is_refused(family, monkeypatch):
    real = harness.load_module

    def loaded(kind, name):
        mod = real(kind, name)
        if kind == "modes":
            del mod.FAMILY
            if family is not None:
                mod.FAMILY = family
        return mod
    monkeypatch.setattr(harness, "load_module", loaded)
    with pytest.raises(ValueError, match="declares no family"):
        harness.load_traffic(traffic())


def family_run(family):
    """A run of a mode no reader names, of ``family``, with something for
    every family-keyed reader to read."""
    ev = [ann("window", 0, 1000), prog("jit_pack_fn", 0, 100),
          op("%fusion.1 = f32[8] fusion()", 0, 100),
          prog("jit_combine_fn", 200, 100), op(KERNEL, 200, 100),
          prog("jit_fold_fn", 400, 100), op(KERNEL, 400, 100)]
    cell = harness.Cell(name="c", chips=1, config={},
                        traffic={"mode": f"{family}_new"},
                        end_to_end=[], per_layer=[])
    return harness.Run(cell=cell, seed=0, family=family, window_s=1.0,
                       steps=2, units=2, latency_ms=[3.0, 5.0],
                       host_ms=[1.0, 2.0], work={"bytes": 100, "flops": 1},
                       counters={"submit_sum_s": 0.5, "submit_count": 2},
                       trace=tracing.reduce(ev),
                       peaks={"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e9})


@pytest.mark.parametrize("name,family", [
    ("round_ms_p95", "sync"), ("host_ms.sync", "sync"),
    ("staging_ms.sync", "sync"), ("packed_agg_roofline", "sync"),
    ("fold_ms_p95", "async"), ("axpy_fold_roofline", "async"),
    ("ingest_ms.async", "async")])
def test_readers_key_to_the_loop_family_not_the_mode_name(name, family):
    read = harness.load_reader(name)
    other = {"sync": "async", "async": "sync"}[family]
    assert read(family_run(family)) is not None
    assert read(family_run(other)) is None


def test_every_metric_has_a_reader_and_every_cell_enough_metrics():
    b = bench()
    for m in b["end_to_end"] + b["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for w in b["workloads"]:
        def listed(m):
            return "workloads" not in m or w["name"] in m["workloads"]
        e2e = [m["name"] for m in b["end_to_end"] if listed(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(listed(m) for m in b["per_layer"])
        for m in b["per_layer"]:
            if listed(m):
                assert m["moves"] in e2e


def check_config(entry: dict, cfg: dict):
    """A configuration states published widths and makes only the cuts
    of model-configs section 4, each with ``published``, ``here`` and
    ``why``: the depth of a stage, the routed experts held (a key that
    names an expert axis under a stage's ``lead``), a vocabulary slice.
    What is left keeps the floors: whole periods of each stage's layer
    pattern, at least 4 layers after the leading dense ones (the stages
    before the first with an expert axis), at least 8 experts held, at
    least an eighth of the vocabulary; a model of one stage holds an
    even share of its published depth.  ``program_overrides`` may set the
    program's architecture only to such a cut (``n_experts`` to the
    experts held), never to a width: the pairs are held to the stated
    widths through the program, and an override would let both shrink."""
    reduced = cfg["reduced"]
    assert set(entry["reduced"]) == set(reduced)
    stages = gen.config_stages(cfg)
    depth_keys = {k for st in stages for k in st["depth"]}
    expert_keys = {k for st in stages
                   for axes in st.get("lead", {}).values() for k in axes}
    vocab_keys = {k for k in cfg if "vocab" in k}
    for key, cut in reduced.items():
        assert key in depth_keys | expert_keys | vocab_keys, (
            f"{key} is no cut of depth, experts held or vocabulary")
        assert {"published", "here", "why"} <= set(cut), key
        assert isinstance(cut["why"], str) and cut["why"], key
        assert isinstance(cut["published"], int), key
        assert cfg[key] == cut["here"], key
        assert 0 < cut["here"] < cut["published"], key
    published = {k: reduced[k]["published"] if k in reduced else cfg[k]
                 for k in depth_keys | expert_keys}
    depths = [gen.stage_depth(st["depth"], cfg) for st in stages]
    full = [gen.stage_depth(st["depth"], published) for st in stages]
    gen.program_layout(cfg)               # whole periods, every pair
    if len(stages) == 1:
        assert full[0] % depths[0] == 0
    lead = next((i for i, st in enumerate(stages) if st.get("lead")), 0)
    assert sum(depths[lead:]) >= min(4, sum(full[lead:]))
    for k in expert_keys:
        assert cfg[k] >= min(8, published[k]), k
    for k in vocab_keys & set(reduced):
        assert 8 * cfg[k] >= reduced[k]["published"], k
    for key, value in cfg.get("program_overrides", {}).items():
        held = {cfg[k] for k in expert_keys & set(reduced)}
        assert key == "n_experts" and held == {value}, (
            f"program_overrides {key}={value} is no cut of the experts held")
    for st in stages:
        for fo, fi in st["targets"].values():
            assert isinstance(fo, int) and isinstance(fi, int)
            assert fo > 0 and fi > 0


def test_configs_state_published_widths_and_only_cut_depth():
    """A file in the one-stage form (``layers_key``) is a dense model whose
    one cut is its depth; every other cut needs the ``stages`` form."""
    for c in bench()["configs"]:
        cfg = json.loads((HERE.parent / c["file"]).read_text())
        if "stages" not in cfg["adapter"]:
            assert c["reduced"] == [cfg["adapter"]["layers_key"]]
        check_config(c, cfg)


def sizing():
    """DeepSeek-V3's one-chip share: depth, experts held, both cut."""
    cfg = json.loads((HERE / "tests" / "deepseek-v3.l5e8.json")
                     .read_text())
    return {"reduced": list(cfg["reduced"])}, cfg


def test_a_stage_and_expert_cut_within_the_floors_is_taken():
    check_config(*sizing())


def test_a_stages_form_configuration_in_benchmark_json_is_taken(monkeypatch):
    """The conformance test itself takes a BENCHMARK.json that lists the
    DeepSeek-V3 share beside the committed configurations."""
    b = bench()
    entry, cfg = sizing()
    b["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": "perfbench/tests/deepseek-v3.l5e8.json",
                         "reduced": entry["reduced"],
                         "why": "one chip's share of the experts and layers"})
    monkeypatch.setitem(globals(), "bench", lambda: b)
    test_configs_state_published_widths_and_only_cut_depth()


def _cut(key, here, published=None, why="a cut"):
    def edit(entry, cfg):
        cfg[key] = here
        cfg["reduced"][key] = {"published": published or cfg[key],
                               "here": here, "why": why}
        entry["reduced"].append(key)
    return edit


def _experts(n):
    def edit(entry, cfg):
        cfg["n_routed_experts"] = cfg["reduced"]["n_routed_experts"][
            "here"] = cfg["program_overrides"]["n_experts"] = n
    return edit


def _moe_layers(n):
    def edit(entry, cfg):
        cfg["num_hidden_layers"] = cfg["reduced"]["num_hidden_layers"][
            "here"] = 1 + n
    return edit


def _no_why(entry, cfg):
    del cfg["reduced"]["first_k_dense_replace"]["why"]


def _override_width(entry, cfg):
    """The routed and shared expert width cut through the program, the
    targets shrunk to match, the file's ``moe_intermediate_size`` left at
    its published value: the layout agrees, the override is refused."""
    cfg["program_overrides"]["moe_d_ff"] = 1024
    for st in cfg["adapter"]["stages"]:
        for t, (fo, fi) in st["targets"].items():
            if "experts" in t or "shared" in t:
                st["targets"][t] = [1024 if fo == 2048 else fo,
                                    1024 if fi == 2048 else fi]
    gen.program_layout(cfg)


@pytest.mark.parametrize("what,edit", [
    ("fewer than 8 experts held", _experts(4)),
    ("fewer than 4 layers after the dense one", _moe_layers(3)),
    ("less than an eighth of the vocabulary",
     _cut("vocab_size", 129280 // 16, published=129280)),
    ("a width cut", _cut("moe_intermediate_size", 1024, published=2048)),
    ("a cut without its reason", _no_why),
    ("a width cut through program_overrides", _override_width),
])
def test_a_cut_past_what_model_configs_allows_is_refused(what, edit):
    entry, cfg = sizing()
    edit(entry, cfg)
    with pytest.raises(AssertionError):
        check_config(entry, cfg)
