"""The benchmark's yardstick on the CPU: the trace reduction on small
hand-built traces, the necessary-work functions, and BENCHMARK.json's
wiring to its files.  Nothing here touches a TPU."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

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
    got = work.round_work(WIDTHS, 3, 8, ranks, codec)
    assert got["bytes"] == by_hand(ranks, 3, wire, scales)


@pytest.mark.parametrize("kind", ["round", "fold"])
def test_padding_rows_add_no_bytes(kind):
    """The same live ranks stored at a larger r_max need the same work."""
    if kind == "round":
        small = work.round_work(WIDTHS, 2, 8, [2, 4], "none")
        large = work.round_work(WIDTHS, 2, 64, [2, 4], "none")
    else:
        small = work.fold_work(WIDTHS, 2, 8, 4, "none")
        large = work.fold_work(WIDTHS, 2, 64, 4, "none")
    assert small == large


def test_fold_bytes_read_the_upload_and_read_and_write_the_state():
    got = work.fold_work({"q": (96, 64)}, 2, 8, 4, "none")
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


def test_configs_state_published_widths_and_only_cut_depth():
    for c in bench()["configs"]:
        cfg = json.loads((HERE.parent / c["file"]).read_text())
        assert c["reduced"] == [cfg["adapter"]["layers_key"]]
        assert set(cfg["reduced"]) == set(c["reduced"])
        published = cfg["reduced"][c["reduced"][0]]["published"]
        assert published % cfg[cfg["adapter"]["layers_key"]] == 0
        for fo, fi in cfg["adapter"]["targets"].values():
            assert isinstance(fo, int) and isinstance(fi, int)
            assert fo > 0 and fi > 0
