"""One run of one cell: set-up, the measured window, the check that
decides ``correct``, and the result line.

Everything that belongs to one cell is found by name: ``BENCHMARK.json``
names the cell's configuration file and traffic mix; the traffic's
``mode`` names the loop that drives the program (``modes/<mode>.py``),
its ``strategy`` the plain reference the answers are held to
(``references/<strategy>.py``), and each metric's value comes from
``metrics/<name>.py``.  A new cell, loop, reference or metric is a new
file.  Each mode declares the loop family it belongs to (``FAMILY``,
one of ``FAMILIES``), and readers test the family, never the mode's
name, so a new mode of a family reports that family's metrics.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path

import gen
import tracing

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


#: a traced run measures at most this long: the trace of a whole window
#: is hundreds of MB, and its per-layer shares settle within seconds
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: nothing is measured."""


# ---------------------------------------------------------------- cells ----
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _listed(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: dict | None = None,
              base: Path = CHECKOUT) -> Cell:
    bench = bench or json.loads((base / "BENCHMARK.json").read_text())
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise ValueError(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    (cfg,) = [c for c in bench["configs"] if c["name"] == wl["config"]]
    return Cell(
        name=workload, chips=int(wl["chips"]),
        config=json.loads((base / cfg["file"]).read_text()),
        traffic=json.loads(
            (HERE / "traffic" / f"{wl['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _listed(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _listed(m, workload)])


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark's directory, as a module."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind}/{name}.py in the benchmark")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(run) -> float | None``."""
    return load_module("metrics", name).read


#: loop families: ``sync`` runs rounds over a cohort, ``async`` folds one
#: upload at a time
FAMILIES = ("sync", "async")


def load_traffic(traffic: dict):
    """The traffic's loop class, its family and its reference module.  A
    traffic file with a key its mode does not read is refused: a knob the
    loop ignores would claim a choice the benchmark does not honour.  So
    is a mode that declares no family: no reader would know it."""
    mode = load_module("modes", traffic["mode"])
    extra = set(traffic) - mode.KEYS
    if extra:
        raise ValueError(f"mode {traffic['mode']!r} reads no traffic key "
                         f"{sorted(extra)}")
    family = getattr(mode, "FAMILY", None)
    if family not in FAMILIES:
        raise ValueError(f"mode {traffic['mode']!r} declares no family: "
                         f"FAMILY is {family!r}, not one of {FAMILIES}")
    return mode.Loop, family, load_module("references", traffic["strategy"])


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: the smallest value with at
    least ``q`` of all values at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


# ------------------------------------------------------------- the runs ----
@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    seed: int
    family: str = ""            # the loop's family, one of FAMILIES
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0              # rounds or folds completed in the window
    units: int = 0              # uploads aggregated in the window
    latency_ms: list = dataclasses.field(default_factory=list)
    host_ms: list = dataclasses.field(default_factory=list)
    work: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None
    peaks: dict | None = None


class Reservoir:
    """A seeded uniform sample of ``size`` answers from a stream of
    unknown length.  Its slots are allocated before the window and each
    kept answer is copied into a slot's own memory (the slot is donated),
    so keeping answers allocates nothing inside the window and the
    device's memory layout does not depend on which rounds the seed
    picks."""

    def __init__(self, seed: int, size: int, blank):
        import jax
        import jax.numpy as jnp
        self.rng = gen.host_rng(seed, 2, 0)
        self.size, self.seen = size, 0
        copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        self.copy_into = jax.jit(lambda dst, src: copy(src),
                                 donate_argnums=(0,))
        # both programs compile here, in set-up, not in the window
        self.slots = [(None, self.copy_into(copy(blank), blank))
                      for _ in range(size)]
        jax.block_until_ready(self.slots)

    def offer(self, index: int, answer):
        if self.seen < self.size:
            j = self.seen
        else:
            j = int(self.rng.integers(0, self.seen + 1))
        if j < self.size:
            self.slots[j] = (index, self.copy_into(self.slots[j][1], answer))
        self.seen += 1

    @property
    def kept(self) -> list:
        return [s for s in self.slots if s[0] is not None]


# ------------------------------------------------------------ the window --
def _counters():
    """The program's own counters the checks and readers use."""
    from repro.obs import get_registry
    reg = get_registry()

    def total(name):
        inst = reg.get(name)
        return sum(inst.samples().values()) if inst is not None else 0.0
    spans = reg.get("obs_span_seconds")
    submit = spans.labels(stage="submit") if spans is not None else None
    return {"kernel_traces": total("kernel_traces_total"),
            "pack_reuses": total("plan_pack_reuses_total"),
            "submit_sum_s": submit.sum if submit is not None else 0.0,
            "submit_count": submit.count if submit is not None else 0}


class CompileWatch:
    """Counts jaxpr traces and backend compiles (cache loads included)
    while it is open."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcWatch:
    """Records the interpreter's garbage collections while it is open:
    ``(generation, start, end)`` on the ``perf_counter`` clock."""

    def __init__(self):
        self.pauses, self._t0 = [], 0.0

    def _on(self, phase_, info):
        if phase_ == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"], self._t0,
                                time.perf_counter()))

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


def stall_lines(lat, spans, pauses) -> list:
    """Where the window's slow steps went: the step-time quantiles, the
    collections by generation, and how many steps slower than twice the
    median held a full (generation 2) collection."""
    if not lat:
        return []
    med = nearest_rank(lat, 0.5)
    q = " ".join(f"p{int(p * 100)} {nearest_rank(lat, p):.1f}"
                 for p in (0.5, 0.9, 0.95, 0.99))
    out = [f"window steps: {len(lat)}, ms {q} max {max(lat):.1f}"]
    for g in (0, 1, 2):
        d = [1e3 * (e - s) for gen_, s, e in pauses if gen_ == g]
        if d:
            out.append(f"window gc gen{g}: {len(d)} collections, "
                       f"{sum(d):.1f} ms, longest {max(d):.1f} ms")
    full = [(s, e) for g, s, e in pauses if g == 2]
    slow = [(t0, t1) for (t0, t1), ms in zip(spans, lat) if ms > 2 * med]
    held = sum(any(s < t1 and e > t0 for s, e in full) for t0, t1 in slow)
    out.append(f"window slow steps (> 2x median): {len(slow)}, "
               f"{held} of them with a full collection")
    return out


def measure(loop, seconds: float, keep: Reservoir):
    """Run steps back to back until ``seconds`` have passed; returns the
    run's timings, each step's ``(start, end)`` and the kept answers
    (``keep``'s sample plus the last)."""
    import jax
    lat, host, spans, units = [], [], [], 0
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t0 = time.perf_counter()
        while True:
            s = time.perf_counter()
            ms, hms, n = loop.step()
            spans.append((s, time.perf_counter()))
            lat.append(ms)
            host.append(hms)
            units += n
            if time.perf_counter() - t0 >= seconds:
                break
            with tracing.phase("sample"):
                keep.offer(*loop.last())
        t1 = time.perf_counter()
    return t1 - t0, lat, host, spans, units, keep.kept + [loop.last()]


def enable_cache():
    """The program's persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), with every program in
    it however fast it compiled, so only a checkout's first run
    compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform}, not a TPU;"
                     " this benchmark measures nothing there")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, cell: Cell | None = None,
        control: bool = False):
    """One run.  Returns ``(result, check_lines, control_gaps)``: the
    result line's object, one line per compared number for standard
    error, and (with ``control``) the bfloat16 control's gaps on the
    same answers."""
    cell = cell or load_cell(workload)
    devs = check_device(cell.chips, require_tpu)
    import jax
    enable_cache()
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")

    t = cell.traffic
    loop_class, family, reference = load_traffic(t)
    loop = loop_class(cell, seed, reference)
    loop.warm()
    keep = Reservoir(seed, int(t["sampled"]), loop.last()[1])
    before = _counters()
    setup_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="pb-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with CompileWatch() as watch, GcWatch() as gcw:
            window_s, lat, host, spans, units, answers = measure(
                loop, min(seconds, TRACE_SECONDS) if trace else seconds,
                keep)
        if trace:
            jax.profiler.stop_trace()
        after = _counters()
        mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs[:cell.chips])
        run_ = Run(cell=cell, seed=seed, family=family, setup_s=setup_s,
                   window_s=window_s, steps=len(lat), units=units,
                   latency_ms=lat, host_ms=host,
                   work=loop.window_work(len(lat)),
                   counters={k: after[k] - before[k] for k in after},
                   peaks=peaks.get(kind))
        if trace:
            run_.trace = tracing.reduce(
                tracing.load(tracing.find_xplane(trace_dir)))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # the check: program state freed, then the reference from the seed
    mode_checks = loop.checks(before, after)
    loop.free()
    gc.collect()
    t_ref = time.perf_counter()
    import jax.numpy as jnp
    gaps = loop.reference(answers, jnp.float32)
    control_gaps = (loop.reference(answers, jnp.bfloat16) if control
                    else None)
    ref_s = time.perf_counter() - t_ref
    del answers

    limit = float(t["limits"]["answer_gap"])
    checks = {
        "answer_gap": (max(g for _, g, _ in gaps), limit),
        "rank_leaves_off": (sum(b for _, _, b in gaps), 0),
        "compiles_in_window": (watch.count, 0),
        "kernel_traces_in_window": (after["kernel_traces"]
                                    - before["kernel_traces"], 0),
        **mode_checks,
    }
    correct = all(v <= lim for v, lim in checks.values())

    metric_list = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metric_list:
        value = load_reader(m["name"])(run_)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": units, "failed": loop.failed,
              "metrics": metrics, "device": device}
    if run_.trace is not None:
        device["busy_s"] = run_.trace["busy_s"]
        device["window_s"] = run_.trace["window_s"]
        result["breakdown"] = tracing.breakdown(run_.trace)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    lines = stall_lines(lat, spans, gcw.pauses)
    lines += [f"reference: {len(gaps)} answers checked in {ref_s:.3f} s"]
    lines += [f"check {k}: {v} (limit {lim})"
              for k, (v, lim) in checks.items()]
    return result, lines, control_gaps
