"""The harness behind ``run.py``: the run's context, the measured window
(set-up time, compiles, tracing), and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time
from pathlib import Path

from bench import check, spec, trace

NO_CHIP_EXIT = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler trace (a no-op when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class CompileCounter:
    """Counts traces and compiles (cache hits included) while active."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.count += 1


class GCPauses:
    """Pauses of the interpreter's garbage collector between ``start``
    and ``stop``, by generation (printed on an earlier line)."""

    def __init__(self):
        self.pauses = {}
        self._t = None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.setdefault(info["generation"], []).append(
                time.perf_counter() - self._t)

    def start(self) -> None:
        gc.callbacks.append(self._on)

    def stop(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        return " ".join(f"gen{g}: {len(p)} max {1e3 * max(p):.1f} ms"
                        for g, p in sorted(self.pauses.items())) or "none"


class Window:
    """The measured window. Entering it ends set-up; with tracing on, the
    profiler records the whole of it and stops when the window is left,
    after the driver's generator and writer have finished."""

    def __init__(self, ctx: "Ctx"):
        self.ctx = ctx
        self.t0 = None
        self._stack = contextlib.ExitStack()

    def __enter__(self):
        ctx = self.ctx
        # set-up's survivors live to the end of the run: frozen, they are
        # not scanned again by every full collection in the window
        gc.collect()
        gc.freeze()
        ctx.setup_s = time.perf_counter() - ctx.t_start
        if ctx.trace:
            self._cap = self._stack.enter_context(
                trace.capture(ctx.out_dir / "trace"))
            self._stack.enter_context(span(trace.WINDOW_SPAN))
        ctx.compiles.active = True
        ctx.gc_pauses.start()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._stack.close()
        self.ctx.compiles.active = False
        self.ctx.gc_pauses.stop()
        gc.unfreeze()
        self.ctx.window_compiles = self.ctx.compiles.count
        if self.ctx.trace and self._cap.get("path"):
            events = trace.extract(self._cap["path"])
            trace.save(events, self.ctx.out_dir / "trace_events.json.gz")
            try:
                self.ctx.trace_summary = trace.reduce(events)
            except ValueError as e:   # no device plane: nothing to read
                log(f"trace not reduced: {e}")
        return False


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's files, the run's arguments, and the
    harness's timing, tracing and memory hooks."""

    cell: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    out_dir: Path
    t_start: float
    setup_parts: dict = dataclasses.field(default_factory=dict)
    setup_s: float | None = None
    trace_summary: dict | None = None
    window_compiles: int = 0
    memory_peak_bytes: int | None = None

    def __post_init__(self):
        self.compiles = CompileCounter()
        self.gc_pauses = GCPauses()
        self._t_part = time.perf_counter()

    span = staticmethod(span)

    def part(self, name: str) -> None:
        """Close a named part of set-up (printed on an earlier line)."""
        now = time.perf_counter()
        self.setup_parts[name] = now - self._t_part
        self._t_part = now

    def window(self) -> Window:
        return Window(self)

    def read_memory(self) -> None:
        import jax
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()
                 if d.memory_stats() is not None]
        self.memory_peak_bytes = int(max(peaks)) if peaks else None


@dataclasses.dataclass
class Result:
    """What a driver returns."""

    attempted: int
    failed: int
    e2e: dict                      # end-to-end metric name -> value
    numbers: dict                  # compared number name -> value
    layer: dict = dataclasses.field(default_factory=dict)  # reader inputs
    notes: dict = dataclasses.field(default_factory=dict)  # earlier lines


def devices_or_exit(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        log(f"no TPU: JAX sees {devs[0].platform}; refusing to run")
        sys.exit(NO_CHIP_EXIT)
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
        sys.exit(NO_CHIP_EXIT)
    return devs


def enable_cache() -> str:
    import jax
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             t_start: float | None = None, root: Path = spec.REPO_DIR,
             require_tpu: bool = True, bench: dict | None = None) -> dict:
    """One run of one cell -> the result line as a dict. Tests call it
    with ``require_tpu=False`` and a small ``bench``; the command never."""
    bench = spec.load_benchmark(root) if bench is None else bench
    cell = spec.cell(bench, name)
    devs = devices_or_exit(cell["chips"], require_tpu)
    kind = devs[0].device_kind
    peaks = spec.peaks(kind) if require_tpu else None
    cache = enable_cache()
    bench_dir = Path(root) / "bench"
    config = spec.config(bench, cell["config"], root)
    traffic = spec.traffic(cell["traffic"], bench_dir)
    limits = spec.limits(name, bench_dir)
    if traced and traffic.get("trace_seconds"):
        # a traced run measures only what the profiler can hold
        seconds = min(seconds, float(traffic["trace_seconds"]))
    out_dir = bench_dir / ".out" / name
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = Ctx(cell=cell, config=config, traffic=traffic, limits=limits,
              seed=seed, seconds=seconds, trace=traced, out_dir=out_dir,
              t_start=time.perf_counter() if t_start is None else t_start)
    log(f"cell={name} seed={seed} seconds={seconds} trace={int(traced)} "
        f"device={devs[0].platform}:{kind} x{len(devs)} cache={cache}")
    res = spec.driver(traffic["kind"], bench_dir).run(ctx)

    numbers = dict(res.numbers)
    numbers["window_compiles"] = ctx.window_compiles
    correct, table = check.judge(numbers, limits["numbers"])
    for k, v in ctx.setup_parts.items():
        log(f"setup part {k}: {v:.3f} s")
    log(f"setup_s={ctx.setup_s:.3f} compiles_in_window={ctx.window_compiles}"
        f" memory_peak_bytes={ctx.memory_peak_bytes}")
    log(f"gc_pauses_in_window: {ctx.gc_pauses.summary()}")
    for k, v in res.notes.items():
        log(f"{k}: {v}")

    metrics = {}
    if not traced:
        e2e = dict(res.e2e, setup_s=ctx.setup_s)
        for m in spec.end_to_end(bench, name):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        rctx = dict(res.layer, trace=ctx.trace_summary, config=config,
                    traffic=traffic, peaks=peaks)
        for m in spec.per_layer(bench, name):
            value = spec.metric_reader(m["name"], bench_dir)(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": device}
    if traced and ctx.trace_summary:
        s = ctx.trace_summary
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
    line["checks"] = table
    for k, v in table.items():
        log(f"check {k}: {v['value']} limit {v['limit']}")
    return line
