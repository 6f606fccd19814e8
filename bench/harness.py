"""Run one cell once: set up, measure a closed loop of whole calls for
``--seconds``, check every answer of the window, print one JSON line.

The process owns the chip.  Set-up generates the cell's instances from
the seed, converts them to the program's input type, and makes one
warm-up call with the window's own shapes, which compiles (or loads
from the persistent compilation cache) every program the window runs.
The window then calls the entry back to back, in whole cycles through
the distinct payloads, until ``--seconds`` have passed (so every run
does the same work); each call is a benchmark span (``bench.round``).
After the window the answers are checked against the plain reference
(``reference.py``), the compiled programs are checked for products
below the configuration's matrix precision, and the metrics are read by
their files in ``bench/metrics/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import List, Optional

from bench import reference, spec, traffic

TRACE_DIR = os.path.join(spec.BENCH_DIR, "out", "trace")
TRACE_MIN_S = 1.0
PEAKS = os.path.join(spec.BENCH_DIR, "peaks.json")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no accelerator, too few chips, or a chip without peaks."""


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) from
    JAX's own ``backend_compile_duration`` events; copied from the
    program's compile sanitizer so the count cannot move with it."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class Call:
    """One timed call: its host-clock span and its answers."""

    t0: float
    t1: float
    instances: list
    answers: List[dict]


@dataclasses.dataclass
class Run:
    """What the metric readers get."""

    cell: spec.Cell
    seed: int
    setup_s: float
    calls: List[Call]
    peaks: dict
    trace: Optional[object] = None     # devtrace.Trace of the window
    span: str = "bench.round"
    compiles_in_window: int = 0
    records: List[dict] = dataclasses.field(default_factory=list)
    programs: Optional[List[str]] = None   # HLO of the compiled programs
    traced: Optional[int] = None   # the first calls, which the trace holds

    @property
    def traced_calls(self) -> List[Call]:
        return self.calls if self.traced is None else self.calls[:self.traced]

    @property
    def answers(self) -> List[dict]:
        return [a for c in self.calls for a in c.answers]

    @property
    def window_s(self) -> float:
        """Host-clock span of the window's whole calls."""
        return self.calls[-1].t1 - self.calls[0].t0

    def trace_window(self):
        """(start, end) of the traced calls on the trace's clock."""
        spans = self.trace.spans_named(self.span)
        return spans[0][0], spans[-1][1]

    def device_busy_s(self) -> Optional[float]:
        """Device busy seconds inside the traced calls."""
        if self.trace is None or not self.trace.busy:
            return None
        busy = sum(self.trace.busy_ns(s, e)
                   for s, e, _ in self.trace.spans_named(self.span))
        return busy * 1e-9 if busy > 0 else None

    def idle_share(self) -> Optional[float]:
        if self.trace is None or not self.trace.busy:
            return None
        lo, hi = self.trace_window()
        return 100.0 * (1.0 - self.trace.busy_ns(lo, hi) / (hi - lo))


def device_info(chips: int, peaks: dict) -> dict:
    """The accelerator JAX finds; ``NoChip`` when there is none, too
    few, or one whose peaks ``peaks.json`` lacks."""
    import jax

    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"needs an accelerator, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks["devices"]:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


class _Tracer:
    """The profiler over the window's first whole cycles through the pool,
    until ``TRACE_MIN_S`` have passed.  The device records every
    operation of the PDHG loop, and a trace of a whole window of small
    instances outgrows what the profiler keeps; every cycle is the same
    work, so the first ones stand for the window."""

    def __init__(self, on: bool):
        self.on = on
        self.calls = None        # calls traced, once the profiler stops
        if on:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            os.makedirs(TRACE_DIR)
            jax.profiler.start_trace(TRACE_DIR)

    def after(self, done: list, cycle: int, w0: float):
        if self.on and self.calls is None and len(done) % cycle == 0 \
                and done[-1].t1 - w0 >= TRACE_MIN_S:
            self.stop(len(done))

    def stop(self, n_calls: int):
        if self.on and self.calls is None:
            import jax

            jax.profiler.stop_trace()
            self.calls = n_calls


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict, entry=None) -> Run:
    """Set up, warm up and measure one cell; ``entry`` replaces the one
    the configuration names (the control and the fault tests pass
    theirs)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    calls = traffic.make_calls(cell.mix, cell.config, seed)
    if entry is None:
        entry = spec.load_file(spec.entry_path(cell.entry)).make(cell.config)
    payloads = [entry.prepare(c) for c in calls]
    entry.solve(payloads[0])                       # warm-up: every shape
    setup_s = time.perf_counter() - t_start
    counter = CompileCounter()
    span = "bench.round"
    done: List[Call] = []
    tracer = _Tracer(trace)
    w0 = time.perf_counter()
    while (not done or len(done) % len(payloads)
           or done[-1].t1 - w0 < seconds):
        k = len(done) % len(payloads)
        with jax.profiler.TraceAnnotation(span):
            t0 = time.perf_counter()
            answers = entry.solve(payloads[k])
            t1 = time.perf_counter()
        done.append(Call(t0, t1, calls[k], answers))
        tracer.after(done, len(payloads), w0)
    tracer.stop(len(done))
    counter.close()
    print(f"window: calls={len(done)} compiles={counter.count} "
          f"compile_s={counter.seconds:.3f} setup_s={setup_s:.3f}",
          file=sys.stderr, flush=True)
    run = Run(cell=cell, seed=seed, setup_s=setup_s, calls=done,
              peaks=peaks, span=span, compiles_in_window=counter.count,
              programs=(entry.programs() if hasattr(entry, "programs")
                        else None))
    if trace:
        from bench import devtrace

        run.trace = devtrace.load(TRACE_DIR)
        run.traced = tracer.calls
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        print(f"trace: calls={run.traced} of {len(done)} "
              f"spans={len(run.trace.spans_named(span))}",
              file=sys.stderr, flush=True)
    return run


def check(run: Run) -> dict:
    """Every answer of the window against the reference, and the compiled
    programs against the configuration's matrix precision; each compared
    number beside its limit."""
    limits = {k: float(v) for k, v in run.cell.config["limits"].items()}
    pairs = [(inst, ans) for c in run.calls
             for inst, ans in zip(c.instances,
                                  c.answers + [None] * (len(c.instances)
                                                        - len(c.answers)))]
    numbers, records = reference.judge(pairs, limits)
    run.records = records
    if "dots_below_highest" in limits:
        # a program the entry cannot show counts as failing the check
        numbers["dots_below_highest"] = (
            float("inf") if run.programs is None
            else reference.dots_below_highest(run.programs))
    print("reference: " + " ".join(f"{k}={v!r}" for k, v in numbers.items()),
          file=sys.stderr)
    return reference.verdict(numbers, limits)


def read_metrics(run: Run, metrics: List[dict]) -> dict:
    out = {}
    for m in metrics:
        value = spec.load_file(spec.metric_path(m["name"])).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, checks: dict, device: dict, trace: bool) -> dict:
    """``device`` holds ``memory_peak_bytes`` already."""
    cell = run.cell
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)
    dev = dict(device)
    line = {"correct": reference.is_correct(checks),
            "attempted": len(run.records),
            "failed": sum(not r["ok"] for r in run.records),
            "metrics": metrics, "device": dev}
    if trace and run.trace is not None:
        lo, hi = run.trace_window()
        dev["busy_s"] = run.trace.busy_ns(lo, hi) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        line["breakdown"] = {"device_ops": run.trace.top_ops(lo, hi),
                             "idle_gaps": run.trace.idle_gaps(lo, hi)}
    line["checks"] = checks
    return line


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "__init__.py")):
        print(f"bench: no program sources under {src}", file=sys.stderr)
        return 2
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    sys.path.insert(0, src)
    from repro.runtime.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # cache every program, however quick to compile: set-up then reads
    # back the eager operations too instead of compiling them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(PEAKS) as f:
        peaks = json.load(f)
    try:
        device = device_info(cell.chips, peaks)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"bench: {cell.name} seed={args.seed} device={device} "
          f"compile_cache={cache_dir}", file=sys.stderr, flush=True)
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start, peaks["devices"][device["kind"]])
    # read the peak before the reference runs; the reference is on the host
    device["memory_peak_bytes"] = memory_peak_bytes()
    checks = check(run)
    line = result_line(run, checks, device, bool(args.trace))
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
