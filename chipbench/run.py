#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

(``python3 -m chipbench.run`` works the same.)  The cell is an entry of
``workloads`` in BENCHMARK.json; its file ``chipbench/workloads/<cell>.json``
names its configuration (``chipbench/configs/<config>.json``) and its
traffic driver (``chipbench/traffic/<driver>.py``).  A run loads, warms up
every shape it will use (set-up), measures for ``--seconds``, frees the
program's state, compares what the window produced with the plain
reference, and prints as its last line one JSON object.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
(each read by ``chipbench/metrics/<metric>.py`` from a profiler trace of
the window, the program's spans and the window's counts).

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not beside it.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
import types

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "chipbench_traces")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its workload entry, the workload file, the config
    file) for cell ``name``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    wl = load_json(BENCH_DIR, "workloads", name + ".json")
    cfg = load_json(BENCH_DIR, "configs", entry["config"] + ".json")
    return bench, entry, wl, cfg


def cell_metrics(bench: dict, key: str, cell: str) -> list:
    """The metrics of ``bench[key]`` that cell ``cell`` reports."""
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    """The ``read(ctx)`` function of chipbench/metrics/<name>.py."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax() -> str:
    """Persistent compilation cache in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached however short
    its compile."""
    import jax
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def require_chips(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            ms = d.memory_stats() or {}
        except Exception:            # noqa: BLE001  (backend without stats)
            ms = {}
        peaks.append(int(ms.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class _Span:
    """One program span: timed on the host clock and marked in the
    profiler's trace."""

    def __init__(self, name: str, out: list):
        self.name, self.dur, self._out = name, 0.0, out

    def __enter__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._out.append(self)
        return False


@contextlib.contextmanager
def program_spans():
    """The program's host spans, without its obs recorder: an active
    recorder also makes the runners block on every output and the engine
    keep every schedule, which is not the path the window measures.  For
    the block, every name in the program's modules that is bound to
    ``repro.obs.trace.span`` is bound to a ``_Span`` instead; yields the
    list the spans are appended to."""
    from repro.obs import trace as obs_trace
    spans: list = []
    orig = obs_trace.span
    bound = [(mod, key) for name, mod in list(sys.modules.items())
             if name == "repro" or name.startswith("repro.")
             for key, val in list(vars(mod).items()) if val is orig]
    for mod, key in bound:
        setattr(mod, key, lambda name, **_: _Span(name, spans))
    try:
        yield spans
    finally:
        for mod, key in bound:
            setattr(mod, key, orig)


def traced_window(session, seconds: float, logdir: str, device_ids):
    """The window under the profiler, with the program's spans; returns
    (record, DeviceTrace, (lo_ns, hi_ns), spans)."""
    import jax
    from chipbench.metrics import _trace
    os.makedirs(logdir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # Python calls would slow the host
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        with program_spans() as spans:
            with jax.profiler.TraceAnnotation("chipbench:window"):
                record = session.window(seconds)
    finally:
        jax.profiler.stop_trace()
    trace = _trace.load(_trace.find_xplane(logdir), device_ids)
    shutil.rmtree(os.path.join(logdir, "plugins"), ignore_errors=True)
    win = [(s, e) for name, s, e in trace.host if name == "chipbench:window"]
    lo, hi = win[-1] if win else (None, None)
    with open(os.path.join(logdir, "summary.json"), "w") as f:
        json.dump({"lines": trace.lines, "window_ns": [lo, hi],
                   "top_ops": _trace.top_ops(trace, 40),
                   "op_count": {k: len(v) for k, v in trace.ops.items()}},
                  f, indent=1)
    return record, trace, (lo, hi), spans


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices=None, wl_override: dict | None = None,
             cfg_override: dict | None = None,
             peaks: dict | None = None) -> dict:
    """Set up, measure, check; returns the result object.  ``devices`` None
    means: look for the chips the cell asks for (tests pass the CPU device,
    small overrides of the workload and configuration, and peaks)."""
    bench, entry, wl, cfg = load_cell(name)
    wl = {**wl, **(wl_override or {})}
    cfg = {**cfg, **(cfg_override or {})}
    from repro.obs.timing import CompileWatch
    if devices is None:
        devices = require_chips(int(entry["chips"]))
    driver = importlib.import_module("chipbench.traffic." + wl["driver"])
    with CompileWatch() as cw_setup:
        session = driver.Session(cfg, wl, seed, devices)
        session.setup()
    setup_s = time.perf_counter() - T_START
    print(f"chipbench: set-up {setup_s:.3f} s, {cw_setup.compiles} compiles "
          f"({cw_setup.compile_s:.3f} s)", file=sys.stderr, flush=True)

    spans, dtrace, bounds = [], None, (None, None)
    with CompileWatch() as cw_win:
        if trace:
            logdir = os.path.join(TRACE_DIR, f"{name}-{seed}")
            record, dtrace, bounds, spans = traced_window(
                session, seconds, logdir, {d.id for d in devices})
        else:
            record = session.window(seconds)
    print(f"chipbench: window {record.window_s:.3f} s, {record.attempted} "
          f"attempted, {cw_win.compiles} compiles inside the window",
          file=sys.stderr, flush=True)
    peak = memory_peak(devices)

    metrics = {}
    breakdown = None
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from chipbench.metrics import _trace
        from chipbench.peaks import device_peaks
        lo, hi = bounds
        window_s = (hi - lo) * 1e-9 if lo is not None else record.window_s
        busy = _trace.busy_s(dtrace, lo, hi)
        ctx = types.SimpleNamespace(
            trace=dtrace, lo_ns=lo, hi_ns=hi, window_s=window_s, busy_s=busy,
            spans=spans, record=record, cfg=cfg, wl=wl, session=session,
            peaks=peaks or device_peaks(dev.device_kind))
        for m in cell_metrics(bench, "per_layer", name):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=busy, window_s=window_s)
        breakdown = {"device_ops": _trace.top_ops(dtrace),
                     "idle_gaps": (_trace.idle_gaps(dtrace, lo, hi)
                                   if lo is not None else [])}
    else:
        values = {**record.e2e, "setup_s": setup_s}
        for m in cell_metrics(bench, "end_to_end", name):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    session.release()
    checks = session.check()
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": record.attempted,
              "failed": record.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = cw_win.compiles
    result["setup_compiles"] = cw_setup.compiles
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chipbench: no program under {SRC}; run from a checkout of "
              f"the repository", file=sys.stderr)
        return 2
    for p in (SRC, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    setup_jax()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    except Exception:                 # noqa: BLE001  (report, no result)
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
