"""The harness: one cell, one run, one result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* the configuration: ``bench/configs/<config>.json`` (the entry's ``file``);
* the traffic mix:   ``bench/traffic/<traffic>.json``, whose ``driver``
  names the driver, ``bench/drivers/<driver>.py``;
* each per-layer metric: ``bench/metrics/<metric>.py``, a ``read(run)``
  that returns a number or None where it finds nothing to read.

A driver module defines ``Cell(config, traffic, seed, run_dir)`` with
``setup()``, ``window(seconds, span)``, ``end_to_end()``, ``counters()``,
``release()``, ``check()`` and ``close()``.  The harness times set-up,
traces the window when asked, reads the device's peak memory before the
program's state is freed, runs the correctness check after that, and
prints the result line last on stdout.  Diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
RUN_ROOT = os.path.join(ROOT, ".bench_run")


class NoChip(SystemExit):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """A module of the benchmark by file path: metric names may hold dots."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", f"{name}.py")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH, "traffic", f"{name}.json")


def driver_path(name: str) -> str:
    return os.path.join(BENCH, "drivers", f"{name}.py")


def resolve(bench: dict, workload: str) -> dict:
    """A cell's entry, configuration, traffic, driver file and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[cell["config"]]
    traffic = _load_json(os.path.relpath(traffic_path(cell["traffic"]),
                                         ROOT))

    def mine(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": _load_json(conf["file"]), "traffic": traffic,
            "driver": driver_path(traffic["driver"]),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def device_info(chips: int) -> dict:
    """The accelerator JAX sees; a run without enough TPU chips ends here,
    before it prints anything on stdout."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     "device(s); this benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    """The peak on the fullest chip, as far as the allocator shows it: the
    larger of its peak of arrays and, read now, the arrays plus the region
    it reserves for compiled programs' scratch, which on a TPU
    ``peak_bytes_in_use`` leaves out.  Read once the window has closed,
    while the program's state and programs are alive."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(max(st.get("peak_bytes_in_use", 0),
                         st.get("bytes_in_use", 0)
                         + st.get("bytes_reserved", 0)))
    return max(peaks)


def memory_stats() -> dict:
    """The first chip's allocator statistics, for the log."""
    import jax
    return dict(jax.devices()[0].memory_stats() or {})


class CompileCounter:
    """Programs JAX builds while ``counting`` is on, through
    ``jax.monitoring``: ``count`` compiled or loaded from the persistent
    cache, ``hits`` of them loaded."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.count = self.hits = 0
        self.counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.counting and event == self.EVENT:
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if self.counting and event == self.HIT:
            self.hits += 1


def enable_cache() -> str:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), small programs included, so that
    only a cell's first run in a checkout compiles."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def log(what: str, **fields) -> None:
    print(json.dumps({"at": time.strftime("%H:%M:%S"), "what": what,
                      **fields}), file=sys.stderr, flush=True)


def host_memory() -> dict:
    """This process's resident and peak resident bytes, where the kernel
    reports them, for the log."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            key = line.split(":", 1)[0]
            if key in ("VmRSS", "VmHWM"):
                out[key] = int(line.split()[1]) * 1024
    return out


def run(argv=None, t_start: float | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    spec = resolve(load_benchmark(), args.workload)
    cell = spec["cell"]
    try:
        device = device_info(cell["chips"])
    except NoChip as e:
        print(str(e), file=sys.stderr, flush=True)
        return 3
    enable_cache()
    from bench import tracing
    compiles = CompileCounter()
    run_dir = os.path.join(RUN_ROOT, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    driver = load_module(spec["driver"], "bench_driver")
    c = driver.Cell(spec["config"], spec["traffic"], args.seed, run_dir)
    try:
        c.setup()
        setup_s = time.monotonic() - t_start
        log("setup done", setup_s=setup_s)
        red = None
        compiles.counting = True
        if args.trace:
            trace_dir = os.path.join(run_dir, "trace")
            with tracing.capture(trace_dir) as got:
                c.window(args.seconds, tracing.span)
            compiles.counting = False
            if got["path"]:
                red = tracing.reduce(tracing.load(got["path"]))
            shutil.rmtree(trace_dir, ignore_errors=True)
        else:
            c.window(args.seconds, tracing.span)
            compiles.counting = False
        device["memory_peak_bytes"] = memory_peak(cell["chips"])
        log("device memory", **memory_stats())
        e2e = c.end_to_end()
        counters = dict(c.counters(), window_compiles=compiles.count,
                        window_cache_hits=compiles.hits)
        c.release()
        checks = c.check()
    finally:
        c.close()
        shutil.rmtree(run_dir, ignore_errors=True)
    log("counters", **host_memory(), **counters)
    correct = all(ch["ok"] for ch in checks)
    for ch in checks:  # the last lines on stderr: each number and its limit
        print(f"check {ch['name']}: {ch['value']!r} limit {ch['limit']!r} "
              f"({'ok' if ch['ok'] else 'FAILED'})", file=sys.stderr,
              flush=True)
    metrics = {}
    if args.trace:
        if red is None:
            print("the trace holds no device operation in the window",
                  file=sys.stderr, flush=True)
            return 4
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        run_ctx = Run(counters, red, device)
        for m in spec["per_layer"]:
            value = load_module(metric_path(m["name"]),
                                "bench_metric").read(run_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out = {"correct": correct, "attempted": counters["attempted"],
           "failed": counters["failed"], "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = tracing.breakdown(red)
    out["checks"] = {ch["name"]: {"value": ch["value"], "limit": ch["limit"]}
                     for ch in checks}
    print(json.dumps(out), flush=True)
    return 0


class Run:
    """What a per-layer metric reads: the driver's counters, the reduced
    trace of the window, and the device (kind and count)."""

    def __init__(self, counters: dict, trace: dict, device: dict):
        self.counters = counters
        self.trace = trace
        self.device = device

    @property
    def peaks(self) -> dict:
        from bench.peaks import peaks
        return peaks(self.device["kind"])

    def idle_share(self) -> float:
        return 1.0 - self.trace["busy_s"] / self.trace["window_s"]
