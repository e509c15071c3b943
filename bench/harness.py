"""The benchmark's harness: one run of one cell, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
configuration's file (``bench/configs/<config>.json``) names its data
generator (``bench/generators/<generator>.py``); the mix's file
(``bench/traffic/<traffic>.json``) names its driver
(``bench/drivers/<driver>.py``).  Each per-layer metric is read by
``bench/layer_metrics/<metric>.py`` (see :func:`reader_path`), and the
tests under ``bench/`` run a cell at the sizes of ``bench/tiny/<cell>.json``.
A later change adds a cell, a mix or a metric by adding such files and
entries; this file finds them by name, under the root it is given.

A run: check the device, generate the data from the seed, let the driver
build the program's own entry objects and warm every shape up (set-up),
measure for ``seconds`` (the window), free the program's state, compare
what the window produced with the plain reference, and print the result.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoDevice(RuntimeError):
    """The machine has no chip the benchmark may report on."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextmanager
def phase(name: str):
    """Log how long a step of set-up or of the check took (host clock)."""
    t = time.perf_counter()
    yield
    log(f"phase {name}: {time.perf_counter() - t:.3f} s")


# -- finding things by name --------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            with open(os.path.join(root, cfg["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, root: str = ROOT):
    """The traffic driver ``bench/drivers/<name>.py`` under ``root``."""
    return _module(os.path.join(root, "bench", "drivers", f"{name}.py"),
                   f"bench_driver_{name}")


def load_generator(name: str, root: str = ROOT):
    """The data generator ``bench/generators/<name>.py`` under ``root``."""
    return _module(os.path.join(root, "bench", "generators", f"{name}.py"),
                   f"bench_generator_{name}")


def reader_path(metric: str, root: str = ROOT) -> str:
    """The file of a per-layer metric's reader: ``<metric>.py``, or else
    the file of the name before its first dot, which reads a quantity for
    every cell (``device_idle_share.py`` for ``device_idle_share.serve``
    and ``device_idle_share.bulk``)."""
    folder = os.path.join(root, "bench", "layer_metrics")
    own = os.path.join(folder, f"{metric}.py")
    return own if os.path.isfile(own) else \
        os.path.join(folder, metric.split(".")[0] + ".py")


def load_reader(metric: str, root: str = ROOT):
    """The reader of one per-layer metric: ``read(ctx) -> float | None``."""
    return _module(reader_path(metric, root), "bench_layer_" +
                   metric.replace(".", "_").replace("-", "_"))


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The end-to-end (``kind="end_to_end"``) or per-layer metrics a cell
    reports.  A metric without ``workloads`` is reported by every cell; a
    per-layer one without it, by every cell that reports its ``moves``."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# -- the device --------------------------------------------------------------

def device_report(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices; raises :class:`NoDevice`
    without a TPU, with fewer chips than the cell asks for, or with a kind
    the peaks table does not hold."""
    import jax

    from bench.peaks import UnknownDevice, peaks_for

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu:
        if d0.platform != "tpu":
            raise NoDevice(f"JAX finds no TPU (platform {d0.platform!r})")
        if len(devs) < chips:
            raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                           f"{len(devs)}")
        try:
            peaks_for(d0.device_kind)
        except UnknownDevice as e:
            raise NoDevice(str(e)) from None
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def seconds_since_process_start(fallback_t0: float) -> float:
    """Wall seconds since this process started, from the kernel's record of
    its start (10 ms ticks); the interpreter's own start-up is included.
    Falls back to the clock read at the first line of ``run.py``."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        start_ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        s = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= s < 86400.0:
            return s
    except (OSError, ValueError, IndexError):
        pass
    return time.perf_counter() - fallback_t0


class CompileCounter:
    """Counts the programs JAX lowers (and those the backend compiles)
    while ``active``: each is a compilation that the in-memory cache did
    not answer."""

    def __init__(self):
        import jax

        self.active = False
        self.lowered = 0
        self.backend = 0

        def on_duration(name, _secs, **_kw):
            if not self.active:
                return
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1
            elif name == "/jax/core/compile/backend_compile_duration":
                self.backend += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


# -- tracing -----------------------------------------------------------------

@contextmanager
def profiled(enabled: bool, out: Dict):
    """The JAX profiler and the program's span tracer over the window.
    ``out`` receives ``path`` (the ``.xplane.pb``), ``dir`` and ``spans``."""
    if not enabled:
        yield
        return
    import glob

    import jax

    from repro import obs

    # a ring that holds every span of a window (the default keeps 16,384)
    obs.TRACER.__init__(enabled=True, ring_spans=1 << 22)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        obs.TRACER.enabled = False
        out["spans"] = obs.TRACER.spans()
        found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        out["dir"] = tmp
        out["path"] = found[0] if found else None


# -- one run -----------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, root: str = ROOT, require_tpu: bool = True,
             overrides: Optional[Dict[str, dict]] = None,
             patch: Optional[Callable] = None) -> dict:
    """One run of ``workload``; returns the result object the command
    prints.  ``overrides`` ({"config": {...}, "traffic": {...}}) and
    ``patch`` (called with the driver's state after set-up) let tests run a
    cell at a small size and break it; the command passes neither."""
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    device = device_report(int(cell["chips"]), require_tpu)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro import obs

    from bench import target_words

    cache_dir = enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    cfg = dict(load_config(bench, cell["config"], root))
    traffic = dict(load_traffic(cell["traffic"], root))
    if overrides:
        cfg.update(overrides.get("config", {}))
        traffic.update(overrides.get("traffic", {}))
    driver = load_driver(traffic["driver"], root)
    counter = CompileCounter()

    with phase("generate"):
        data = load_generator(cfg["generator"], root).generate(cfg, seed)
    state = driver.setup(cfg, traffic, seed, data, seconds)
    if patch is not None:
        patch(state)
    gc.collect()
    gc.freeze()             # set-up's objects are never scanned again
    before = obs.snapshot()
    traced: Dict = {}
    counter.active = True
    setup_s = seconds_since_process_start(t0)
    with profiled(trace, traced), target_words.recorded(state, trace):
        with jax.profiler.TraceAnnotation("bench.window"):
            t_open = time.perf_counter()
            observed = driver.window(state, seconds)
            t_close = time.perf_counter()
    counter.active = False
    after = obs.snapshot()
    mem_peak = memory_peak_bytes()
    log(f"window: {t_close - t_open:.3f} s; compiles in the window: "
        f"{counter.lowered} lowered, {counter.backend} backend-compiled")
    print(f"compiles_in_window lowered={counter.lowered} "
          f"backend={counter.backend}", flush=True)

    e2e = driver.end_to_end(state, observed)
    e2e["setup_s"] = setup_s
    log(f"end to end: {e2e}")
    ctx = driver.layer_context(state, observed)
    ctx.update({"before": before, "after": after,
                "t_open": t_open, "t_close": t_close,
                "spans": traced.get("spans", []), "trace": None,
                "device_kind": device["kind"]})
    driver.release(state)
    gc.unfreeze()
    gc.collect()

    with phase("check"):
        checks = driver.check(state, observed, data)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": bool(correct),
              "attempted": int(observed["attempted"]),
              "failed": int(observed["failed"])}
    wanted = cell_metrics(bench, workload,
                          "per_layer" if trace else "end_to_end")
    metrics = {}
    if trace:
        from bench import trace_reduce

        if traced.get("path"):
            try:
                ctx["trace"] = trace_reduce.reduce_file(
                    traced["path"], ctx, kernel="itemset_count")
            except ValueError as e:      # nothing ran on a device
                log(f"trace: {e}")
        for m in wanted:
            value = load_reader(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(traced.get("dir") or "", ignore_errors=True)
    else:
        for m in wanted:
            if m["name"] in e2e and e2e[m["name"]] is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    device["memory_peak_bytes"] = mem_peak
    if trace and ctx["trace"] is not None:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["device"] = device
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
