#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of
``BENCHMARK.json``'s ``workloads``; everything else is found by name:

* ``configs/<config>.json``   — the deployment (DedupConfig, corpus size,
  service options, the reference's tokenizer settings);
* ``workloads/<cell>.json``   — the traffic mix, the entry that drives
  the window (``entries/<entry>.py``) and the limits of the checks;
* ``metrics/<metric>.py``     — one reader per per-layer metric.

A run generates its data from ``--seed``, builds and warms the system
(set-up), measures for ``--seconds``, then checks what the window
produced against the plain reference (``reference.py``, ``compare.py``).
With ``--trace 0`` the result line holds the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the line holds
the per-layer metrics, read from the trace and the program's counters.

The last line of standard output is the JSON result; the last lines of
standard error are the compared numbers with their limits.  Without a
TPU, or with fewer chips than the cell asks for, the run exits nonzero
and prints no result.  ``--control`` runs the cell's control (see
PERF.md) in the program's place; the benchmark's own runs never use it.
A run that lowers or compiles anything inside the window is not
correct: every shape is warmed in set-up.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# A fixed path inside the checkout: the path is part of the cache key.
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
if HERE not in sys.path:
    sys.path.insert(0, HERE)

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_file(relpath: str, module_name: str):
    """Import a file of the benchmark directory by its path (names such
    as ``trace`` would otherwise meet the standard library's)."""
    path = os.path.join(HERE, relpath)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(kind: str, name: str):
    """Import ``<kind>/<name>.py`` from the benchmark directory."""
    return load_file(os.path.join(kind, name + ".py"),
                     f"bench_{kind}_{name.replace('.', '_')}")


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class CompileCounter:
    """Counts lowerings (every jit cache miss), backend compiles and
    persistent-cache hits, as JAX's monitoring events report them."""

    def __init__(self):
        import jax

        self.lowerings = self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, seconds: float, **_):
        if event == LOWER_EVENT:
            self.lowerings += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return (self.lowerings, self.compiles, self.cache_hits)


class Window:
    """The measured window: marks its start and end, counts what
    compiled inside it, and runs the profiler around it when tracing."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.t0 = self.t1 = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def __enter__(self):
        ctx = self.ctx
        if ctx.tracing:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            ctx.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
            self._span = ctx.span("window")
            self._span.__enter__()
        self.c0 = ctx.compiles.snapshot()
        self.t0 = time.perf_counter()
        ctx.setup_s = self.t0 - ctx.t_start
        return self

    def close(self) -> None:
        """The window's end: called by the entry when its last measured
        work returns (later work, such as a drain, is not timed)."""
        if self.t1 is None:
            self.t1 = time.perf_counter()

    def __exit__(self, *exc):
        self.close()
        ctx = self.ctx
        c1 = ctx.compiles.snapshot()
        ctx.window_compiles = tuple(b - a for a, b in zip(self.c0, c1))
        if ctx.tracing:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.seconds = self.t1 - self.t0
        return False


class Context:
    """What an entry and a metric reader see of one run."""

    def __init__(self, args, cell: dict, config: dict, workload: dict,
                 t_start: float):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.tracing = bool(args.trace)
        self.control = bool(args.control)
        self.cell, self.config, self.workload = cell, config, workload
        self.t_start = t_start
        self.counters: dict = {}
        self.trace = None
        self.trace_dir = None
        self.setup_s = None
        self.window_compiles = (0, 0, 0)
        self.memory_peak_bytes = None
        self.compiles = CompileCounter()

    def rng(self, stream: str):
        from traffic.clinical_notes import rng_for

        return rng_for(self.seed, stream)

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    def window(self) -> Window:
        return Window(self)

    def reference_config(self) -> dict:
        d, t = self.config["dedup"], self.config["tokenizer"]
        return {"stem": t["stem"], "seed_key": t["minhash_seed_key"],
                "ngram": d["ngram"], "num_hashes": d["num_hashes"],
                "rows_per_band": d["rows_per_band"],
                "edge_threshold": d["edge_threshold"],
                "tree_threshold": d["tree_threshold"]}

    def read_memory(self) -> None:
        """Peak device memory of the fullest chip, read once the window
        has closed and before the reference runs."""
        import jax

        peaks = []
        for dev in jax.local_devices()[: self.cell["chips"]]:
            stats = dev.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peaks) if peaks else 0


def find_devices(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if not allow_cpu and (not devs or devs[0].platform != "tpu"):
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def configure_jax(persistent_cache: bool = True) -> None:
    import jax

    if not persistent_cache:
        return
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # Cache every program, however quick its compile: the default keeps
    # only those that took a second or more, so small kernels compiled
    # again in every process.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", action="store_true")
    return p.parse_args(argv)


def run(argv=None, *, overrides: dict | None = None,
        allow_cpu: bool = False, t_start: float | None = None) -> dict:
    """One run; returns the result dict (the last stdout line).

    ``overrides`` is merged into the cell's config and workload files
    (``{"config": {...}, "workload": {...}}``); tests use it to run a
    cell at a size the CPU holds."""
    args = parse(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; one of "
                         f"{sorted(cells)}")
    cell = cells[args.workload]
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", cell["name"] + ".json")) as f:
        workload = json.load(f)
    overrides = overrides or {}
    config = merge(config, overrides.get("config", {}))
    workload = merge(workload, overrides.get("workload", {}))

    configure_jax(persistent_cache=not allow_cpu)
    devices = find_devices(cell["chips"], allow_cpu)
    import peaks

    kind = devices[0].device_kind
    if not allow_cpu:
        peaks.for_kind(kind)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)

    ctx = Context(args, cell, config, workload,
                  T_START if t_start is None else t_start)
    entry = load("entries", workload["entry"])
    outcome = entry.run(ctx)

    for line in outcome.get("log", []):
        print(line, file=sys.stderr)
    lw, cw, hw = ctx.window_compiles
    print(f"compiles in window: {lw} lowered, {cw} compiled, {hw} "
          f"persistent-cache hits; set-up {ctx.setup_s:.3f} s with "
          f"{ctx.compiles.compiles} compiles "
          f"({ctx.compiles.compile_s:.3f} s) and {ctx.compiles.cache_hits} "
          "cache hits in all", file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": None, "attempted": outcome["attempted"],
              "failed": outcome["failed"], "metrics": {}, "device": device}
    if ctx.tracing:
        reduce = load_file("trace.py", "bench_trace")
        ctx.trace = reduce.Trace.from_file(
            reduce.find_xplane(ctx.trace_dir))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s()
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = load("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                               "idle_gaps": ctx.trace.idle_gaps(k=10)}
    else:
        values = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    limits = workload["check"]["limits"]
    checks = {k: {"value": outcome["checks"][k], "limit": limits[k]}
              for k in limits}
    checks["compiles_in_window"] = {"value": lw + cw, "limit": 0}
    result["correct"] = (outcome["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    info = {k: v for k, v in outcome["checks"].items() if k.startswith("_")}
    print(f"check details: {json.dumps(info)}", file=sys.stderr)
    print(f"run: {time.perf_counter() - ctx.t_start:.3f} s from process "
          "start to the end of the check", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
