"""Run one benchmark cell of the clustering service and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: the
deployment, its grid, parameters and correctness limits) and a traffic mix
(``bench/traffic/<mix>.json``).  In one process that holds the cell's
chips, the run

1. builds a ``ClusteringService`` with the service's own defaults, behind a
   ``MiningClient``, as ``repro.launch.serve_mine`` does;
2. makes every request's points from ``--seed`` (set-up);
3. builds every program the traffic can reach (bench/warm.py; set-up);
4. drives the traffic through ``MiningClient.submit`` for ``--seconds``
   (open or closed loop, bench/drive.py), then waits for the answers;
5. reads the device's peak memory, stops the service, and compares a
   sample of the answers with the plain reference (bench/reference.py,
   bench/compare.py);
6. prints, on standard output, report lines starting with ``#`` (among
   them the process's peak resident memory and the host's CPU count at
   the end of each phase: data, warm-up, window, the trace's reading
   with ``--trace 1``, reference) and, last,
   one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
   (the cell's end-to-end metrics with ``--trace 0``; its per-layer
   metrics, read from a profiler trace of the window, with ``--trace 1``),
   ``device``, ``breakdown`` (``--trace 1``) and ``checks`` (each number
   compared, beside its limit).  The checks are also the last lines of
   standard error.

Every metric is read by its own file, ``bench/metrics/<name>.py``, found by
the name in ``BENCHMARK.json``.  The run exits nonzero and prints no result
when JAX finds no TPU, or fewer chips than the cell asks for, or when the
repository's ``src/`` is not beside ``bench/``.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where
that is set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
import drive  # noqa: E402
import loadgen  # noqa: E402
import peaks as peaks_mod  # noqa: E402
import trace_reduce  # noqa: E402
import warm as warm_mod  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def cell_metrics(bench: dict, cell: dict, trace: bool) -> List[dict]:
    """The metrics this cell reports in this kind of run."""
    name = cell["name"]
    if not trace:
        return [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
    e2e = {m["name"] for m in cell_metrics(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in e2e
                             else [])]


def load_reader(name: str):
    """``bench/metrics/<name>.py``; a metric split by the end-to-end metric
    it moves, ``<base>.<part>``, is read by ``<base>.py`` unless it has a
    file of its own."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "metrics", f"{name.split('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def import_program():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program source tree at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # small eager programs are kept too, so a warm set-up reads them back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import repro  # noqa: F401

    return jax


class Context:
    """What a metric reader may read."""

    def __init__(self, window, setup_s: float, spans: List[dict],
                 summary, peaks: dict, compiles_in_window: int) -> None:
        self.window = window
        self.setup_s = setup_s
        self.spans = spans
        self.summary = summary
        self.peaks = peaks
        self.compiles_in_window = compiles_in_window

    def answered(self, algo: Optional[str] = None):
        return [r for r in self.window.records if r.result is not None
                and (algo is None or r.req.algo == algo)]

    def latencies_s(self) -> List[float]:
        return [r.latency for r in self.answered()]

    def mean_stage_ms(self, stages) -> Optional[float]:
        per: Dict[str, float] = {}
        for sp in self.spans:
            if sp.get("name") in stages and sp.get("dur_s") is not None:
                per[sp["trace_id"]] = per.get(sp["trace_id"], 0.0) + float(
                    sp["dur_s"])
        if not per:
            return None
        return 1e3 * sum(per.values()) / len(per)


def check_sample(window, traffic: dict, seed: int) -> list:
    """The answered requests the reference re-computes: the largest ones,
    then a draw from the seed."""
    answered = [r for r in window.records if r.result is not None]
    want = int(traffic["check"]["sample"])
    largest = int(traffic["check"]["largest"])
    by_size = sorted(answered, key=lambda r: (-r.req.shape.n, r.req.index))
    chosen = by_size[:largest]
    rest = by_size[largest:]
    rng = loadgen.seeded_rng(seed, 4)
    extra = rng.permutation(len(rest))[: max(0, want - len(chosen))]
    return chosen + [rest[i] for i in sorted(extra)]


def reference_numbers(rec, precision: str = "float32") -> Dict[str, float]:
    import reference

    req, res = rec.req, rec.result
    x = req.data
    if req.algo == "kmeans":
        p = req.params
        init = reference.sample_init(p["seed"], x.shape[0], p["k"])
        ref = reference.kmeans(x, init, tol=p["tol"],
                               max_iters=p["max_iters"], precision=precision)
        return compare.kmeans_numbers(np.asarray(res["labels"])[: len(x)],
                                      res["inertia"], ref)
    ref = reference.dbscan(x, eps=req.params["eps"],
                           min_pts=req.params["min_pts"], precision=precision)
    expansions = (None if res.get("executor") == "numpy-mt"
                  else res.get("expansions"))
    return compare.dbscan_numbers(np.asarray(res["labels"])[: len(x)],
                                  expansions, ref)


def host_memory_line(phase: str) -> str:
    """The report line for the end of ``phase``: the process's peak
    resident memory so far (``ru_maxrss``, KiB on Linux) in bytes, and the
    host's CPU count, which sizes the reference's thread pool."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return (f"host memory: peak_rss_bytes {peak} after {phase} "
            f"(cpu_count {os.cpu_count()})")


def _device_report(devices) -> dict:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks),
            "per_device_peak_bytes": peaks}


def run_cell(cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, metrics: List[dict], *,
             require_accelerator: bool = True,
             process_t0: float = PROCESS_T0, out=None) -> dict:
    """One run of one cell; returns the result object."""
    out = out or sys.stdout

    def say(line: str) -> None:
        print(f"# {line}", file=out, flush=True)

    jax = import_program()
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform != "tpu":
            raise NoAccelerator(
                f"JAX found no TPU (platform {devices[0].platform!r})")
        if len(devices) < int(cell["chips"]):
            raise NoAccelerator(f"the cell needs {cell['chips']} chips, JAX "
                                f"sees {len(devices)}")
    peaks = (peaks_mod.peaks_for(devices[0].device_kind)
             if require_accelerator else {})
    say(f"device platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"compile_cache={jax.config.jax_compilation_cache_dir}")

    from repro.service import (BacklogFull, ClusteringService,
                               EnergyBudgetExceeded, MiningClient,
                               RateLimited)

    shed_types = (BacklogFull, RateLimited, EnergyBudgetExceeded)
    compile_times: List[tuple] = []

    def on_event(name, _secs, **kw):
        if name == COMPILE_EVENT:
            compile_times.append((time.perf_counter(),
                                  str(kw.get("fun_name", ""))))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    cache_events: Dict[str, int] = {}

    def on_cache_event(name, **_kw):
        kind = name.rsplit("/", 1)[-1]
        if kind in ("cache_hits", "cache_misses"):
            key = kind[len("cache_"):]
            cache_events[key] = cache_events.get(key, 0) + 1

    jax.monitoring.register_event_listener(on_cache_event)

    t_gen = time.perf_counter()
    reqs = loadgen.plan(config, traffic, seed, seconds)
    loadgen.make_data(config, seed, reqs)
    say(f"set-up: {len(reqs)} requests made in "
        f"{time.perf_counter() - t_gen:.3f} s")
    say(host_memory_line("data"))

    workdir = tempfile.mkdtemp(prefix="bench-service-")
    service = ClusteringService(workdir)
    client = MiningClient(service=service)
    tmp_trace = None
    try:
        service.start()
        w = warm_mod.warm(service, config, traffic, seed)
        say(f"set-up: warm-up ran {w['warm_runs']} items in "
            f"{w['warm_s']:.3f} s (slowest {w['slowest_item_s']:.3f} s); "
            f"{len(compile_times)} programs built so far, "
            f"{cache_events.get('hits', 0)} read from the compilation "
            f"cache, {cache_events.get('misses', 0)} compiled")
        say(host_memory_line("warm-up"))
        if trace:
            tmp_trace = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(tmp_trace, profiler_options=opts)
        setup_s = time.perf_counter() - process_t0
        if traffic["loop"] == "open":
            window = drive.open_loop(client, reqs, seconds, shed_types)
        else:
            window = drive.closed_loop(client, reqs, seconds,
                                       int(traffic["clients"]), shed_types)
        t_end = max([window.t1] + [r.done for r in window.records
                                   if r.done is not None])
        wall_end = window.wall_t0 + (t_end - window.t0)
        summary = None
        if trace:
            jax.profiler.stop_trace()
        device = _device_report(devices[: int(cell["chips"])])
        spans = []
        if trace:
            ids = {r.handle.trace_id for r in window.records
                   if r.handle is not None}
            spans = [s for s in service.export_trace()
                     if s.get("trace_id") in ids]
    finally:
        service.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    say(host_memory_line("window"))

    built = [name for t, name in compile_times if window.t0 <= t <= t_end]
    compiles = len(built)
    if trace:
        devs = trace_reduce.load(trace_reduce.find_xplane(tmp_trace))
        devs = devs[: int(cell["chips"])]
        t0_ns = int(window.wall_t0 * 1e9)
        summary = trace_reduce.summarize(devs, t0_ns, int(wall_end * 1e9))
        shutil.rmtree(tmp_trace, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        say(host_memory_line("trace"))

    lanes: Dict[str, int] = {}
    for r in window.records:
        if r.result is not None:
            lane = str(r.result.get("executor"))
            lanes[lane] = lanes.get(lane, 0) + 1
    shed = sum(r.shed is not None for r in window.records)
    errors = sum(r.error is not None for r in window.records)
    say(f"lanes (answered requests per lane): "
        f"{json.dumps(lanes, sort_keys=True)}")
    if window.lateness:
        late = np.asarray(window.lateness) * 1e3
        p50 = float(np.percentile(late, 50))
        say(f"open-loop generator lateness: p50 {p50!r} ms, max "
            f"{float(late.max())!r} ms over {len(late)} sends")
    say(f"peak_bytes_in_use per device: {device.pop('per_device_peak_bytes')}")
    say(f"window: {len(window.records)} sent, {shed} shed, {errors} failed, "
        f"{window.unanswered} unanswered; {compiles} programs built between "
        f"the first send and the last answer {sorted(set(built))[:20]}")

    ctx = Context(window, setup_s, spans, summary, peaks, compiles)
    values = {}
    for m in metrics:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t_ref = time.perf_counter()
    sample = check_sample(window, traffic, seed)
    rows = [reference_numbers(r) for r in sample]
    numbers = compare.worst(rows)
    numbers["unanswered"] = float(window.unanswered + errors)
    correct, checks = compare.judge(numbers, config["limits"])
    say(f"reference: {len(sample)} answers re-computed in "
        f"{time.perf_counter() - t_ref:.3f} s")
    say(host_memory_line("reference"))

    result = {"correct": bool(correct), "attempted": len(window.records),
              "failed": shed + errors + window.unanswered,
              "metrics": values, "device": device}
    if trace:
        result["breakdown"] = trace_reduce.breakdown(summary, spans)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        cell = find_cell(bench, args.workload)
        config = load_json(BENCH, "configs", f"{cell['config']}.json")
        traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
        result = run_cell(cell, config, traffic, args.seed, args.seconds,
                          bool(args.trace),
                          cell_metrics(bench, cell, bool(args.trace)))
    except (NoAccelerator, FileNotFoundError, KeyError,
            peaks_mod.UnknownDeviceKind) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for line in compare.check_lines(result["checks"]):
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
