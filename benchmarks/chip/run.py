"""One run of one cell of the chip benchmark.

    python -m benchmarks.chip.run --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python benchmarks/chip/run.py  ...            # the same

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: the workload names a configuration (``configs/``, which
names its deployment, ``deployments/<kind>.py``) and a traffic mix
(``traffic/``), the mix names queries (``queries/<q>.sql`` with
``queries/<q>.columns.json``, whose tables are ``tables/<table>.py``, and
the plain reference ``oracles/<q>.py``), and each per-layer metric is
``layer_metrics/<metric>.json`` naming a reader in ``readers/``.  A new
cell, query, table, mix, configuration, deployment or metric adds files and
``BENCHMARK.json`` entries and edits nothing here.

The run: make the data from ``--seed`` (anew in every run, under
``.bench_data/<workload>/``, removed when the run ends), build the deployment, warm up (the pass twice per stream), open the window for
``--seconds``, close it at pass boundaries, read counters and memory, shut
the deployment down, and only then run the plain reference and compare every
answer the window produced.  The last line of standard output is the result.

It fails unless jax finds a TPU with as many chips as the cell asks for.
``--allow-cpu`` rehearses on a CPU: the last line then names the CPU, and no
device metric is reported.
"""
from __future__ import annotations

import time

T0 = time.time()       # process start, as near as python lets us see it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if __package__ in (None, ""):          # run as a file: make the package ours
    sys.path.insert(0, ROOT)
    __package__ = "benchmarks.chip"

from . import compare, datagen, trace_reduce, traffic  # noqa: E402

PKG = __package__


def say(msg: str) -> None:
    print(f"[chipbench +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_cell(workload: str) -> dict:
    """The cell's entries of ``BENCHMARK.json`` and the files they name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf_entry["file"])) as fh:
        config = json.load(fh)
    mix = traffic.load_mix(cell["traffic"])
    queries = [traffic.load_query(q) for q in mix["pass"]]

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "mix": mix, "queries": queries,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def cell_data(spec: dict, seed: int, scale=None, owner: str = ""):
    """``(directory, tables, cardinalities)`` of the cell's data for a seed,
    made now, in place of what an earlier run of ``owner`` (the workload,
    or whoever else calls) left.  The caller removes it."""
    scale = float(spec["config"]["scale"] if scale is None else scale)
    tables = sorted({t for q in spec["queries"] for t in q["columns"]})
    ddir = datagen.write_data(
        os.path.join(ROOT, ".bench_data", owner or spec["cell"]["name"]),
        scale, seed, tables)
    return ddir, tables, datagen.cardinalities(scale, tables)


def find_device(chips: int, allow_cpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": str(devs[0].device_kind),
            "count": len(devs)}
    if info["platform"] != "tpu" and not allow_cpu:
        raise SystemExit(f"no TPU: jax found {info}; this run needs the "
                         "chip (--allow-cpu rehearses on a CPU)")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, jax found {info}")
    return info


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_peaks(kind: str, platform: str):
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    if kind in peaks:
        return peaks[kind]
    if platform == "tpu":
        raise SystemExit(f"device kind {kind!r} is not in peaks.json: add "
                         "its published peaks, with their source")
    return None


def read_layer_metric(name: str, evidence: dict):
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as fh:
        spec = json.load(fh)
    reader = importlib.import_module(f"{PKG}.readers.{spec['reader']}")
    return reader.read(evidence, **spec.get("args", {}))


def oracle_answers(queries, ddir: str, money: str = "int64") -> dict:
    out = {}
    for q in queries:
        t0 = time.perf_counter()
        mod = importlib.import_module(f"{PKG}.oracles.{q['name']}")
        out[q["name"]] = mod.answer(ddir, money)
        say(f"oracle {q['name']} ({money}): "
            f"{time.perf_counter() - t0:.1f}s, {len(out[q['name']][0])} rows")
    return out


def window_answers(logs) -> list:
    """``(query, rows)`` for every query the window attempted."""
    return [(r.query, None if r.table is None
             else compare.table_rows(r.table))
            for i, log in sorted(logs.items()) for r in log.records]


class GcWatch:
    """Collections of the interpreter's garbage collector that took 50 ms
    or more while it watched, as ``[generation, seconds, seconds since the
    watch began]``.  A collection stops every thread of the process; it is
    printed beside the latencies so that a stalled query can be told from
    one the collector held.  Watching changes nothing the collector does."""

    def __init__(self):
        self.pauses, self._t = [], 0.0
        self.since = time.perf_counter()
        gc.callbacks.append(self)

    def __call__(self, phase, info) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif now - self._t >= 0.05:
            self.pauses.append([info["generation"], round(now - self._t, 3),
                                round(self._t - self.since, 3)])

    def stop(self) -> list:
        gc.callbacks.remove(self)
        return self.pauses


def _summary(values: list) -> dict:
    v = sorted(values)
    if not v:
        return {"n": 0}
    return {"n": len(v), "min": v[0],
            "median": traffic.nearest_rank(v, 0.5),
            "p90": traffic.nearest_rank(v, 0.9), "max": v[-1]}


class Tracer:
    """The profiler around a sub-window: from the window's opening until
    every stream has finished ``passes`` passes."""

    def __init__(self, directory: str, passes: int):
        self.dir, self.passes = directory, passes
        self.host_lo = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness's own spans suffice
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def window(self, gate: threading.Event, logs, threads) -> None:
        import jax

        self.host_lo = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            gate.set()
            while any(t.is_alive() for t in threads) and not all(
                    log.passes >= self.passes for log in logs.values()):
                time.sleep(0.002)
        jax.profiler.stop_trace()

    def reduce(self, logs):
        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            return None
        size = os.path.getsize(path)
        t0 = time.perf_counter()
        raw = trace_reduce.read_xplane(path)
        records = [(r.t0, r.t1, r.query) for log in logs.values()
                   for r in log.records]
        reduced = trace_reduce.reduce(raw, host_lo=self.host_lo,
                                      query_records=records)
        say(f"trace: {size} bytes, read and reduced in "
            f"{time.perf_counter() - t0:.1f}s")
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def run(args) -> int:
    spec = load_cell(args.workload)
    try:
        import arrow_ballista_tpu  # noqa: F401 — x64 and the compile cache
    except ImportError as e:
        raise SystemExit(f"the system under test is not in this checkout: "
                         f"{e}")
    import jax

    device = find_device(int(spec["cell"]["chips"]), args.allow_cpu)
    peaks = load_peaks(device["kind"], device["platform"])
    say(f"device {device}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    setup = {"start_s": time.time() - T0}

    # --- data, from the seed ------------------------------------------
    t = time.perf_counter()
    ddir, tables, cards = cell_data(spec, args.seed, args.scale)
    setup["data_s"] = time.perf_counter() - t
    say(f"data {ddir}: {setup['data_s']:.1f}s, rows {cards}")
    try:
        return measure(args, spec, device, peaks, setup, ddir, tables, cards)
    finally:
        shutil.rmtree(ddir, ignore_errors=True)


def measure(args, spec, device, peaks, setup, ddir, tables, cards) -> int:
    """Deployment, warm-up, window, counters, then the plain reference."""
    import jax

    config, mix, queries = spec["config"], spec["mix"], spec["queries"]
    # --- the deployment, and its warm-up ---------------------------------
    from arrow_ballista_tpu.obs import device as device_obs

    from .deploy import deploy

    t = time.perf_counter()
    deployment = deploy(config, ddir, tables)
    sessions = [deployment.session() for _ in range(int(mix["streams"]))]
    setup["deploy_s"] = time.perf_counter() - t
    annotate = jax.profiler.TraceAnnotation if args.trace \
        else (lambda name: contextlib.nullcontext())
    try:
        # the first pass alone (it reads the parquet and fills the scan
        # cache), the second on every stream at once (the warm run takes
        # paths the first run teaches)
        t = time.perf_counter()
        warm = traffic.StreamLog()
        traffic.run_pass(sessions[0], 0, queries, warm, annotate)
        setup["warmup_first_pass_s"] = time.perf_counter() - t
        t = time.perf_counter()
        gate = threading.Event()
        gate.set()
        second, threads = traffic.run_window(sessions, queries, 0.0,
                                             annotate, gate)
        for th in threads:
            th.join()
        setup["warmup_second_pass_s"] = time.perf_counter() - t
        failed = [r for log in [warm, *second.values()]
                  for r in log.records if r.error]
        if failed:
            raise SystemExit(f"warm-up failed: {failed[0].query}: "
                             f"{failed[0].error}")
        say(f"warm-up: first pass {setup['warmup_first_pass_s']:.1f}s, "
            f"second {setup['warmup_second_pass_s']:.1f}s")

        # --- the window ----------------------------------------------
        gate = threading.Event()
        logs, threads = traffic.run_window(sessions, queries, args.seconds,
                                           annotate, gate)
        tracer = None
        if args.trace:
            tracer = Tracer(os.path.join(ROOT, ".bench_trace",
                                         args.workload),
                            int(mix.get("trace_passes", 1)))
            tracer.start()
        s0 = device_obs.STATS.snapshot()
        jobs_before = len(deployment.submitted)
        setup_s = time.time() - T0
        gc_watch = GcWatch()
        if tracer:
            tracer.window(gate, logs, threads)
        else:
            gate.set()
        for th in threads:
            th.join()
        gc_pauses = gc_watch.stop()
        s1 = device_obs.STATS.snapshot()
        mem_peak = memory_peak_bytes()
        jobs = [dict(j, stats=deployment.job_stats(j["job_id"]))
                for j in deployment.submitted[jobs_before:]]
    finally:
        deployment.close()
    e2e = traffic.end_to_end(logs)
    counters = {k: s1[k] - s0[k] for k in s1
                if isinstance(s1[k], (int, float)) and k in s0}
    compiled = counters.get("jit_compiles", 0) + counters.get(
        "jit_retraces", 0)
    emit({"workload": args.workload, "seed": args.seed, "setup": setup,
          "window": e2e,
          "queries_per_s": int(mix["streams"]) / e2e["query_s"]
          if e2e.get("query_s") else None,
          "latency_s": {q["name"]: _summary(
              [r.t1 - r.t0 for log in logs.values() for r in log.records
               if r.query == q["name"] and not r.error]) for q in queries},
          "programs_compiled_in_window": compiled,
          "gc_pauses_in_window": gc_pauses,
          "slowest": sorted(([round(r.t1 - r.t0, 3), r.query, r.stream,
                              round(r.t0 - logs[r.stream].opened, 3)]
                             for log in logs.values() for r in log.records),
                            reverse=True)[:4],
          "counters": {k: counters[k] for k in sorted(counters)
                       if counters[k]},
          "jobs_in_window": len(jobs)})
    errors = [r for log in logs.values() for r in log.records if r.error]
    for r in errors[:3]:
        say(f"FAILED {r.query} on stream {r.stream}: {r.error}")

    # --- the plain reference, now that the window has closed, memory is
    # read and the deployment is gone
    answers = window_answers(logs)
    t = time.perf_counter()
    verdict = compare.judge(answers, oracle_answers(queries, ddir),
                            config["correct"])
    say(f"reference and comparison: {time.perf_counter() - t:.1f}s")

    metrics = {}
    dev = dict(device, memory_peak_bytes=mem_peak)
    result = {"correct": verdict["correct"], "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": dev}
    if not args.trace:
        values = {"setup_s": setup_s, "query_s": e2e.get("query_s")}
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        reduced = tracer.reduce(logs)
        evidence = {
            "workload": args.workload, "config": config, "mix": mix,
            "queries": {q["name"]: q for q in queries},
            "cardinalities": cards, "window": e2e, "counters": counters,
            "jobs": jobs, "setup": setup, "trace": reduced,
            "peaks": peaks}
        for m in spec["per_layer"]:
            value = read_layer_metric(m["name"], evidence)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced and not reduced["simulated_device"]:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        elif reduced:
            say(f"rehearsal trace (host events standing in, not a device "
                f"number): {json.dumps(reduced)[:1500]}")
    result["compared"] = verdict["numbers"]
    if verdict["first_fault"]:
        say(f"NOT CORRECT: {verdict['first_fault']}")
    for name, n in verdict["numbers"].items():
        print(f"compared {name} = {n['value']} (limit {n['limit']})",
              file=sys.stderr, flush=True)
    emit(result)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on a CPU; the last line then names it")
    ap.add_argument("--scale", type=float, default=None,
                    help="rehearsal only: a scale other than the "
                         "configuration's")
    args = ap.parse_args(argv)
    if args.scale is not None and not args.allow_cpu:
        raise SystemExit("--scale is for --allow-cpu rehearsals: a cell "
                         "runs at its configuration's scale")
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
