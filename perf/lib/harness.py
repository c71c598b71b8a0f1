"""One run of one cell: load, warm, measure, check, print.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name in `BENCHMARK.json`; this
module holds only what every cell shares.
"""

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types

from . import trace as trace_lib
from . import window as window_lib
from . import work as work_lib

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_cell(root, name):
    """(cell, config entry, config, traffic) for the workload `name`."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "perf", "workloads", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def load_reference(root, config):
    """The configuration's plain reference, a file beside its sizes."""
    path = os.path.join(root, config["reference"])
    spec = importlib.util.spec_from_file_location(
        "perf_reference_" + os.path.basename(path).replace(".py", "").replace("-", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench, group, cell_name):
    """The metrics of `group` that this cell reports."""
    return [
        m for m in bench[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def make_env(root, cell, config, traffic, seed, rehearse=False, compile_events=None):
    """What a runner and `measure` are handed: the package, the cell's
    files, the seed, the plain reference, and the package's counters."""
    import jax

    import tensorframes_tpu as tfs
    from tensorframes_tpu.utils import telemetry

    return types.SimpleNamespace(
        jax=jax, tfs=tfs, root=root, cell=cell, config=config, traffic=traffic,
        seed=seed, reference=load_reference(root, config),
        counters=telemetry.flat_counters, rehearse=rehearse,
        compile_events=[] if compile_events is None else compile_events,
    )


def make_runner(env):
    return importlib.import_module("perf.runners." + env.config["runner"]).Runner(env)


def decide(compared, raised):
    """The comparison that decides `correct`: no call raised, and every
    number compared lies at or under its limit (a NaN does not)."""
    return raised == 0 and all(c["value"] <= c["limit"] for c in compared.values())


def measure(env, runner, seconds, trace_dir=None, trace_seconds=0.0):
    """Warm-up, the window, and with `trace_dir` a traced slice after it.
    Returns what the metrics read. The harness's look for a chip is not
    here, so a test can drive this with the timed path broken."""
    jax, traffic = env.jax, env.traffic
    in_flight = traffic.get("in_flight", 2)

    def wait(out):
        jax.block_until_ready(out)

    window_lib.closed_loop(
        runner.issue, wait, seconds=3600.0, in_flight=in_flight,
        max_calls=traffic.get("warmup_calls", 3),
    )
    runner.start_window()
    counters0 = dict(env.counters())
    compiles0 = len(env.compile_events)
    t_window = time.perf_counter()
    start, end, calls = window_lib.closed_loop(
        runner.issue, wait, seconds, in_flight=in_flight
    )
    summary = window_lib.summarize(start, end, calls, runner.rows_per_call)
    counters1 = dict(env.counters())
    out = types.SimpleNamespace(
        t_window=t_window, summary=summary, calls=calls,
        counters={k: counters1[k] - counters0.get(k, 0) for k in counters1},
    )
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        mark = jax.profiler.TraceAnnotation

        def issue_marked():
            with mark("perf.issue"):
                return runner.issue()

        def wait_marked(o):
            with mark("perf.wait"):
                jax.block_until_ready(o)

        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            _, _, traced = window_lib.closed_loop(
                issue_marked, wait_marked, trace_seconds, in_flight=in_flight
            )
        finally:
            jax.profiler.stop_trace()
        out.traced_calls = traced
    out.compiles_in_window = len(env.compile_events) - compiles0
    return out


def main(root, t0, argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the traffic file's tiny sizes; a CPU is accepted; "
                         "prints counts and `correct`, never a metric")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(root, args.workload)
    if args.rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}

    steps = [("start", t0)]  # where set-up goes: (step reached, when)
    import jax

    steps.append(("jax_imported", time.perf_counter()))
    devs = jax.local_devices()
    steps.append(("devices_found", time.perf_counter()))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices())}
    if not args.rehearse and (
        devs[0].platform != "tpu" or len(devs) < cell["chips"]
    ):
        print(f"perf/run.py: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"jax found {device}. Nothing measured.", file=sys.stderr)
        return 1

    compile_events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_events.append(name)
        if name == BACKEND_COMPILE else None
    )

    env = make_env(root, cell, config, traffic, args.seed, args.rehearse,
                   compile_events)
    # a rehearsal on the CPU leaves no CPU programs in the chip's cache
    cache_dir = None if args.rehearse else env.tfs.config.enable_compilation_cache()
    steps.append(("package_imported", time.perf_counter()))
    runner = make_runner(env)
    steps.append(("data_on_device", time.perf_counter()))

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, "perf", ".trace", cell["name"])
    got = measure(env, runner, args.seconds, trace_dir,
                  traffic.get("trace_seconds", 3.0))
    setup_s = got.t_window - t0
    for c in got.calls + getattr(got, "traced_calls", []):
        if c.error is not None:
            print(f"perf/run.py: a call raised: {c.error[:1500]}", file=sys.stderr)
            break
    stats = [d.memory_stats() or {} for d in devs]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device["memory_peak_bytes"] = peak
    # what the benchmark itself still holds for the check, in that peak
    held = max((s.get("bytes_in_use", 0) for s in stats), default=0)

    # the check comes last: after the window, after the peak was read
    t_check = time.perf_counter()
    compared, wrong_calls = runner.check()
    check_s = time.perf_counter() - t_check
    raised = got.summary["raised"]
    correct = decide(compared, raised)

    result = {
        "correct": bool(correct),
        "attempted": got.summary["attempted"],
        "failed": raised + wrong_calls,
    }
    extra = {
        "workload": cell["name"], "seed": args.seed, "rehearsal": args.rehearse,
        "window": {k: got.summary[k] for k in
                   ("seconds", "rows", "call_p50_ms", "verb_host_ms_per_call",
                    "call_max_ms", "slow_calls")},
        "check_s": check_s, "compilation_cache_dir": cache_dir,
        "bytes_held_after_window": held,
        "compiles_in_window": got.compiles_in_window,
        # seconds from each step of set-up to the next
        "setup_steps": {
            name: t - before
            for (_, before), (name, t) in zip(steps, steps[1:] + [("warmed", got.t_window)])
        },
    }
    if args.rehearse:
        # a rehearsal is not a chip run: counts and `correct`, no metric
        result.update(metrics={}, device=device, **extra, compared=compared)
        return finish(result, correct)

    end_to_end = {
        "rows_per_s": got.summary["rows_per_s"],
        "call_p95_ms": got.summary["call_p95_ms"],
        "peak_hbm_gib": peak / 2**30,
        "setup_s": setup_s,
    }
    if not args.trace:
        wanted = metrics_for(bench, "end_to_end", cell["name"])
        values = {m["name"]: end_to_end.get(m["name"]) for m in wanted}
    else:
        events = trace_lib.load_events(trace_lib.find_xplane(trace_dir))
        reduced = trace_lib.reduce_events(events, config["program_modules"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            print("perf/run.py: the trace holds no device plane or none of "
                  "the benchmark's marks", file=sys.stderr)
            return 1
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        for d in reduced["per_device"]:
            print(json.dumps({"device": d["plane"], "busy_s": d["busy_s"],
                              "idle_pct": d["idle_pct"]}), flush=True)
        work = work_lib.for_runner(config["runner"], config)
        ctx = types.SimpleNamespace(
            cell=cell, config=config, traffic=traffic, chips=cell["chips"],
            window=got.summary, calls=got.calls, counters=got.counters,
            compiles_in_window=got.compiles_in_window, trace=reduced,
            traced_calls=[c for c in got.traced_calls if c.error is None],
            rows_per_call=runner.rows_per_call, end_to_end=end_to_end,
            work=work, peaks=work_lib.peaks_for(device["kind"]),
            least_seconds=work_lib.least_seconds,
        )
        wanted = metrics_for(bench, "per_layer", cell["name"])
        values = {}
        for m in wanted:
            reader = importlib.import_module("perf.metrics." + m["name"])
            values[m["name"]] = reader.read(ctx)
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        extra["module_seconds"] = reduced["module_seconds"]
        extra["program_roofline_bound"] = reduced.get("program_roofline_bound")
        extra["end_to_end_of_this_run"] = end_to_end
    units = {m["name"]: m["unit"] for m in wanted}
    result["metrics"] = {
        k: {"value": v, "unit": units[k]} for k, v in values.items()
        if v is not None  # a reader that found nothing to read
    }
    result["device"] = device
    result.update(extra)
    result["compared"] = compared  # comes last
    return finish(result, correct)


def finish(result, correct):
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
