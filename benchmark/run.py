#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: reads the cell from BENCHMARK.json and the data files under
benchmark/, sets the system up (counted as `setup_s`), measures for
`--seconds`, checks the outputs, and prints as the LAST line of stdout
the JSON object the driver reads — the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`. It exits non-zero
and prints no such line when jax finds no TPU or fewer chips than the
cell asks for, or when anything it needs is missing. It never falls back
to the CPU. `--rehearse` walks the same path at toy widths on the CPU
with the kernels interpreted, to find wrong paths and arguments without
the chip, and never prints the result line.

Everything that belongs to one configuration, traffic mix, cell, model
family or per-layer metric is a file found by its name:

    configs/<config>.json          sizes as run, source, what was assumed
    traffic/<traffic>.json         parameters the one generator reads
    workloads/<cell>.json          runner, system settings, expectations
    models/<family>.py             build, loss, FLOPs and bytes arithmetic
    reference/<name>.py            the plain float32 reference
    layer_metrics/<metric>.py      `read(run)` -> value, or None; a metric
                                   split by cell kind (`<metric>.train`,
                                   `<metric>.serve`) may share `<metric>.py`
"""

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import faulthandler                                        # noqa: E402
import importlib                                           # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import sys                                                 # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: the driver allows a run 360 s (1200 s for the first in a checkout,
#: which compiles); a run that hangs dumps every thread and dies
DEADLINE_S = 1150


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """`layer_metrics/<metric>.py`, else the file of the longest dotted
    prefix: `hbm_peak_gib.train` and `.serve` are one reader."""
    name = metric
    while not os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py")):
        if "." not in name:
            raise FileNotFoundError(f"no reader for per-layer metric {metric!r} "
                                    f"under benchmark/layer_metrics/")
        name = name.rsplit(".", 1)[0]
    return load_module("layer_metrics", name)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on the CPU, kernels interpreted; never "
                         "prints the result line")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: copy the profiler's files to DIR, "
                         "to look at a trace by hand")
    args = ap.parse_args()
    faulthandler.enable()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    system = load_json("workloads", cell["name"] + ".json")

    # the compile cache lives at one fixed place inside the checkout (the
    # path is part of the cache's key), whatever the machine's environment
    # says, and without the machine's size cap: a capped cache that cannot
    # hold a cell's programs evicts them all and every run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FLAGS_pallas_interpret"] = "1"
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell['chips']}"
            ).strip()
    sys.path.insert(0, ROOT)

    import jax
    dev = jax.devices()[0]
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"no accelerator: jax reports platform {dev.platform!r}",
                  file=sys.stderr)
            return 3
    if len(jax.devices()) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chips, jax sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 3

    import paddle_tpu  # noqa: F401  (the system under test)
    from benchmark.harness import peaks, result

    ledger = result.CompileLedger()
    result.say(f"[{cell['name']}] seed {args.seed} seconds {seconds:g} trace "
               f"{args.trace} on {len(jax.devices())} x {dev.device_kind} "
               f"({dev.platform}); compile cache "
               f"{jax.config.jax_compilation_cache_dir}; imports took "
               f"{time.perf_counter() - T_START:.1f}s")
    if not args.rehearse:
        peaks.peaks_for(dev.device_kind)          # unknown chip: an error
    trace_dir = os.path.join(ROOT, ".bench_trace",
                             f"{cell['name']}.{os.getpid()}")
    run = result.Run(
        cell=cell["name"], config=config, mix=mix, system=system,
        chips=cell["chips"], seed=args.seed, seconds=seconds,
        traced=bool(args.trace), rehearse=args.rehearse, t_start=T_START,
        deadline=T_START + DEADLINE_S, trace_dir=trace_dir,
        model=load_module("models", config["model"]),
        device_kind=dev.device_kind)
    reference = load_module("reference", run.model.REFERENCE)
    runner = importlib.import_module(
        f"benchmark.harness.{system['runner']}_runner")
    try:
        runner.run(run, ledger, reference)
    finally:
        if run.traced:
            import shutil
            if args.keep_trace and os.path.isdir(trace_dir):
                shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
            shutil.rmtree(trace_dir, ignore_errors=True)
            if not os.listdir(os.path.dirname(trace_dir)):
                os.rmdir(os.path.dirname(trace_dir))

    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for m in wanted:
        if not applies(m, cell["name"]):
            continue
        if args.trace:
            value = load_reader(m["name"]).read(run)
        else:
            value = run.e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = result.device_block(run)
    result.say(f"  compiles {ledger.compiles} ({ledger.compile_s:.1f}s), "
               f"persistent cache hits {ledger.hits} misses {ledger.misses}")
    result.say("  notes " + json.dumps(run.notes))
    result.say("  e2e " + json.dumps(run.e2e))
    result.say("  counts " + json.dumps(run.counts))
    faulthandler.cancel_dump_traceback_later()
    if args.rehearse:
        result.say(f"rehearsal only: no result line (correct={run.correct}, "
                   f"metrics {sorted(metrics)})")
        return 0 if run.correct else 1
    if args.trace and not run.trace:
        print("traced run, but no operation ran on the device",
              file=sys.stderr)
        return 4
    print(result.result_line(run, metrics, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
