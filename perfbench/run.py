"""The benchmark's command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

resolves the cell in BENCHMARK.json to its configuration, traffic mix,
family and driver BY NAME (lib/manifest.py), runs it on the accelerator
jax finds, and prints one JSON object as the last line of stdout. With
`--trace 0` its metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read by `metrics/<name>.py`.

It fails, printing no result, when jax finds no TPU or fewer chips than
the cell asks for. `--rehearse` (the tests' CPU run at tiny widths, asked
for by name together with JAX_PLATFORMS=cpu) is the one exception: its
line says `platform: cpu` and carries no metric.

Not part of a measured run: `--control <precision>` puts the lower
precision in the program's place (the serving engine's own
`weight_quant`, or the reference's fp8 matmuls for training) and must
come out not correct; `--manifest` points at another BENCHMARK.json.
"""
import argparse
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.realpath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(HERE, "lib"))
sys.path.insert(0, ROOT)

import manifest as mf  # noqa: E402
import measure  # noqa: E402
import trace as xtrace  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--control", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates: one window each")
    return ap.parse_args(argv)


def scratch_dir():
    """Traces and the like: inside the checkout, at a fixed path."""
    d = os.path.join(ROOT, ".perfbench")
    os.makedirs(d, exist_ok=True)
    return d


def is_cold(cache_dir):
    try:
        return not any(os.scandir(cache_dir))
    except FileNotFoundError:
        return True


def trace_numbers(ctx, out):
    """busy_s / window_s and the breakdown, from the run's own trace."""
    path = xtrace.find_xplane(ctx["trace_dir"])
    keep = lambda plane, line: (
        plane.startswith(xtrace.DEVICE_PREFIX)
        and line in (xtrace.OPS_LINE, xtrace.MODULES_LINE)
    ) or plane.startswith(xtrace.HOST_PREFIX)
    t = xtrace.load(path, keep_lines=keep)
    b = xtrace.busy(t)
    if b is None:
        raise RuntimeError("the trace holds no device operation")
    return t, b, {"device_ops": xtrace.top_ops(t, 10),
                  "idle_gaps": xtrace.idle_gaps(t, 10)}


def main(argv=None):
    args = parse(argv)
    man = mf.Manifest(args.manifest)
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    mix = man.traffic(cell)
    family = man.family(cfg)
    driver = man.driver(cfg)

    from paddle_tpu.framework.platform import (compile_cache_dir,
                                               init_platform)
    platform = init_platform()      # raises with no TPU unless cpu is named
    import jax
    if platform != "tpu" and not args.rehearse:
        print(f"perfbench: needs a TPU, jax found {platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < cell["chips"]:
        print(f"perfbench: {args.workload} needs {cell['chips']} chips, jax "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 3
    devices = jax.devices()[:cell["chips"]]
    cold = platform == "tpu" and is_cold(compile_cache_dir())
    measure.cache_everything()
    watch = measure.CompileWatch().install()
    trace_dir = os.path.join(scratch_dir(), "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"perfbench: {args.workload} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace} on {len(devices)} x "
          f"{devices[0].device_kind} ({platform}); compile cache "
          f"{compile_cache_dir()} {'cold' if cold else 'warm'}", flush=True)

    ctx = dict(manifest=man, cell=cell, config=cfg, traffic=mix,
               family=family, seed=args.seed, seconds=args.seconds,
               trace=bool(args.trace), trace_dir=trace_dir, watch=watch,
               devices=devices, t_start=T_START, cold=cold,
               control=args.control, rehearse=args.rehearse,
               sweep=[float(x) for x in args.sweep.split(",")]
               if args.sweep else None, sweep_check=bool(args.sweep),
               weight_quant=args.control if cfg["mode"] == "serve" else None)
    out = driver.run(ctx)

    setup = watch.mark()
    print(f"[counts] {json.dumps(out['counts'])}", flush=True)
    for k in sorted(out["metrics"]):
        print(f"[measured] {k} = {out['metrics'][k]}", flush=True)

    device = dict(out["facts"]["device"])
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["counts"]["attempted"]),
              "failed": int(out["counts"]["failed"]), "metrics": {},
              "device": device, "counts": out["counts"]}
    if args.rehearse or args.control or args.sweep:
        result["not_a_measured_run"] = ("rehearsal" if args.rehearse else
                                        args.control or "sweep")
        return finish(result, out["checks"])
    if not args.trace:
        for m in man.end_to_end_of(cell["name"]):
            result["metrics"][m["name"]] = measure.metric(
                out["metrics"][m["name"]], m["unit"])
    else:
        trace, b, breakdown = trace_numbers(ctx, out)
        device["busy_s"], device["window_s"] = b["busy_s"], b["window_s"]
        result["breakdown"] = breakdown
        rctx = dict(ctx, out=out, trace=trace, busy=b, compile_total=setup,
                    peaks=measure.peaks_for(devices[0].device_kind))
        for m in man.per_layer_of(cell["name"]):
            value = man.reader(m)(rctx)
            if value is not None:
                result["metrics"][m["name"]] = measure.metric(value,
                                                              m["unit"])
    return finish(result, out["checks"])


def finish(result, checks):
    """Each number compared beside its limit: the last lines of standard
    output before the result line and of standard error, and the result
    line's last key."""
    for row in checks:
        extra = {k: v for k, v in row.items()
                 if k not in ("name", "value", "limit", "ok")}
        text = (f"[check] {row['name']}: {row['value']} (limit "
                f"{row['limit']}) {'ok' if row['ok'] else 'NOT OK'} "
                f"{extra or ''}")
        print(text, flush=True)
        print(text, file=sys.stderr, flush=True)
    result["checks"] = checks
    measure.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
