#!/usr/bin/env python3
"""chipbench/run.py -- one run of one cell of the on-chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip. A cell is an entry of ``workloads`` in
``BENCHMARK.json``: ``configs/<config>.json`` (what data) under
``traffic/<traffic>.json`` (which queries, what happens to the HBM column
cache). Set-up: the data for ``--seed`` (made under ``.cache/`` in a process
pool before JAX is imported, if absent), the device check, warm-up passes
until one compiles nothing. Then the window, made of whole passes (see
``window.py``), each query through ``daft_tpu.read_parquet -> query builder
-> .to_pydict()``. Then, outside both, every answer of the window against
the plain reference (``reference/<q>.py``, float64 on the same files).

``--trace 0`` prints the cell's end-to-end metrics. ``--trace 1`` splits
the window: passes under ``jax.profiler`` (ten seconds at most), then up to
three passes with the device tier off, and prints the per-layer metrics.
The last line of standard output is the result as one JSON object.

``--rehearse`` walks all of it on the CPU at the configuration's
``rehearse_scale_factor``, adds the lower-precision control, and never
reports success. ``--control`` adds the control to a run on the chip.
Without a TPU of a kind in ``peaks.PEAKS`` nothing is measured.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import datetime  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import answers, datagen, window  # noqa: E402

#: longest stretch of a traced run's window spent under the profiler
TRACE_SECONDS = 10.0
#: passes with the device tier off in a traced run, at most
HOST_PASSES = 3


def say(msg: str = "") -> None:
    print(msg, flush=True)


class Refused(SystemExit):
    """The run cannot measure anything: exit non-zero, print no result."""

    def __init__(self, why: str):
        print(f"chipbench: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(f"missing {os.path.relpath(path, ROOT)}") from None


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise Refused(f"no workload {name!r} in BENCHMARK.json; it has "
                  f"{[c['name'] for c in bench['workloads']]}")


def metrics_of(bench: dict, group: str, cell: str) -> List[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


@dataclasses.dataclass
class PassResult:
    wall_s: float
    start_s: float
    end_s: float
    plan_s: float
    uploaded_bytes: int
    #: (query, its answer or the exception it raised, device failures
    #: that appeared while it ran)
    answers: list


@dataclasses.dataclass
class Context:
    """What a metric's reader may read (``end_to_end/<name>.py`` and
    ``layer_metrics/<name>.py``: ``read(ctx) -> float or None``)."""
    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    passes: List[PassResult]          # the window's device-tier passes
    host_passes: List[PassResult]     # traced run: device tier off
    queries: Dict[str, object]        # name -> chipbench.queries.<name>
    table_rows: Dict[str, int]        # rows in the files, per table
    counters: Dict[str, Dict[str, float]]   # delta over ``passes``
    compiles_in_window: int
    memory_peak_bytes: int
    peaks: Optional[dict]
    trace: Optional[object] = None    # xplane.TraceSummary of a traced run

    @property
    def walls(self) -> List[float]:
        return [p.wall_s for p in self.passes]


# ------------------------------------------------------------- set-up

def prepare_data(config: dict, seed: int, rehearse: bool) -> str:
    sf = config["rehearse_scale_factor"] if rehearse else \
        config["scale_factor"]
    t0 = time.time()
    root = datagen.ensure_dataset(
        os.path.join(ROOT, ".cache", "chipbench"), config["name"], sf,
        config["parts"], config["tables"], seed, os.cpu_count() or 1)
    say(f"[data] {config['name']} SF{sf:g} seed={seed} parts="
        f"{config['parts']} tables={len(config['tables'])} -> "
        f"{os.path.relpath(root, ROOT)} ({time.time() - t0:.1f}s)")
    return root


def check_device(cell: dict, rehearse: bool):
    import jax
    from chipbench import peaks
    devs = jax.devices()
    d0 = devs[0]
    say(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__}")
    if rehearse:
        say("[device] REHEARSAL on whatever JAX finds: this run measures "
            "nothing and cannot succeed")
        return devs, peaks.PEAKS.get(d0.device_kind)
    if d0.platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0].platform is "
                      f"{d0.platform!r} (--rehearse walks the run on the "
                      f"CPU)")
    if d0.device_kind not in peaks.PEAKS:
        raise Refused(f"device kind {d0.device_kind!r} is not in "
                      f"chipbench/peaks.py")
    if len(devs) < cell["chips"]:
        raise Refused(f"{cell['name']} needs {cell['chips']} chips, JAX "
                      f"finds {len(devs)}")
    return devs, peaks.PEAKS[d0.device_kind]


def table_rows(root: str, tables) -> Dict[str, int]:
    import pyarrow.parquet as pq
    from chipbench.reference import common
    return {t: sum(pq.ParquetFile(p).metadata.num_rows
                   for p in common.files(root, t)) for t in tables}


# ------------------------------------------------------------ a pass

def run_pass(engine, queries: Dict[str, object], traffic: dict,
             label: str) -> PassResult:
    from jax.profiler import TraceAnnotation
    clear = traffic["cache"] == "clear-before-each-query"
    plan_s = 0.0
    uploaded = 0
    out = []
    start = time.perf_counter()
    with TraceAnnotation(label):
        for q in traffic["queries"]:
            failures = engine.failures()
            try:
                if clear:
                    with TraceAnnotation("clear-cache"):
                        engine.clear_cache()
                held = engine.cache_bytes()
                t0 = time.perf_counter()
                with TraceAnnotation(f"plan:{q}"):
                    df = queries[q].build(engine.get_df)
                plan_s += time.perf_counter() - t0
                with TraceAnnotation(f"execute:{q}"):
                    got = df.to_pydict()
                uploaded += max(0, engine.cache_bytes() - held)
            except Exception as exc:  # a query that raises is a failed one
                got = exc
            out.append((q, got, engine.failures() - failures))
    end = time.perf_counter()
    return PassResult(end - start, start, end, plan_s, uploaded, out)


def warm_up(engine, queries, traffic, meter) -> None:
    """Passes until one compiles nothing (two at least, so that a resident
    mix is resident)."""
    for i in range(12):
        before = meter.snap()
        p = run_pass(engine, queries, traffic, f"warm:{i}")
        after = meter.snap()
        for q, got, _ in p.answers:
            if isinstance(got, Exception):
                raise got
        say(f"[warm] pass {i}: {p.wall_s:.3f}s, compile requests "
            f"{after[0] - before[0]} (cache hits {after[1] - before[1]}, "
            f"{after[2] - before[2]:.1f}s)")
        if i >= 1 and after[0] == before[0]:
            return
    raise RuntimeError("warm-up: still compiling after 12 passes")


def run_window(engine, queries, traffic, seconds: float, label: str,
               origin: Optional[float] = None,
               most: Optional[int] = None) -> List[PassResult]:
    """Whole passes while one of the median length still fits (the first
    always starts), ``most`` at most."""
    origin = time.perf_counter() if origin is None else origin
    passes: List[PassResult] = []
    while (most is None or len(passes) < most) and window.may_start(
            time.perf_counter() - origin, [p.wall_s for p in passes],
            seconds):
        passes.append(run_pass(engine, queries, traffic,
                               f"{label}{len(passes)}"))
    return passes


def delta(after: dict, before: dict) -> dict:
    return {g: {k: v - before.get(g, {}).get(k, 0)
                for k, v in after[g].items()} for g in after}


# ------------------------------------------------- answers, outside both

def _jsonable(o):
    if isinstance(o, datetime.date):
        return {"__date__": o.isoformat()}
    raise TypeError(type(o))


def _revive(d):
    return datetime.date.fromisoformat(d["__date__"]) if "__date__" in d \
        else d


def reference_answer(root: str, q: str) -> dict:
    """Computed from the files by ``reference/<q>.py``, kept beside them."""
    path = os.path.join(root, f"_reference_{q}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f, object_hook=_revive)
    ref = importlib.import_module(f"chipbench.reference.{q}").answer(root)
    with open(path + ".tmp", "w") as f:
        json.dump(ref, f, default=_jsonable)
    os.replace(path + ".tmp", path)
    return ref


def check_answers(root: str, config: dict, passes: List[PassResult]):
    """Every answer of ``passes`` against its reference. Returns
    (attempted, failed, worst relative error per query)."""
    rtol = config["rtol"]
    attempted = failed = 0
    worst: Dict[str, float] = {}
    refs: Dict[str, tuple] = {}
    for n, p in enumerate(passes):
        for q, got, new_failures in p.answers:
            attempted += 1
            if q not in refs:
                refs[q] = (reference_answer(root, q), importlib.import_module(
                    f"chipbench.reference.{q}").COMPARE)
            try:
                if isinstance(got, Exception):
                    raise answers.Mismatch(
                        f"raised {type(got).__name__}: {got}")
                err = answers.compare(f"pass {n} {q}", got, *refs[q], rtol)
                worst[q] = max(worst.get(q, 0.0), err)
                if new_failures:
                    raise answers.Mismatch(
                        f"{new_failures} device failure(s) while it ran")
            except answers.Mismatch as bad:
                failed += 1
                if failed <= 5:
                    say(f"[check] FAILED pass {n} {q}: {bad}")
    return attempted, failed, worst


def run_control(root: str, config: dict, traffic: dict) -> bool:
    """The reference in bfloat16, put in the engine's place: the comparison
    has to refuse it. Prints each query's number beside the limit; returns
    whether some query of the mix was refused."""
    from chipbench.reference import common
    rtol = config["rtol"]
    refused = False
    for q in dict.fromkeys(traffic["queries"]):
        mod = importlib.import_module(f"chipbench.reference.{q}")
        low = answers.cut_to_answer(mod.answer(root, common.bf16),
                                    mod.COMPARE)
        ref = reference_answer(root, q)
        try:
            measured = answers.compare(f"control {q}", low, ref,
                                       mod.COMPARE, math.inf)
            shown = f"worst relative error {measured:.3e}"
        except answers.Mismatch as bad:
            shown = f"no number ({bad})"
        try:
            answers.compare(f"control {q}", low, ref, mod.COMPARE, rtol)
            verdict = "PASSED the comparison"
        except answers.Mismatch:
            verdict = "refused"
            refused = True
        say(f"[control] {q} in bfloat16: {shown} (limit {rtol:g}): "
            f"{verdict}")
    say(f"[control] the mix in bfloat16 is "
        f"{'refused, as it must be' if refused else 'NOT REFUSED'}")
    return refused


# ------------------------------------------------------------ metrics

def read_metrics(group: str, package: str, bench: dict, ctx: Context
                 ) -> Dict[str, dict]:
    out = {}
    for m in metrics_of(bench, group, ctx.cell["name"]):
        try:
            reader = importlib.import_module(
                f"chipbench.{package}.{m['name']}")
        except ModuleNotFoundError:
            raise Refused(f"metric {m['name']!r} has no reader "
                          f"chipbench/{package}/{m['name']}.py") from None
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------- run

def execute(args) -> dict:
    """The whole run; returns the result (``main`` prints it)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    queries = {q: importlib.import_module(f"chipbench.queries.{q}")
               for q in traffic["queries"]}
    say(f"[cell] {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} {traffic['queries']} cache={traffic['cache']}, "
        f"seed {args.seed}, {args.seconds:g}s, trace {args.trace}")

    root = prepare_data(config, args.seed, args.rehearse)

    if not args.rehearse:
        # one compile cache, inside the checkout, at a path that never
        # moves; the engine takes the directory this variable names
        cache_dir = os.path.join(ROOT, ".cache", "jax")
        os.makedirs(cache_dir, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    devs, peak = check_device(cell, args.rehearse)
    from chipbench.engine import Engine
    from chipbench.meter import CompileMeter
    engine = Engine(root)
    backend = engine.backend_name()
    if not args.rehearse and backend != "tpu":
        raise Refused(f"daft_tpu's backend is {backend!r}, not 'tpu'")
    meter = CompileMeter()
    rows = table_rows(root, config["tables"])

    warm_up(engine, queries, traffic, meter)
    setup_s = time.time() - T0
    say(f"[setup] {setup_s:.3f}s from process start to the window")

    trace = None
    host_passes: List[PassResult] = []
    compiles0 = meter.snap()[0]
    counters0 = engine.counters()
    if not args.trace:
        passes = run_window(engine, queries, traffic, args.seconds, "pass:")
        counters1 = engine.counters()
    else:
        import jax
        from chipbench import xplane
        trace_dir = os.path.join(ROOT, ".cache", "chipbench", "_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # our spans are enough; traces grow
        opts.host_tracer_level = 2
        origin = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            passes = run_window(engine, queries, traffic,
                                min(TRACE_SECONDS, args.seconds / 4),
                                "pass:")
        finally:
            jax.profiler.stop_trace()
        counters1 = engine.counters()
        engine.host_tier(True)
        try:
            host_passes = run_window(engine, queries, traffic, args.seconds,
                                     "host:", origin=origin,
                                     most=HOST_PASSES)
        finally:
            engine.host_tier(False)
        trace = xplane.reduce(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = meter.snap()[0] - compiles0
    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devs[:cell["chips"]])

    say(f"[window] {len(passes)} whole passes in "
        f"{passes[-1].end_s - passes[0].start_s:.3f}s of {args.seconds:g}s"
        + (f", then {len(host_passes)} with the device tier off"
           if args.trace else "")
        + f"; compile requests in the window: {compiles}")

    attempted, failed, worst = check_answers(root, config,
                                             passes + host_passes)
    for q in dict.fromkeys(traffic["queries"]):
        say(f"[check] {q}: worst relative error "
            f"{worst.get(q, float('nan')):.3e} (limit {config['rtol']:g}), "
            f"keys, counts and integers exact")
    if engine.failures():
        say(f"[check] device failures in this process: "
            f"{engine.first_failure()}")
    correct = attempted > 0 and failed == 0
    say(f"[check] attempted {attempted} failed {failed} -> correct "
        f"{correct}")

    control_refused = None
    if args.control or args.rehearse:
        control_refused = run_control(root, config, traffic)

    ctx = Context(cell=cell, config=config, traffic=traffic,
                  setup_s=setup_s, passes=passes,
                  host_passes=host_passes, queries=queries, table_rows=rows,
                  counters=delta(counters1, counters0),
                  compiles_in_window=compiles,
                  memory_peak_bytes=memory_peak, peaks=peak, trace=trace)
    if args.trace:
        metrics = read_metrics("per_layer", "layer_metrics", bench, ctx)
    else:
        metrics = read_metrics("end_to_end", "end_to_end", bench, ctx)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device,
              "passes": len(passes), "worst_relative_error": worst,
              "pass_walls_s": [round(p.wall_s, 4) for p in passes]}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in trace.device_ops],
            "idle_gaps": [list(kv) for kv in trace.idle_gaps]}
    if control_refused is not None:
        result["control_refused"] = control_refused
    return result


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the run on the CPU at a tiny scale; always "
                         "ends non-zero")
    ap.add_argument("--control", action="store_true",
                    help="also put the bfloat16 reference in the engine's "
                         "place and show that the comparison refuses it")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(load_json(ROOT, "BENCHMARK.json")["run_seconds"])
    return args


def main(argv=None) -> int:
    args = parse(argv)
    result = execute(args)
    if args.rehearse:
        for name, m in result.pop("metrics").items():
            say(f"[rehearsal] {name} = {m['value']!r} {m['unit']} (a CPU "
                f"walk at a tiny scale: not a measurement)")
        say(json.dumps({"rehearsal": True, "ok": False,
                        "correct": result["correct"],
                        "control_refused": result["control_refused"],
                        "attempted": result["attempted"],
                        "failed": result["failed"]}))
        return 1
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
