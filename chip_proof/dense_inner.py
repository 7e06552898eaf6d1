#!/usr/bin/env python3
"""The dense grouped aggregate's inner loops, timed on the chip inside Q1's
fused program (PR 47: where does ``kernels.DENSE_MASKED_MAX_SLOTS`` lie?).

    python3 chip_proof/dense_inner.py [out.json] [<dims>:<inner> ...]

``<dims>`` as ``4,2`` (K = prod(d + 1) slots), ``<inner>`` ``masked`` or
``matmul``: the bound is set for the trace, nothing else of the program is
touched. Each case is Q1's ``run_packed`` (``tests/test_tpu_compile.py::
_q1_program``) over one 4 194 304-row table of 3.75 M live rows, as the
resident cells launch it: compile seconds, then the module's device time and
its largest operations from a profile of ``RUNS`` launches
(``plane_split.split``), and the wall of the launches. ``error`` is the
worst relative error of ``grouped_agg_dense_impl``'s seven f32 sums in Q1's
layout against numpy in float64, and whether the counts are equal.
"""
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "tests"), HERE]
C = int(os.environ.get("ROWS", 4194304))   # smaller: a rehearsal on the CPU
LIVE, RUNS = C * 3750000 // 4194304, 20
CASES = ["4,2:matmul", "4,2:masked", "8,2:matmul", "8,2:masked",
         "8,4:matmul", "8,4:masked", "64,32:matmul"]


def q1_inputs(prog, dims, rng):
    import jax.numpy as jnp
    arrays = {}
    for name, dt in prog.in_np_dtypes.items():
        if name in prog.key_sources:
            d = dims[prog.key_sources.index(name)]
            a = rng.integers(0, d, C).astype(dt)
        elif name == "l_shipdate":
            a = rng.integers(8036, 10561, C).astype(dt)   # 1992 .. 1998
        else:
            a = rng.uniform(0.0, 1.0, C).astype(dt) * (
                1e5 if name == "l_extendedprice" else
                50 if name == "l_quantity" else 0.1)
        arrays[name] = jnp.asarray(a)
    valids = {n: jnp.ones((C,), jnp.bool_) for n in arrays}
    return arrays, valids, jnp.asarray(np.arange(C) < LIVE), ()


def sums_error(dims, rng):
    """``grouped_agg_dense_impl`` alone in Q1's layout against float64."""
    import jax
    import jax.numpy as jnp
    from daft_tpu.device import kernels
    K = kernels.dense_slots(dims)
    out_cap = max(128, 1 << (K - 1).bit_length())
    keys = [rng.integers(0, d, C).astype(np.int32) for d in dims]
    vals = [rng.uniform(0.0, s, C).astype(np.float32)
            for s in (50, 1e5, 1e5, 1e5, 0.1, 50, 1e5)]
    mask = np.arange(C) < LIVE
    ops = ("sum",) * 6 + ("count",)
    ones = jnp.ones((C,), jnp.bool_)
    fn = jax.jit(kernels.grouped_agg_dense_impl,
                 static_argnames=("ops", "out_cap", "dims"))
    _, _, ov, _, g = fn(tuple(map(jnp.asarray, keys)), (ones,) * len(keys),
                        tuple(map(jnp.asarray, vals)), (ones,) * 7,
                        jnp.asarray(mask), ops=ops, out_cap=out_cap,
                        dims=dims)
    gid = np.zeros(C, np.int64)
    for k, d in zip(keys, dims):
        gid = gid * (d + 1) + k
    live = np.unique(gid[mask])
    worst = 0.0
    for got, v in zip(ov[:6], vals[:6]):
        ref = np.bincount(gid[mask], v[mask].astype(np.float64),
                          minlength=K)[live]
        got = np.asarray(got)[:len(live)].astype(np.float64)
        worst = max(worst, float(np.max(np.abs(got - ref) / np.abs(ref))))
    counts = np.bincount(gid[mask], minlength=K)[live]
    return {"groups": int(g), "worst_rel": worst,
            "counts_equal": bool(np.array_equal(
                np.asarray(ov[6])[:len(live)], counts))}


def main():
    import jax
    import plane_split
    import test_tpu_compile as progs
    from daft_tpu.device import kernels
    argv = sys.argv[1:]
    out = argv.pop(0) if argv and argv[0].endswith(".json") else None
    prog, (cap0, _, _) = progs._q1_program()
    rng = np.random.default_rng(47)
    results = []
    for case in argv or CASES:
        dims_s, inner = case.split(":")
        dims = tuple(int(d) for d in dims_s.split(","))
        K = kernels.dense_slots(dims)
        kernels.DENSE_MASKED_MAX_SLOTS = K if inner == "masked" else K - 1
        assert kernels.dense_inner_loop(dims) == inner
        jax.clear_caches()   # the bound is no part of a trace's key
        out_cap = max(cap0, 1 << (K - 1).bit_length())
        fn = jax.jit(prog._run_packed,
                     static_argnames=("out_cap", "strategy", "dims"))
        args = q1_inputs(prog, dims, rng)
        kw = {"out_cap": out_cap, "strategy": "dense", "dims": dims}
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        rec = {"case": case, "K": K, "device": jax.devices()[0].device_kind,
               "first_call_s": time.perf_counter() - t0}
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            t0 = time.perf_counter()
            outs = [fn(*args, **kw) for _ in range(RUNS)]
            jax.block_until_ready(outs)
            rec["wall_ms_a_run"] = (time.perf_counter() - t0) / RUNS * 1e3
            jax.profiler.stop_trace()
            from chipbench import xplane
            planes = plane_split.split(xplane.find_xplane(d))["planes"]
        rec["modules"] = next(iter(planes.values()), {})
        rec["error"] = sums_error(dims, rng)
        results.append(rec)
        mods = rec["modules"]
        print(json.dumps({**rec, "modules": {
            m: {k: v for k, v in s.items() if k != "top_ops"}
            for m, s in mods.items()}}), flush=True)
        for m, s in mods.items():
            print("   ", m, json.dumps(s.get("top_ops")), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
