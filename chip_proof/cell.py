#!/usr/bin/env python3
"""One run of chipbench/run.py in this process, from a side's root, with what
the result line does not carry written beside it:
    python3 ../cell.py <label> <run.py arguments...>
-> /root/repo/chiprun_out/<label>.json"""
import json
import os
import resource
import sys
import time


def main():
    label, argv = sys.argv[1], sys.argv[2:]
    out_dir = "/root/repo/chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    sys.path.insert(0, os.getcwd())
    t0 = time.time()
    if os.environ.get("CELL_SWITCH_S"):
        # an experiment, not a setting of the program: the interpreter's
        # GIL switch interval (5 ms by default) for this one run
        sys.setswitchinterval(float(os.environ["CELL_SWITCH_S"]))
    from chipbench import run, xplane
    orig = run.say
    run.say = lambda msg="": orig(f"+{time.time() - t0:7.1f}s {msg}")
    rec = {"label": label, "argv": argv, "cwd": os.getcwd(),
           "env": {k: v for k, v in os.environ.items()
                   if k.startswith(("DAFT_TPU", "CELL_"))},
           "switch_interval_s": sys.getswitchinterval()}
    real_reduce = xplane.reduce

    def reduce(path, *a, **kw):
        if os.environ.get("CELL_PROFILE"):
            # run.py deletes its profile once reduced: read it by hand here
            try:
                sys.path.insert(0, "/root/repo/chip_proof")
                import launch_split
                import plane_split
                rec["xplane_MB"] = os.path.getsize(path) / 1e6
                rec["plane_split"] = plane_split.split(path)
                rec["launch_split"] = launch_split.split(path)
                if rec["xplane_MB"] < 200:
                    import gzip
                    import shutil
                    with open(path, "rb") as src, gzip.open(os.path.join(
                            out_dir, label + ".xplane.pb.gz"), "wb") as dst:
                        shutil.copyfileobj(src, dst)
            except BaseException as e:
                rec["launch_split_error"] = repr(e)
        ts = real_reduce(path, *a, **kw)
        rec["trace"] = {"module_s": ts.module_s, "module_runs": ts.module_runs,
                        "span_module_s": ts.span_module_s, "span_count": ts.span_count,
                        "busy_s": ts.busy_s, "window_s": ts.window_s, "passes": ts.passes}
        return ts
    xplane.reduce = reduce
    kept = []
    if os.environ.get("CELL_SPANS"):
        # every span of every traced query, for a timeline (PR 42) and for
        # span_checks.py (PR 43)
        from daft_tpu import tracing as _tr
        real_finish = _tr.SpanRecorder.finish

        def finish(self, status=None):
            real_finish(self, status)
            kept.append({"t0_us": self._root_t0, "wall_us": self._root_dur,
                         "spans": self.spans()})
        _tr.SpanRecorder.finish = finish
    rc = 1
    try:
        args = run.parse(argv)
        result = run.execute(args)
        run.say(json.dumps(result))
        rec["result"] = result
        rc = 0
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
        rec["exit"] = repr(e)
    except BaseException as e:
        import traceback
        traceback.print_exc()
        rec["error"] = repr(e)
    try:
        from daft_tpu import tracing
        from daft_tpu.device import costmodel
        fin = list(tracing.finished())
        keep = []
        rec["dropped_max"] = max((s.get("dropped", 0) for s in fin), default=None)
        rec["spans_max"] = max((s.get("spans", 0) for s in fin), default=None)
        for s in fin[-12:]:
            keep.append({k: s.get(k) for k in ("wall_us", "covered_us", "tables", "selects", "agg_launches", "joins", "plan", "decode", "chips", "t0_perf_s",
                                               "spans", "dropped", "holes", "handoffs", "waits_short")}
                        | {"phases": {n: {k: p.get(k) for k in ("count", "wall_us", "sum_us", "bytes", "cpu_us", "timed_us")}
                                      for n, p in (s.get("phases") or {}).items()}})
        rec["last_traces"] = keep
        rec["decisions"] = dict(costmodel.decision_counts)
        lp = costmodel.link_profile()
        rec["link"] = {"rtt_ms": lp.rtt_s * 1e3, "up_GBps": lp.up_bps / 1e9, "down_GBps": lp.down_bps / 1e9}
        rec["ledger"] = costmodel.ledger_snapshot(raw=True)
        from daft_tpu.device import cache
        st = cache.get_cache().stats()
        rec["cache"] = {k: st[k] for k in ("entries", "bytes", "hits", "misses", "put_bytes")}
    except BaseException as e:
        rec["post_error"] = repr(e)
    if kept:
        try:
            sys.path.insert(0, "/root/repo/chip_proof")
            import span_checks
            rec["span_checks"] = span_checks.check(kept)
            rec["unnamed"] = span_checks.unnamed(kept)
        except BaseException as e:
            rec["span_checks_error"] = repr(e)
        with open(os.path.join(out_dir, label + ".spans.json"), "w") as f:
            json.dump(kept[-int(os.environ.get("CELL_SPANS_KEEP", "9")):], f, default=str)
    rec["maxrss_GB"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    rec["took_s"] = time.time() - t0
    with open(os.path.join(out_dir, label + ".json"), "w") as f:
        json.dump(rec, f, default=str)
    return rc


if __name__ == "__main__":
    sys.exit(main())
