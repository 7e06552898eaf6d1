#!/usr/bin/env python3
"""What the instrument's clocks cost and resolve on this machine (PR 43): ns a
call and the smallest step seen in a busy loop, for the wall, monotonic and
thread-CPU clocks, and what one traced span, one wait and one hand-off cost.

    python3 chip_proof/clock_cost.py -> JSON (and chiprun_out/clock_cost.json)
"""
import json
import os
import resource
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())


def per_call(fn, n=20000):
    t = time.perf_counter_ns()
    for _ in range(n):
        fn()
    return (time.perf_counter_ns() - t) / n


def step(fn, seconds=0.3):
    """Smallest and median non-zero difference between successive readings
    while this thread spins."""
    end = time.perf_counter() + seconds
    last, steps = fn(), []
    while time.perf_counter() < end:
        now = fn()
        if now != last:
            steps.append(now - last)
            last = now
    steps.sort()
    return {"n": len(steps), "min": steps[0] if steps else None,
            "median": steps[len(steps) // 2] if steps else None}


def main():
    out = {"switch_interval_s": sys.getswitchinterval()}
    clocks = {
        "time.time_ns": time.time_ns,
        "perf_counter_ns": time.perf_counter_ns,
        "thread_time_ns": time.thread_time_ns,
        "process_time_ns": time.process_time_ns,
        "clock_gettime_ns(THREAD_CPUTIME)":
            lambda: time.clock_gettime_ns(time.CLOCK_THREAD_CPUTIME_ID),
        "getrusage(THREAD).ru_utime+stime_ns": lambda: int(1e9 * (
            lambda r: r.ru_utime + r.ru_stime)(
                resource.getrusage(resource.RUSAGE_THREAD))),
    }
    for name, fn in clocks.items():
        try:
            out[name] = {"ns_a_call": round(per_call(fn), 1), "step_ns": step(fn)}
        except Exception as e:
            out[name] = repr(e)
    try:
        def sched():
            with open("/proc/thread-self/schedstat") as f:
                return int(f.read().split()[0])
        out["/proc/thread-self/schedstat"] = {
            "ns_a_call": round(per_call(sched, 2000), 1), "step_ns": step(sched)}
    except Exception as e:
        out["/proc/thread-self/schedstat"] = repr(e)
    from daft_tpu import tracing
    rec = tracing.SpanRecorder("c" * 32, max_spans=10)
    ctx = tracing.SpanContext(rec, rec.root_id)

    def a_span():
        with tracing.span("expr:eval"):
            pass

    def a_wait():
        with tracing.wait("wait:result"):
            pass
    out["untraced span() ns"] = round(per_call(a_span), 1)
    out["untraced wait() ns"] = round(per_call(a_wait), 1)
    with tracing.attach(ctx):
        out["traced span() ns (no profile)"] = round(per_call(a_span, 5000), 1)
        out["traced wait() under the floor ns"] = round(per_call(a_wait, 5000), 1)
        out["handoff() ns"] = round(per_call(lambda: rec.handoff(3), 5000), 1)
        t = time.perf_counter_ns()
        out["note_wait() under the floor ns"] = round(per_call(
            lambda: tracing.note_wait("wait:channel", t, t + 1000), 5000), 1)
        out["unique_span_id ns"] = round(per_call(
            lambda: rec.unique_span_id("x"), 5000), 1)
    print(json.dumps(out, indent=1))
    os.makedirs("/root/repo/chiprun_out", exist_ok=True)
    with open("/root/repo/chiprun_out/clock_cost.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
