"""Executor -> device programs: dispatches the kernel ledger recorded
during the passes (all families), per pass."""


def read(ctx):
    return sum(ctx.counters["dispatches"].values()) / len(ctx.passes)
