"""Dispatch gate: of the decisions ``costmodel.decision_counts`` tallied
during the passes, the share that chose the device."""


def read(ctx):
    d = ctx.counters["decisions"]
    total = d["device"] + d["host"]
    return 100.0 * d["device"] / total if total else None
