"""API / plan: milliseconds a pass spends in the optimizer and the
translation to a physical plan (the program's ``plan:optimize`` and
``plan:translate`` spans), median over the traced passes. ``plan_ms`` beside
it is the benchmark's own span around the query builders, which come
before."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "plan:optimize", "plan:translate")
