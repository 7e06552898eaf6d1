"""Tracing: of the traced queries' wall that lies in no leaf span
(``holes.us``), the share that lies under a span that says what was going
on (a submit, a drain, a launch, a prefetch, a wait in a pool's queue or at
the window gate, a hand-off in progress): 100 x (``us`` - ``unnamed_us``) /
``us`` over the traced passes together. The instrument's own coverage of
the holes. None on a program whose summaries name no holes (the parent of
PR 43), or where there is no hole."""

from chipbench import wait_spans


def read(ctx):
    holes = [s.get("holes") for inside in wait_spans.traced(ctx)
             for s in inside]
    if not holes or any(h is None for h in holes):
        return None
    total = sum(h["us"] for h in holes)
    if not total:
        return None
    return 100.0 * (total - sum(h["unnamed_us"] for h in holes)) / total
