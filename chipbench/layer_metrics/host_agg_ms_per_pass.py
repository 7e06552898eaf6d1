"""Host operators: milliseconds a pass spends in the host aggregate
kernels, the merge of device partials included (``agg:host``), and in sort
and top-k (``sort:topn``); each the union over the program's threads,
added; median over the traced passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "agg:host", "sort:topn")
