"""Host operators: milliseconds a pass spends in the host's projections and
filters (``expr:eval``), in the hash partitioning of exchanges and join
inputs (``exchange:partition``) and in sizing partitions for the spill
buffers and the join's pair budget (``mem:size``); each the union over the
program's threads, added; median over the traced passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "expr:eval", "exchange:partition",
                                  "mem:size")
