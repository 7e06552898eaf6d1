"""Host operators: milliseconds a pass spends handing rows from one
operator's partitions to the next one's: the hash partitioning of
exchanges, join inputs and the fused group-by dispatcher
(``exchange:partition``) and the hash-free hand-over of an input small
enough for one reducer (``exchange:gather``); each the union over the
program's threads, added; median over the traced passes. The part of
``host_other_ms_per_pass`` that is neither ``expr:eval`` nor
``mem:size``."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "exchange:partition",
                                  "exchange:gather")
