"""Host operators: how many times a pass hash-partitions a batch (the
``count`` of ``exchange:partition`` spans: one a call of
``RecordBatch.partition_by_hash`` or ``out_of_core.radix_split`` on a
batch with rows), median over the traced passes. A fan-out costs a take
and ``n`` slices a column whatever the rows, so few large ones beat many
small ones."""

from chipbench import program_spans


def read(ctx):
    phases = program_spans.per_pass(ctx)
    if phases is None:
        return None
    return phases.get("exchange:partition", {}).get("count", 0)
