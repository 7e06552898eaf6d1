"""Device programs: milliseconds a pass spends in the fused device join as
the host sees it (``join:device``: both sides' keys padded and put on the
chip, the one sort / probe / expand program, its packed index matrix
fetched and unpacked), the union over the program's threads, median over
the traced passes. 0 where the gate kept every pair on the host: the twin
of ``host_join_ms_per_pass``. None when the program has no such span (the
parent of PR 38 tallies no ``joins``), or no pass holds a summary."""

from chipbench import program_spans
from chipbench.layer_metrics import join_device_pairs_pct


def read(ctx):
    if not join_device_pairs_pct.joins_of(ctx):
        return None
    return program_spans.phase_ms(ctx, "join:device")
