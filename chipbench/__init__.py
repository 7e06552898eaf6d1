"""The on-chip benchmark of daft-tpu (``BENCHMARK.json`` at the root names
its cells and metrics; ``PERF.md`` says why each is there).

Driven by data: the harness finds everything by the name ``BENCHMARK.json``
gives, so a later PR adds a cell, a query or a metric as new files and
entries and edits nothing here.

- ``run.py``: one run of one cell (set-up, window of whole passes, answers
  against the references, metrics, the result line).
- a cell = ``configs/<config>.json`` (what data, which guarantees, ``rtol``)
  + ``traffic/<traffic>.json`` (which queries, the HBM cache's policy).
- a query = ``queries/<q>.py`` (``build(get_df)``; the public API only) +
  ``reference/<q>.py`` (``answer(root, rnd)``: pyarrow/pandas/numpy in
  float64 on the same files, and ``COMPARE``, how its answer is compared).
- a metric = ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``
  (``read(ctx)`` -> a number, or ``None`` where there is nothing to read).
- the yardstick: ``datagen.py`` (seeded TPC-H data), ``window.py`` (the
  whole-pass window), ``answers.py`` (the comparison), ``xplane.py`` (trace
  -> numbers), ``peaks.py`` (the chip's peaks, bytes a scan must read),
  ``meter.py`` (compile requests). ``engine.py`` is the only module that
  imports the program.
- ``selfcheck/``: ``python -m pytest chipbench/selfcheck`` (by hand; not part
  of ``tests/``).
"""
