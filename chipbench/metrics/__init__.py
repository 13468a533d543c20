"""One reader per metric, found by the metric's name in BENCHMARK.json.

``chipbench/metrics/<name>.py`` defines ``read(rec)``, which takes the
window's record (built by ``harness.run``) and returns the number, or
None where the record holds nothing to read it from; the harness then
leaves the metric out of the line. The record holds

* ``reads``, ``window_s``, ``bytes``, ``latencies`` (s), ``setup_s``,
  ``stored_bytes``, ``logical_bytes``, ``compiles``, ``kernel_bytes``
  (one entry a read) and ``peaks`` (``peaks.json``'s row for the chip);
* ``io``: the window's delta of every numeric field of the store's
  ``ReadStats`` (``decode_s``, ``fetch_wait_s``, ``cache_hits``, ...);
  a field the program lacks is absent;
* ``counters``: the window's delta of the kind's own counters
  (``Built.counters``), empty where it gives none;
* ``spans``: in a traced run, the window's span table,
  ``{name: {"count", "total_s", "self_s"}}`` for each span that ran in
  it; None in an untraced run;
* ``trace``: the traced window reduced (``trace.Summary``), or None.
"""
