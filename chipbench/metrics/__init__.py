"""One reader per metric, found by the metric's name in BENCHMARK.json.

``chipbench/metrics/<name>.py`` defines ``read(rec)``, which takes the
window's record (built by ``harness.record``) and returns the number, or
None where the record holds nothing to read it from; the harness then
leaves the metric out of the line.
"""
