"""perfbench — tpudl's benchmark: the yardstick later PRs are measured with.

Everything that decides a number lives here: traffic generation, the
reduction from spans and traces to metrics, the table of peaks, the
FLOP and byte counts, the plain references and the comparison that
decides ``correct``. From ``tpudl`` it takes only the system under test
and its spans, counters and kernel names.
"""
