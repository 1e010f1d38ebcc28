"""perfbench — the benchmark's own directory (see perfbench/README.md).

Everything the yardstick needs lives here, where PRs that change the
program cannot change it: the generator, the plain reference, the FLOP
and byte counts, the table of peaks, the trace reduction, the comparison
that decides ``correct``. From the program it takes only the system under
test (``graphlearn_tpu``) and its counters, spans and program names.
"""
