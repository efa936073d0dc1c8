"""The benchmark's own code: it finds a cell's files by the names in
``BENCHMARK.json``, drives the training step of the system under test, reads
the trace and decides ``correct`` against a plain reference.  Nothing here
is imported by the system under test."""
