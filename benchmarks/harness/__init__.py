"""The benchmark's own code: cells, traffic, probes, the trace
reduction, the flop/byte counts and the output check.  It imports the
program only as the system under test (see run.py)."""
