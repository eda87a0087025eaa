"""The benchmark: the yardstick later PRs are measured with.

Everything here is the benchmark's own (BENCHMARK.json `paths`): the
traffic generators, the plain reference that decides `correct`, the
reduction from spans, counters and the device trace to metrics, and the
table of peaks.  From the program it takes only the system under test
and its spans, counters and kernel names.
"""
