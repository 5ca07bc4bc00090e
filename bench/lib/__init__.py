"""The benchmark's yardstick: spec loading, traffic, statistics, peaks,
operation and byte counts, and the reduction of profiler traces."""
