"""What every cell of the benchmark shares, and later PRs may not change:
the closed-loop driver, the reduction from a profiler trace to numbers, the
table of peaks, the counts of operations and bytes from shapes, the seeded
parameter fill and the comparison that decides ``correct``."""
