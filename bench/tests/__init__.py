"""Tests of the benchmark harness, on the CPU at small sizes (Pallas in
interpret mode); none of them needs, or loads, a TPU library."""
