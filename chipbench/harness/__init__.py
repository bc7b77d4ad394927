"""The benchmark's harness: traffic, weights, the serving loop, the trace
reduction, roofline counts and the correctness check."""
