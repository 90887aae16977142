"""The benchmark harness: spec, traffic, weights, driver, trace reduction
and the correctness check."""
