"""The benchmark's own code: resolution by name, store generation,
trace reduction, the peaks table, work counts and the result line."""
