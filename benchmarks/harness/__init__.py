"""The benchmark's yardstick: traffic, reduction, peaks, operation counts,
the plain reference and the two regime harnesses. Nothing here names a
cell or a configuration; those are data under ../configs, ../workloads
and ../layer_metrics."""
