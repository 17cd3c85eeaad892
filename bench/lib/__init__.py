"""Shared code of the benchmark: scenes, the traffic loops, the float64
oracle and its lower-precision control, the trace reader, and the
roofline work counts with the peaks table."""
