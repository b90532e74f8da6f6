"""Shared code of the chip benchmark: the yardstick that later changes to
the program are measured with (peaks, operation and byte counts, traffic,
trace reduction, the plain reference and the measured window)."""
