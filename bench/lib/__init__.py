"""Shared pieces of the chip benchmark: spec loading, peaks, devices,
trace reduction and statistics. Nothing here knows a configuration, a
traffic mix or a metric by name; those are files found by name."""
