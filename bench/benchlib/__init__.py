"""The benchmark's own library: cell lookup, traffic, weights, references,
FLOP and byte counts, the device-peak table and the trace reduction.

Nothing here is imported by the program under test; the benchmark imports
the program (``repro``) only to drive it.
"""
