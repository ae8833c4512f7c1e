"""The benchmark's yardstick: cell lookup, traffic, driving, reduction, checks.

Everything here belongs to the benchmark, not to the program under test. The
program is reached only through `harness.program`; the plain references
under `bench/references/` import nothing of it.
"""
