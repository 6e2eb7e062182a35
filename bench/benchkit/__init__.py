"""The benchmark's own library: everything that decides a number.

Traffic generation, the plain f64 reference, percentile arithmetic, the
ELL byte model, the peaks table and the device-trace reduction live here
and import nothing of the program under test (``src/repro``); the
drivers in :mod:`benchkit.serve` and :mod:`benchkit.job` are the only
modules that call into it.
"""
