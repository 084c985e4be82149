"""The EMVB benchmark's yardstick: spec loading, data generation, load
generation, the plain reference, trace reduction and the work model.

Everything here belongs to the benchmark, not to the program under test:
the program (``src/repro``) is imported only by :mod:`harness.runner` to
build the system under test.
"""
