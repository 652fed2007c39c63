"""The benchmark's own library: data, traffic, reference, trace reduction.

Nothing here imports the program under test except ``cell.py``, which
drives it; the reference, the generators, the peak table and the work count
are the benchmark's yardstick and stay independent of ``src/``.
"""
