"""Operation and byte counts of the kernels and models the benchmark times.

One module per kernel, named as the kernel is named in the device trace;
each exposes ``cost(**shapes) -> (flops, bytes)`` for one call.  Counts are
what the algorithm needs: every operand read once from HBM and every
result written once.
"""
