"""Checks of the benchmark's own yardstick, run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest smolbench/tests -q
"""
