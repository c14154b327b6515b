"""Algorithmic work counts of the device kernels."""
