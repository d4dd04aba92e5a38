"""Plain float32 forward passes of the benchmark's configurations.

Each module follows its paper and notes where the served program departs
from it (the reference computes what the program is meant to compute).
Nothing here imports the program under test.
"""
