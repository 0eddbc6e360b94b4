"""Plain references the benchmark compares the program with.

They import nothing of the program under test and take nothing it has
made: weights and inputs are made again here, from the seed, by the
benchmark's own generators.
"""
