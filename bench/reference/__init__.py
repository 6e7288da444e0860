"""Plain float32 references, one module per model family.

They import nothing of the program and take nothing it made: weights and
states are made here from the seed, handed to the program, and kept for
the comparison.
"""
