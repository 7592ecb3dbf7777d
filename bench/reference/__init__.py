"""Plain float32 references, independent of the program's code.

Each family module holds the weight maker (weights from the seed, in the
layout the serving program takes, in the type they are served in) and a
forward pass in float32 at the highest matmul precision, run layer by
layer so that it fits beside the served weights.
"""
