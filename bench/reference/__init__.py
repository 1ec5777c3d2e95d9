"""Plain float32 references of the benchmark's model configurations.

Nothing here imports the program. Each module gives ``init_params`` (the
weights, made from a key in one jitted call, in the program's parameter
layout and the dtype they are served in) and ``hidden`` (the final hidden
states of a token batch). ``Numerics`` says how a matrix product is taken:
float32 at full precision for the reference, or float8 (e4m3) operands for
the control that a lower precision would give.
"""
