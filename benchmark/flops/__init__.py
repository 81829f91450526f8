"""Shape functions: the operations an algorithm needs, computed from its sizes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Only matrix-unit work is counted (matmuls, convolutions, attention
scores); elementwise passes, norms, gathers and recomputation are not.
"""
