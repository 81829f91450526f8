"""Plain references: straightforward float32 ``jax.numpy`` with ``highest``
matmul precision, no kernels, no batching tricks, independent of the code
under test. The benchmark decides ``correct`` against these, outside the
timed window. Each departure from the published description is noted where
it is made.
"""
