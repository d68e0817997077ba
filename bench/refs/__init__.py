"""Plain references: straightforward jax.numpy in float32 (every matrix
product at HIGHEST precision unless a control lowers it), no kernels, no
caches, no batching across requests or clients. They import nothing of
the program under test and read only what the benchmark made: weights,
data and the recorded inputs of the timed path."""
