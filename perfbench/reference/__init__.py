"""Plain references: float32 ``jax.numpy``, no kernels, cache or
batching tricks. They import nothing of ``tpudl`` and are given nothing
that ``tpudl`` has made: weights come from the seed, here."""
