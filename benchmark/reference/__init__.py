"""Plain references: straightforward float32 ``jax.numpy`` at ``highest``
matmul precision, written from the models' equations. They import
nothing of ``routest_tpu`` and take nothing it has made."""
