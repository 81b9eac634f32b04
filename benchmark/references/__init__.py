"""Plain references: each configuration's forward pass, loss, gradients
and optimizer in straightforward jax.numpy, float32, matrix products at
``highest`` precision. They import nothing of the program."""
