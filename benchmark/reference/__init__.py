"""Plain references: each architecture's forward pass in straightforward
`jax.numpy`, float32, no kernels, no cache, no batching tricks."""
