"""repro — Triton-distributed (overlapping distributed kernels) on TPU in JAX."""
