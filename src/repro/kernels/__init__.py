"""Pallas TPU kernels (compiled on TPU, interpreted on CPU: `ops.interpret_mode`) + jnp oracles."""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
