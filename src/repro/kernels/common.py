"""Shared constants for the Pallas kernel families."""
from __future__ import annotations

import jax.numpy as jnp

# Large-negative mask value safe to exponentiate in fp32 (exp -> exactly 0)
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
