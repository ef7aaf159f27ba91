"""MaTEx-JAX: user-transparent distributed training and serving.

User scripts go through ``repro.api`` (``api.load(arch) -> Session``);
everything else is runtime the Session owns.
"""
