"""The benchmark's weights: made from the seed, on the device, in one call.

A reference module's ``weight_spec(config)`` names every tensor with its
shape, dtype and init.  ``canonical(spec, seed)`` draws them all in one
jitted call, in the dtype they are served in, so the program and the
reference see the same numbers: the program through the layout adapter in
``bench/program.py``, the reference as they are.  Draws depend only on the
seed and the tensor's name.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _leaf(key, shape, dtype, init, scale):
    if init == "normal":
        x = scale * jax.random.normal(key, shape, jnp.float32)
    elif init == "norm":                      # a multiplier near 1
        x = 1.0 + scale * jax.random.normal(key, shape, jnp.float32)
    elif init == "a_log":                     # A in [1, 16], as published
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif init == "dt_bias":                   # softplus^-1 of log-uniform dt
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init {init!r}")
    return x.astype(jnp.dtype(dtype))


def generate(spec: dict, key) -> dict:
    """Traceable: every tensor of ``spec`` from one PRNG key."""
    return {name: _leaf(jax.random.fold_in(key, zlib.crc32(name.encode())),
                        tuple(shape), dtype, init, scale)
            for name, (shape, dtype, init, scale) in spec.items()}


def canonical(spec: dict, seed32: int) -> dict:
    """-> the canonical weights, on the default device."""
    return jax.jit(lambda k: generate(spec, k))(jax.random.PRNGKey(seed32))
