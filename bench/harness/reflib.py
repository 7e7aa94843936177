"""Pieces the plain references share.  Nothing here imports the program.

* ``KeyChain``: the weights are random, drawn from the seed's key in a
  fixed order; this is that order (split the key, draw with the second
  half, carry the first), the weights' "file format".
* ``weight``: one weight as served: a normal draw scaled by
  ``1/sqrt(fan_in)`` and rounded to the dtype it is served in, then held
  in float32.
* ``Numerics``: the arithmetic of a reference run.  ``exact`` is float32
  at ``HIGHEST`` precision.  ``fp8`` is the control: every matmul operand
  rounded to float8 e4m3 (weights scaled per tensor, activations per
  token) and multiplied exactly, the precision step below bfloat16.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


class KeyChain:
    def __init__(self, key: jax.Array) -> None:
        self.key = key

    def __call__(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub


def weight(key: jax.Array, shape: Tuple[int, ...], fan_in: int,
           dtype) -> jax.Array:
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(jnp.float32)


def fp8(x: jax.Array, axis=None) -> jax.Array:
    """``x`` rounded to float8 e4m3 with a scale per tensor (``axis``
    None) or per slice along ``axis``, returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


class Numerics:
    """``mm(spec, act, w)``: an einsum whose first operand is an activation
    (one row per token, contracted on its last axis) and second a weight."""

    def __init__(self, control: bool) -> None:
        self.control = control

    def act(self, x: jax.Array) -> jax.Array:
        return fp8(x, axis=-1) if self.control else x

    def w(self, w: jax.Array) -> jax.Array:
        return fp8(w) if self.control else w

    def mm(self, spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
        return jnp.einsum(spec, self.act(x), self.w(w), precision=HIGHEST)


def rms_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMS norm with unit scale (every norm's learned scale starts at 1)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
