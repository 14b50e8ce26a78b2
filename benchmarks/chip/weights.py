"""Seeded weights, the same for the served path and for the reference.

A model's tensors are declared by name (``Spec``).  Tensor ``name`` of layer
``i`` is drawn from a key that depends only on the seed, the name and the
layer, so the harness can make a whole stacked model in one jitted call on
the device while the reference makes one layer at a time and gets the same
numbers.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """One named tensor: ``normal`` draws N(0, scale^2); ``gain`` draws
    1 + N(0, scale^2) (a norm's multiplier)."""

    shape: tuple[int, ...]
    dtype: Any = jnp.bfloat16
    kind: str = "normal"
    scale: float = 1.0


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A non-negative seed below 2**64 as two 32-bit words (low, high)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def base_key(lo: Any, hi: Any) -> jax.Array:
    """A PRNG key from both words of a seed (``jax.random.key`` keeps only
    the low 32 bits of a large seed); the words may be traced."""
    return jax.random.fold_in(jax.random.key(jnp.asarray(lo, jnp.uint32)),
                              jnp.asarray(hi, jnp.uint32))


def _name_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def draw(key: jax.Array, spec: Spec) -> jax.Array:
    x = jax.random.normal(key, spec.shape, jnp.float32) * spec.scale
    if spec.kind == "gain":
        x = 1.0 + x
    elif spec.kind != "normal":
        raise ValueError(f"unknown kind {spec.kind!r}")
    return x.astype(spec.dtype)


def global_tensors(key: jax.Array, specs: dict[str, Spec]
                   ) -> dict[str, jax.Array]:
    return {n: draw(_name_key(key, n), s) for n, s in specs.items()}


def layer_tensors(key: jax.Array, specs: dict[str, Spec], layer: Any
                  ) -> dict[str, jax.Array]:
    """Layer ``layer``'s tensors (``layer`` may be traced)."""
    return {n: draw(jax.random.fold_in(_name_key(key, n), layer), s)
            for n, s in specs.items()}


def stacked_layers(key: jax.Array, specs: dict[str, Spec], n_layers: int
                   ) -> dict[str, jax.Array]:
    """Every layer's tensors stacked on a leading axis, made one layer at a
    time so a float32 draw never spans the whole stack."""
    return jax.lax.map(lambda i: layer_tensors(key, specs, i),
                       jnp.arange(n_layers))
