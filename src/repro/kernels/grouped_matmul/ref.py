"""Pure-jnp oracle for the grouped matmul: ``jax.lax.ragged_dot``.

Rows past ``sum(group_sizes)`` come out as zeros here; the kernel leaves
them unspecified, so comparisons mask them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul_ref(lhs: jnp.ndarray, rhs: jnp.ndarray,
                       group_sizes: jnp.ndarray,
                       out_dtype=None) -> jnp.ndarray:
    """lhs: (m, k) rows sorted by group; rhs: (G, k, n); group_sizes:
    (G,) int32.  Returns (m, n) in ``out_dtype`` (default lhs dtype),
    accumulated in float32."""
    out = jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                             group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(out_dtype or lhs.dtype)


__all__ = ["grouped_matmul_ref"]
