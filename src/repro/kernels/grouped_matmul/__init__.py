"""Grouped (ragged) matrix multiplication — the expert GEMM of dropless
mixture-of-experts routing.

Rows of ``lhs`` arrive sorted by group (expert); ``group_sizes`` says how
many rows each group holds; group ``g``'s rows are multiplied by
``rhs[g]``.  Rows past ``sum(group_sizes)`` belong to no group (padding)
and their output is unspecified: callers mask them.

The kernel walks only the (row tile, group) pairs that hold rows, so an
empty group reads none of its weights: a decode step streams exactly the
experts its tokens were routed to.  The visit plan (``plan_visits``) is
plain jnp outside the kernel, built once per expert layer and shared by
its three matmuls (gate, up, down).

Layout follows the other kernel packages: ``grouped_matmul.py`` holds the
``pl.pallas_call`` kernel and its visit plan, ``ops.py`` the public op
(Pallas on the chip, interpret mode on the CPU, a ``ragged_dot`` oracle
for ``impl="xla"`` and for the backward pass), ``ref.py`` the oracle.
"""

from .ops import grouped_matmul

__all__ = ["grouped_matmul"]
