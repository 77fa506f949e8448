"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling, MXU-aligned
block shapes) and are VALIDATED on CPU in interpret mode — `interpret()`
flips automatically when no TPU is present.  The last two dimensions of
every block are multiples of the (8, 128) f32 VREG tile or span the whole
array (a per-partition row vector is viewed as a (P, 1, R) array so its
row tile is a (1, bt) block), which is what the Mosaic TPU lowering
requires; `tests/test_tpu_compile.py` compiles each kernel for a v5e chip.

The kernels keep rows on the lane axis throughout: one-hot matrices are
built as (bins × rows) and contracted over the row axis against the value
rows (an A·Bᵀ matmul), so no tile is ever transposed between lanes and
sublanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128
SUBLANE = 8


def interpret() -> bool:
    """Pallas interpret mode: True unless running on a real TPU."""
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_block(n: int, target: int, align: int) -> int:
    """Largest aligned block <= max(target, align) that tiles padded n."""
    b = min(round_up(n, align), round_up(target, align))
    return max(b, align)


def contract_rows(values: jax.Array, onehot: jax.Array) -> jax.Array:
    """(V, bt) values · (S, bt) one-hotᵀ → (V, S) f32 segment sums.

    HIGHEST precision: the TPU's default f32 matmul rounds the value
    operand to bf16, which breaks the rtol-1e-5 agreement with the host
    sums that the per-partition answers are held to.
    """
    return jax.lax.dot_general(
        values, onehot.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
