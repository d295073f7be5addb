"""Pallas TPU kernel: embedding-bag (gather + weighted segment reduce).

The table stays in HBM/ANY memory (it is far larger than VMEM); each grid
cell handles one batch block, issuing per-id dynamic row loads and
accumulating ``w * row`` into a VMEM accumulator.  On real TPU hardware the
row loads lower to dynamic-slice DMAs; production kernels double-buffer them
(FBGEMM-TBE style) — the single-buffer form here keeps the reference simple
and is what we validate in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _kernel(ids_ref, w_ref, table_ref, out_ref, *, b_blk, bag):
    def body(i, _):
        b = i // bag
        k = i % bag
        idx = ids_ref[b, k]
        w = w_ref[b, k]
        row = table_ref[pl.ds(idx, 1), :]
        out_ref[pl.ds(b, 1), :] = (out_ref[pl.ds(b, 1), :]
                                   + w * row.astype(jnp.float32))
        return 0

    out_ref[...] = jnp.zeros_like(out_ref)
    jax.lax.fori_loop(0, b_blk * bag, body, 0)


def embedding_bag_pallas(
    table, ids, weights, *, b_blk: int = 64, interpret: bool | None = None,
):
    """table [V, D], ids [B, K], weights [B, K] -> [B, D]."""
    interpret = resolve_interpret(interpret)
    b, bag = ids.shape
    v, d = table.shape
    b_pad = -(-b // b_blk) * b_blk
    if b_pad != b:
        ids = jnp.pad(ids, ((0, b_pad - b), (0, 0)))
        weights = jnp.pad(weights, ((0, b_pad - b), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, b_blk=b_blk, bag=bag),
        grid=(b_pad // b_blk,),
        in_specs=[
            pl.BlockSpec((b_blk, bag), lambda i: (i, 0)),
            pl.BlockSpec((b_blk, bag), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.MemorySpace.ANY),   # the table
        ],
        out_specs=pl.BlockSpec((b_blk, d), lambda i: (i, 0)),
        # fp32 accumulation regardless of table dtype
        out_shape=jax.ShapeDtypeStruct((b_pad, d), jnp.float32),
        interpret=interpret,
    )(ids, weights, table)
    return out[:b].astype(table.dtype)
