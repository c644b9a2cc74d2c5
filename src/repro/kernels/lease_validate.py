"""Batched TL2 certification — Pallas TPU kernel (the paper's hot loop).

When a replica (pod controller) validates a *batch* of remote/forwarded
transactions (Lilac-TM §3.2: forwarded transactions are certified at the
target without re-execution), the work is: look up each transaction's
read-set versions in the store's version array, compare against the
snapshot versions, and check write locks.  At pod scale (thousands of
in-flight certifications per lease window) this is a bandwidth-bound
gather+compare.

The TPU has no general vector gather, so the lookup is reformulated as a
*chunk-local compare*: the read (and write) entries are flattened into one
column ``[E, 1]`` and the version (lock) array is streamed through VMEM as
lane-dense ``[1, chunk]`` rows.  Each (entry-tile × chunk) cell compares
every entry's item against the chunk's lane ids; an entry is bad when the
lane it hits holds a value other than the one it expects.  The chunk axis
is the innermost grid dim, so the per-entry flag accumulates in the
resident output block across the sweep.  Reads expect their snapshot
version; writes run through the same kernel against the lock values they
index expecting 0 (unlocked).  The lock values need not be per item: the
certifier hands one bit per write entry, so the lock sweep is as long as
the batch's write entries, not the store.  Per-transaction verdicts are
the row-wise OR outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def _mismatch_kernel(idx_ref, want_ref, vals_ref, bad_ref, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        bad_ref[...] = jnp.zeros_like(bad_ref)

    idx = idx_ref[...]                                   # [te, 1] int32
    lane = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    hit = idx == lane                                    # [te, chunk]
    wrong = hit & (vals_ref[...] != want_ref[...])       # [1,c] vs [te,1]
    bad_ref[...] = jnp.maximum(
        bad_ref[...],
        jnp.max(wrong.astype(jnp.int32), axis=1, keepdims=True))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _mismatch(values, idx, want, *, block_entries: int, chunk: int,
              interpret: bool):
    """``bad[e] = values[idx[e]] != want[e]`` for ``idx[e] >= 0`` (else 0)."""
    e = idx.shape[0]
    n = values.shape[0]
    chunk = min(_round_up(chunk, LANES), _round_up(n, LANES))
    te = min(_round_up(block_entries, SUBLANES), _round_up(e, SUBLANES))
    n_pad, e_pad = _round_up(n, chunk), _round_up(e, te)
    values = jnp.pad(values, (0, n_pad - n)).reshape(1, n_pad)
    idx = jnp.pad(idx, (0, e_pad - e), constant_values=-1).reshape(e_pad, 1)
    want = jnp.pad(want, (0, e_pad - e)).reshape(e_pad, 1)
    bad = pl.pallas_call(
        functools.partial(_mismatch_kernel, chunk=chunk),
        grid=(e_pad // te, n_pad // chunk),
        in_specs=[
            pl.BlockSpec((te, 1), lambda ie, ic: (ie, 0)),
            pl.BlockSpec((te, 1), lambda ie, ic: (ie, 0)),
            pl.BlockSpec((1, chunk), lambda ie, ic: (0, ic)),
        ],
        out_specs=pl.BlockSpec((te, 1), lambda ie, ic: (ie, 0)),
        out_shape=jax.ShapeDtypeStruct((e_pad, 1), jnp.int32),
        interpret=interpret,
    )(idx, want, values)
    return bad[:e, 0] > 0


@functools.partial(
    jax.jit, static_argnames=("block_entries", "chunk", "interpret"),
)
def lease_validate(
    store_versions: jax.Array,    # [n_items] int32
    read_items: jax.Array,        # [B, R] int32 (-1 padded)
    read_versions: jax.Array,     # [B, R] int32
    write_locks: jax.Array,       # [L] int32 (0/1), indexed by write_items
    write_items: jax.Array,       # [B, W] int32 into write_locks (-1 padded)
    *,
    block_entries: int = 256,
    chunk: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    # normalize dtypes at the boundary: callers hand numpy buffers of
    # whatever width their logs use; a silent int64 view of an int32 buffer
    # once produced garbage write items (see tests/test_certify.py lock
    # parity), so the kernel refuses to rely on caller dtypes
    store_versions = jnp.asarray(store_versions, jnp.int32)
    read_items = jnp.asarray(read_items, jnp.int32)
    read_versions = jnp.asarray(read_versions, jnp.int32)
    write_locks = jnp.asarray(write_locks, jnp.int32)
    write_items = jnp.asarray(write_items, jnp.int32)
    b = read_items.shape[0]
    kw = dict(block_entries=block_entries, chunk=chunk, interpret=interpret)
    stale = _mismatch(store_versions, read_items.reshape(-1),
                      read_versions.reshape(-1), **kw)
    locked = _mismatch(write_locks, write_items.reshape(-1),
                       jnp.zeros(write_items.size, jnp.int32), **kw)
    return ~(stale.reshape(b, -1).any(axis=1)
             | locked.reshape(b, -1).any(axis=1))
