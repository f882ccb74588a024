"""Paged KV cache geometry + device-side gather/scatter addressing.

Instead of one contiguous ``(B, max_len, ...)`` strip per slot, every
per-position cache leaf becomes a shared pool of fixed-size pages
``(num_pages, page_size, ...)``; a per-slot page table ``(B, P)`` of
physical page ids maps each slot's logical positions onto the pool.  A
slot then only ties up ``ceil(need / page_size)`` pages — short and long
requests share HBM instead of every slot reserving ``max_len`` positions.

The addressing runs INSIDE the jitted step:

* ``gather_pages`` materializes a slot-major ``(B, T, ...)`` view of the
  pool that is element-for-element the contiguous cache layout, so the
  attention math downstream of it is the *same code* (same masks, same
  reductions) as the contiguous path — that is what makes paged reads
  bit-identical to the contiguous baseline.  (A TPU production path
  would fuse the gather into a paged-attention kernel; this is the
  HLO-level expression of the same addressing.)
* ``scatter_rows`` / ``scatter_chunk`` write decode tokens / prefill
  chunks through the page table with ``mode="drop"`` masking, so rows
  that are not live (or padded chunk tails) write nothing — there is no
  trash page, and a freed-and-reallocated page never sees stray writes
  from its old owner.

Two logical cache classes share one pool geometry: full-length caches
(GQA without a window, MLA) with ``len_linear`` positions per slot, and
sliding-window ring buffers with ``len_swa`` positions.  They use
separate page tables because a slot needs a different page count in
each; ring slots keep the contiguous path's ``pos % len_swa`` addressing
on top of the table.

A pool here may be one layer's ``(num_pages, ps, ...)`` or the
layer-flattened stack ``(L * num_pages, ps, ...)`` that the layer scan
carries (``repro.models.stack``): layer ``l``'s pages are then addressed
by the same table offset by ``l * num_pages``, and every function below
reads or writes exactly the pages the table names, in place.

Page *allocation* is host-side policy and lives with the serving engine
(``repro.serving.paging``); this module is only the device-side layout.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of a paged cache (closed over by jitted programs).

    ``len_linear`` / ``len_swa`` are the LOGICAL positions per slot (what
    the contiguous layout would allocate: ``max_len``, and
    ``min(max_len, sliding_window)``); ``num_pages`` / ``num_pages_swa``
    size the physical pools.  ``len_swa = 0`` means no sliding-window
    caches in the model.
    """
    page_size: int
    len_linear: int
    num_pages: int
    len_swa: int = 0
    num_pages_swa: int = 0

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {self.num_pages}")

    @property
    def pages_per_slot(self) -> int:
        """Page-table width for full-length caches."""
        return -(-self.len_linear // self.page_size)

    @property
    def pages_per_slot_swa(self) -> int:
        """Page-table width for sliding-window ring caches."""
        return -(-self.len_swa // self.page_size)

    def pages_for(self, positions: int) -> int:
        """Pages a slot must hold to cover ``positions`` cache positions."""
        return -(-min(positions, self.len_linear) // self.page_size)


def gather_pages(pool, table, length: int):
    """Slot-major view of a paged pool: (num_pages, ps, ...) -> (B, length, ...).

    ``pool`` may be the layer-flattened stack (L*num_pages, ps, ...) with
    ``table`` offset by ``l*num_pages`` to view layer ``l``.

    ``view[b, t] == pool[table[b, t // ps], t % ps]`` — exactly the
    contiguous cache layout for slot b, so downstream attention math is
    unchanged.  Logical pages past a slot's allocation read whatever page
    their table entry names (0 when unallocated); callers mask those
    positions exactly like the contiguous path masks unwritten ones.

    Trailing-page semantics (audited; the kernel read path reproduces
    them exactly): ``length`` is NOT required to be a page multiple.  The
    last page a slot uses is always read IN FULL and then sliced —
    ``length % ps != 0`` means positions in ``[length - length % ps,
    length)`` come from a page whose tail entries (``>= length % ps``)
    are cut off by the ``[:, :length]`` slice, while a ``length`` exactly
    on a page boundary reads its final page whole with nothing sliced.
    Either way every position ``t < length`` that the slot has not yet
    WRITTEN (``t > pos[b]``) still appears in the view — as stale pool
    contents or page-0 rows — and is hidden downstream by the causal
    ``idx <= pos`` mask, never by this function.  The in-kernel path
    (repro.kernels.paged_attention) mirrors this by fetching whole pages
    into scratch, slicing ``[:length]``, and applying the identical mask,
    so both boundary parities are covered by the same regression tests
    (tests/test_paged_kernel.py).
    """
    B, P = table.shape
    ps = pool.shape[1]
    view = pool[table]                                   # (B, P, ps, ...)
    return view.reshape(B, P * ps, *pool.shape[2:])[:, :length]


def scatter_rows(pool, table, slots, vals, *, live=None):
    """Write one position per slot: vals (B, 1, ...) at logical slot (B,).

    Rows where ``live`` is False write nothing (offset pushed past the
    page -> ``mode="drop"``), so done/empty/mid-prefill rows never touch
    pages they do not own.  On the layer-flattened stack (``table`` offset
    by ``l*num_pages``) the write lands in layer ``l``'s pages, in place.
    """
    B, P = table.shape
    ps = pool.shape[1]
    page = jnp.take_along_axis(table, jnp.clip(slots // ps, 0, P - 1)[:, None],
                               axis=1)[:, 0]
    # positions past the table (e.g. pos == max_len) DROP, exactly like the
    # contiguous layout's slot -> T scatter — never remap into the last page
    off = jnp.where(slots < P * ps, slots % ps, ps)
    if live is not None:
        off = jnp.where(live, off, ps)                   # out of page -> drop
    return pool.at[page, off].set(vals[:, 0], mode="drop")


def scatter_chunk(pool, table, slots, valid, vals):
    """Write a prefill chunk: vals (B, C, ...) at logical slots (B, C).

    ``valid`` (B, C) marks real tokens; padded tails are dropped.  Chunk
    positions are distinct within a row and rows own disjoint pages, so
    the scatter has no write collisions.  Like :func:`scatter_rows`, it
    takes the layer-flattened stack with an offset table too.
    """
    B, P = table.shape
    ps = pool.shape[1]
    lp = jnp.clip(slots // ps, 0, P - 1)
    page = jnp.take_along_axis(table, lp, axis=1)        # (B, C)
    off = jnp.where(valid & (slots < P * ps), slots % ps, ps)  # else drop
    return pool.at[page, off].set(vals, mode="drop")
