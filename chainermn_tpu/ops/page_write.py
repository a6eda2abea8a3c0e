"""Pallas per-slot cache write: one new K row and one new V row per slot,
each at that slot's own cursor, as ONE in-place device operation.

The decode step of a serving grid writes ``k_new[i]`` to
``k_page[i, start[i]]`` (and V likewise) for every slot ``i``.
``vmap(lax.dynamic_update_slice)`` says exactly that, but on the TPU it
lowers to a scatter loop of one iteration per slot and page, five device
operations each — 40% of a StarCoder2-3B decode step (PERF.md §6, PR 28).
Here the pages stay in HBM, aliased to the outputs, and the kernel starts
one small DMA per slot and page from the new rows to
``page[i, start[i]]`` and waits for all of them at the end: the cost of
moving ``2 * slots`` rows, no arithmetic, no copy of a page.

The write moves bytes only, so the pages afterwards are byte for byte what
``vmap(dynamic_update_slice)`` leaves, for any page dtype; a start outside
``[0, capacity)`` is read as ``dynamic_update_slice`` reads it.

:func:`write_rows` is what the model calls. It adapts by what it can see at
trace time and keeps the ``vmap`` form in three cases:

* a slab of several rows per slot (``l > 1``): the kernel moves one;
* a row ``[h_kv, d_head]`` that is not whole tiles of the chip's memory
  layout (:func:`rows_are_whole_tiles`): the kernel's DMA cannot address
  such a row, and for some of these shapes XLA stores the page in another
  order than the kernel asks for, which would cost a copy of the page a
  call — far more than the loop;
* pages split over several devices: a custom call cannot be divided by the
  SPMD partitioner. ``ServingStep(mesh=...)`` with more than one device
  traces its programs under :func:`partitioned_pages`.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.utils import on_tpu

__all__ = ["page_write_rows", "vmap_write_rows", "write_rows",
           "partitioned_pages", "pages_are_partitioned",
           "rows_are_whole_tiles"]

_trace = threading.local()


@contextlib.contextmanager
def partitioned_pages(partitioned: bool = True):
    """Trace-time scope: the pages of programs traced inside are split over
    several devices, so :func:`write_rows` keeps the partitionable form."""
    before = getattr(_trace, "partitioned", False)
    _trace.partitioned = bool(partitioned)
    try:
        yield
    finally:
        _trace.partitioned = before


def pages_are_partitioned() -> bool:
    """Whether the program being traced runs under
    :func:`partitioned_pages`: what a kernel over a page asks before it
    takes the page as one device's."""
    return getattr(_trace, "partitioned", False)


def rows_are_whole_tiles(h_kv: int, d_head: int, dtype) -> bool:
    """Whether one cache row ``[h_kv, d_head]`` fills whole memory tiles:
    128 lanes, and sublanes in the smallest power of two that holds
    ``h_kv`` — at most 8, at least the dtype's packing (2 rows of bf16 share
    a sublane). The compile matrix in tests/ops_tests holds this reading
    against the chip's compiler."""
    packing = max(1, 4 // jnp.dtype(dtype).itemsize)
    sublanes = max(packing, min(8, pl.next_power_of_2(h_kv)))
    return d_head % 128 == 0 and h_kv % sublanes == 0


def vmap_write_rows(k_page, v_page, k_new, v_new, start):
    """The write as XLA sees it without the kernel — also its oracle, and
    the form of an ``l > 1`` slab per slot."""
    def put(page, new):
        return jax.vmap(
            lambda c, u, s0: jax.lax.dynamic_update_slice(c, u, (s0, 0, 0))
        )(page, new, start)

    return put(k_page, k_new), put(v_page, v_new)


def _kernel(start_ref, k_new_ref, v_new_ref, k_in_ref, v_in_ref,
            k_out_ref, v_out_ref, sem):
    del k_in_ref, v_in_ref           # the outputs ARE the pages (aliased)
    n = k_new_ref.shape[0]

    def copies(i):
        row = pl.ds(start_ref[i], 1)
        return (pltpu.make_async_copy(k_new_ref.at[i],
                                      k_out_ref.at[i, row], sem),
                pltpu.make_async_copy(v_new_ref.at[i],
                                      v_out_ref.at[i, row], sem))

    def start(i, carry):
        for dma in copies(i):
            dma.start()
        return carry

    def wait(i, carry):
        for dma in copies(i):
            dma.wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def page_write_rows(k_page, v_page, k_new, v_new, start):
    """``k_page``/``v_page`` ``[slots, capacity, h_kv, d_head]``;
    ``k_new``/``v_new`` ``[slots, 1, h_kv, d_head]`` in the pages' dtype;
    ``start`` int32 ``[slots]``. Returns the two pages with row
    ``start[i]`` of slot ``i`` replaced, every other byte untouched; the
    pages are aliased to the results (in place under ``jit`` when donated
    or dead afterwards)."""
    n, cap = k_page.shape[:2]
    if k_new.shape != (n, 1) + k_page.shape[2:] or k_new.shape != v_new.shape:
        raise ValueError(
            f"new rows {k_new.shape}/{v_new.shape} do not fit one row per "
            f"slot of pages {k_page.shape}")
    if k_new.dtype != k_page.dtype or v_new.dtype != v_page.dtype:
        raise ValueError(
            f"new rows ({k_new.dtype}, {v_new.dtype}) must arrive in the "
            f"pages' dtype ({k_page.dtype}, {v_page.dtype}): the write "
            "copies bytes")
    # dynamic_update_slice's reading of a start: negative counts from the
    # end, and what still lies outside the page is clamped into it
    start = jnp.asarray(start, jnp.int32)
    start = jnp.clip(jnp.where(start < 0, start + cap, start), 0, cap - 1)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[any_space] * 4,
            out_specs=[any_space] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_page.shape, k_page.dtype),
                   jax.ShapeDtypeStruct(v_page.shape, v_page.dtype)],
        # operands count the scalar-prefetched starts: 3, 4 are the pages
        input_output_aliases={3: 0, 4: 1},
        interpret=not on_tpu(),
        name="page_write_rows",
    )(start, k_new, v_new, k_page, v_page)


def write_rows(k_page, v_page, k_new, v_new, start):
    """The per-slot write of ``TransformerBlock``'s decode branch: the
    kernel, or the ``vmap`` form where the module's docstring says the
    kernel cannot serve."""
    if (k_new.shape[1] != 1 or pages_are_partitioned()
            or not rows_are_whole_tiles(*k_page.shape[2:], k_page.dtype)):
        return vmap_write_rows(k_page, v_page, k_new, v_new, start)
    return page_write_rows(k_page, v_page, k_new, v_new, start)
