"""A page pool's array functions: what a model's layer calls, inside a traced
program, to write a pool and to view it through a page table. Pure
``jax.numpy``, no owner: the host's manager of pools, tables and slots is
``serving.kv_cache.PagedKVCache``; the attends over a pool, each kernel with
its reference, are ``paged_attention.py`` and ``latent_attention.py``.

``write_kv`` is the same write into a dense ``[B, H_kv, S_max, D]`` cache:
the lockstep decode of ``GPTForCausalLM.generate`` and
``incubate.nn.FusedMultiTransformer``, and the oracle of ``paged_write_kv``
(over ``paged_gather``).
"""

import jax.numpy as jnp
from jax import lax

#: page-table entry marking an unallocated block. Device code never branches
#: on it — lookups clamp sentinels to page 0, the reserved TRASH page the
#: allocator never hands out, so gathers/scatters stay in-bounds and the
#: decode mask (``key_pos <= position``) keeps trash bytes out of the math.
PAGE_SENTINEL = -1


def write_kv(cache, new, positions):
    """Write new K (or V) entries into a ``[B, H_kv, S_max, D]`` cache.

    ``positions`` scalar: contiguous write of ``new [B, H_kv, T, D]``
    starting at that sequence index (the prefill / shared-step case —
    ``lax.dynamic_update_slice``, batch must match the cache's).
    ``positions`` ``[B]``: per-row single-token scatter of
    ``new [B, H_kv, 1, D]`` at each row's own index (the continuous-batching
    decode case, where slots sit at different sequence positions).
    """
    new = new.astype(cache.dtype)
    positions = jnp.asarray(positions)
    if positions.ndim == 0:
        zero = jnp.zeros((), positions.dtype)
        return lax.dynamic_update_slice(cache, new, (zero, zero, positions, zero))
    # one row per (slot, head), indexed on the two LEADING dimensions of the
    # [B*H_kv, S_max, D] view: the form XLA scatters into a donated cache
    # where it lies. Indexed on (slot, position) of the 4-D cache, around
    # the head dimension, XLA transposes the whole cache to put the indexed
    # dimensions first, and back, every step.
    B, Hkv, S, D = cache.shape
    flat = cache.reshape(B * Hkv, S, D)
    flat = flat.at[jnp.arange(B * Hkv), jnp.repeat(positions, Hkv), :].set(
        new[:, :, 0, :].reshape(B * Hkv, D))
    return flat.reshape(B, Hkv, S, D)


def paged_write_kv(pool, new, page_table, positions):
    """Write ``T`` tokens' K (or V) per slot into a ``[P, H_kv, ps, D]``
    page pool: token ``t`` of row ``b`` of ``new [B, H_kv, T, D]`` lands in
    page ``page_table[b, (positions[b]+t) // ps]`` at offset
    ``(positions[b]+t) % ps``. ``T`` is static (1 for plain decode, ``k+1``
    for speculative verify, a bucket for suffix prefill).

    The update is made a PAGE at a time: gather the pages the ``T``
    positions of each row can touch, lay the new rows into them, scatter
    whole pages back — one gather and one scatter whatever ``T`` is, both
    indexed on the pool's leading dimension only. That is the form XLA
    applies in place to a donated pool in the layout the pool is stored in
    (a scatter indexed on page AND offset makes the TPU compiler transpose
    the whole pool to a layout of its own and back, every step).

    Sentinel entries clamp to the trash page (slots without a live request
    all write identical token-0 state there, so the race is benign), and
    writes past the table's capacity ``num_blocks * ps`` route to the trash
    page too — a verify step near the end of a sequence can draft past
    ``S_max`` without going out of bounds; the host caps how many of those
    tokens it accepts. A touched page in which no token lands is written
    back as it was read."""
    ps = pool.shape[2]
    nb = page_table.shape[1]
    pos = jnp.asarray(positions)
    T = new.shape[2]
    new = new.astype(pool.dtype)
    nblk = (T + ps - 2) // ps + 1  # pages T consecutive positions can span
    block = (pos // ps)[:, None] + jnp.arange(nblk)            # [B, nblk]
    pages = jnp.take_along_axis(page_table, jnp.minimum(block, nb - 1),
                                axis=1)
    pages = jnp.where(block < nb, jnp.maximum(pages, 0), 0)
    # which token, if any, lands in offset s of touched block j of row b
    t = block[:, :, None] * ps + jnp.arange(ps) - pos[:, None, None]
    rows = jnp.take_along_axis(                       # [B, nblk, H_kv, ps, D]
        new[:, None], jnp.clip(t, 0, T - 1)[:, :, None, :, None], axis=3)
    lands = ((t >= 0) & (t < T))[:, :, None, :, None]
    merged = jnp.where(lands, rows, pool[pages])
    return pool.at[pages].set(merged)


def write_state_rows(buf, new, rows):
    """``new [n, ...]`` onto rows ``rows [n]`` (run-time values) of the state
    buffer ``buf [rows, ...]``, one after the other where it lies: where two
    name the same row, the later one stands."""
    for i in range(new.shape[0]):
        buf = lax.dynamic_update_slice_in_dim(
            buf, new[i:i + 1].astype(buf.dtype), rows[i], axis=0)
    return buf


def paged_gather(pool, page_table):
    """Materialize the dense ``[B, H_kv, num_blocks*ps, D]`` view of a page
    pool under a table — the oracle path's cache reconstruction (sentinels
    clamp to trash, so dense position ``j`` of an unallocated block holds
    trash bytes that the decode mask never admits)."""
    g = pool[jnp.maximum(page_table, 0)]        # [B, nb, Hkv, ps, D]
    B, nb, Hkv, ps, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, nb * ps, D)


def window_blocks(page_table, start, page_size: int, window: int, T: int):
    """What an extend of ``T`` tokens at ``start [B]`` reads of a sliding
    layer's pools: ``(first [B], sub [B, n])``, the sequence position of
    the view's first token and the table entries of the blocks from the one
    that holds ``start - window + 1`` to the one that holds ``start + T -
    1`` (``n`` is static: blocks past the table's end read as sentinels).
    ``paged_gather(pool, sub)`` is then the window and the new tokens, not
    the whole table's view."""
    nb = page_table.shape[1]
    back = (window + page_size - 2) // page_size
    n = back + (T + page_size - 2) // page_size + 1
    fb = jnp.maximum(start // page_size - back, 0)
    blocks = fb[:, None] + jnp.arange(n, dtype=fb.dtype)[None, :]
    sub = jnp.take_along_axis(page_table, jnp.minimum(blocks, nb - 1), axis=1)
    return fb * page_size, jnp.where(blocks < nb, sub, PAGE_SENTINEL)
