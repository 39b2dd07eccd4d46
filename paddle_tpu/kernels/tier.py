"""Mosaic kernel or jnp reference: the ONE definition of which body a
serving trace bakes in. Every module that has both (``paged_attention``,
``latent_attention``, ``gated_delta``, ``mamba2``, ``mamba1``, and the model's sparse decode and
latent layers) asks here."""

import contextlib

from ..core.place import on_tpu

_PAGED_IMPL = None  # the tier a test pinned (use_paged_attention_impl)
_PAGED_IMPLS = ("oracle", "pallas")


def default_paged_impl() -> str:
    """Which paged-attend implementation a trace bakes in — the ONE place
    that says: ``pallas`` (the ragged kernels — compiled Mosaic on TPU, the
    Pallas interpreter on cpu) on TPU, the ``oracle`` (gather + dense
    ``decode_attend`` einsum) elsewhere, unless a test pinned the tier with
    ``use_paged_attention_impl``."""
    if _PAGED_IMPL is not None:
        return _PAGED_IMPL
    return "pallas" if on_tpu() else "oracle"


@contextlib.contextmanager
def use_paged_attention_impl(impl: str):
    """Pin the paged-attend implementation for traces entered under the
    context: the seam by which a CPU test runs the kernels under the
    interpreter and ``chip_smoke.py`` runs the oracle on the chip. The
    choice is baked in at TRACE time, so wrap the engine's construction
    and its first ``generate`` / ``compile_programs`` (programs already
    compiled are unaffected)."""
    global _PAGED_IMPL
    if impl not in _PAGED_IMPLS:
        raise ValueError(f"paged impl {impl!r}; want one of {_PAGED_IMPLS}")
    prev, _PAGED_IMPL = _PAGED_IMPL, impl
    try:
        yield
    finally:
        _PAGED_IMPL = prev
