"""Public wrappers for the device kernels — the one place where each
operation's implementation is chosen.

  * ``gather_segsum``/``gather_segmin``, ``merge_streams``/
    ``tournament_merge`` and ``presence_matrix`` are jnp (XLA) on every
    backend: each inner step is a data-dependent vector gather, which
    Mosaic does not lower (see kernels/segment_reduce.py, kernels/merge.py,
    kernels/presence.py).
  * ``attention`` is opt-in Pallas (off the store's path).

CPU and TPU run the same algorithm; the only difference is how a Pallas
kernel executes (``interpret()``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash
from .merge import lex_searchsorted, merge_streams, tournament_merge
from .presence import presence_matrix
from .segment_reduce import (gather_segmin as _gather_segmin,
                             gather_segsum as _gather_segsum)


def interpret() -> bool:
    """Pallas kernels run in interpret mode on the CPU backend (the test
    suite) and compile through Mosaic everywhere else."""
    return jax.default_backend() == "cpu"


def gather_segsum(dst, seg_id, wt, x, *, n_out: int) -> jnp.ndarray:
    """Message gather + CSR segment sum (analytics inner loop)."""
    return _gather_segsum(dst, seg_id, wt, x, n_out=n_out)


def gather_segmin(dst, seg_id, wt, x, *, n_out: int) -> jnp.ndarray:
    """Segment-min relaxation (BFS / SSSP / CC inner loop)."""
    return _gather_segmin(dst, seg_id, wt, x, n_out=n_out)


def attention(q, k, v, *, causal: bool = True, scale=None,
              use_pallas: bool = False):
    """Blocked attention; XLA reference by default (dry-run path), Pallas
    kernel opt-in (validated in interpret mode on CPU)."""
    if not use_pallas:
        return ref.mha_ref(q, k, v, causal=causal, scale=scale)
    return _flash(q, k, v, causal=causal, scale=scale,
                  interpret=interpret())


__all__ = ["gather_segsum", "gather_segmin", "merge_streams",
           "tournament_merge", "presence_matrix",
           "attention", "lex_searchsorted", "interpret"]
