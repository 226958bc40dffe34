"""Device kernels for LSMGraph's compute hot spots.

  <name>.py — the kernel (pl.pallas_call + BlockSpec VMEM tiling where
              it is a Pallas kernel, jnp where it is not),
  ops.py    — public wrappers; the one place each implementation is chosen,
  ref.py    — pure-jnp oracles used by the allclose test sweeps.
"""
from .ops import (attention, gather_segmin, gather_segsum, interpret,
                  lex_searchsorted, merge_streams, presence_matrix,
                  tournament_merge)

__all__ = ["attention", "gather_segmin", "gather_segsum", "interpret",
           "lex_searchsorted", "merge_streams", "presence_matrix",
           "tournament_merge"]
