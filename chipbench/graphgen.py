"""Traffic generation: the Graph500 R-MAT graph and its update stream.

``rmat_edges`` and ``update_stream`` are copies of
``src/repro/data/graphgen.py`` (``rmat_edges``, ``update_stream``), kept
here so that a change to the program cannot move the data the benchmark
measures.  Two departures, neither of which changes the distribution:

* ``rmat_edges`` draws the same doubles in the same order but does its
  bit arithmetic in 32-bit integers (about half the host time);
* ``update_stream`` takes its delete-pick seed from the caller (the
  original fixes it at 0 for every graph).

``stream_blocks`` is the benchmark's own: the scale's R-MAT graph and its
20:1 insert/delete stream, ingested undirected as ``chip_smoke.py``
does, then further R-MAT samples of the same size from seeds derived
from ``--seed``, so that a window never runs out of requests.
``loaded_graph`` is a fixed graph whose vertex labels ``--seed``
permutes, for mixes whose work must not change with the seed.
"""
from __future__ import annotations

from typing import Iterator, List, NamedTuple, Tuple

import numpy as np

# Graph500 Kronecker initiator (a, b, c; d = 1 - a - b - c = 0.05).
RMAT_ABC = (0.57, 0.19, 0.19)


def rmat_edges(scale: int, n_edges: int, *, seed=0,
               a=0.57, b=0.19, c=0.19) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    src = np.zeros(n_edges, np.int32)
    dst = np.zeros(n_edges, np.int32)
    for _ in range(scale):
        r = rng.random(n_edges)
        go_right = r > a + b                      # src bit
        go_down = ((r > a) & (r <= a + b)) | (r > a + b + c)  # dst bit
        src = (src << 1) | go_right
        dst = (dst << 1) | go_down
    return src, dst


def update_stream(src: np.ndarray, dst: np.ndarray, *, delete_ratio:
                  float = 1 / 21, chunk: int = 4096, seed=0
                  ) -> Iterator[Tuple[str, np.ndarray, np.ndarray]]:
    """Mixed insert/delete stream (paper: 20:1 inserts to deletes).
    Deletes only target previously inserted edges."""
    rng = np.random.default_rng(seed)
    inserted_at = 0
    for off in range(0, len(src), chunk):
        s, d = src[off:off + chunk], dst[off:off + chunk]
        yield "insert", s, d
        inserted_at = off + len(s)
        n_del = int(len(s) * delete_ratio)
        if n_del and inserted_at > chunk:
            pick = rng.integers(0, inserted_at, n_del)
            yield "delete", src[pick], dst[pick]


class Op(NamedTuple):
    """One write request: ``delete`` flag and its (src, dst) records."""

    delete: bool
    src: np.ndarray
    dst: np.ndarray


def block_seed(seed: int, block: int) -> np.random.SeedSequence:
    """Seed of stream block ``block`` (0 = the graph itself) for ``--seed``.
    Any whole number is a valid seed; negative ones wrap."""
    return np.random.SeedSequence([seed % (1 << 64), block])


def derived_rng(seed: int, *tags: int) -> np.random.Generator:
    """A generator for one use of ``--seed`` other than the stream's
    blocks (query draws, samples): ``tags`` tell the uses apart."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), 1 << 32, *tags]))


def stream_block(graph: dict, seed: int, block: int) -> List[Op]:
    """Block ``block`` of the undirected update stream: one R-MAT sample
    of the configuration's scale and edge factor and its 20:1 stream,
    each request doubled into both directions."""
    scale = graph["scale"]
    ss = block_seed(seed, block)
    g_seed, d_seed = ss.spawn(2)
    a, b, c = graph["rmat_abc"]
    src, dst = rmat_edges(scale, graph["edge_factor"] << scale,
                          seed=g_seed, a=a, b=b, c=c)
    return [Op(op == "delete", np.r_[s, d], np.r_[d, s])
            for op, s, d in update_stream(
                src, dst, delete_ratio=graph["delete_ratio"],
                chunk=graph["chunk_edges"], seed=d_seed)]


def stream_blocks(graph: dict, seed: int, min_records: int) -> List[Op]:
    """Whole blocks, in order, until they hold ``min_records`` records."""
    ops: List[Op] = []
    n, block = 0, 0
    while block == 0 or n < min_records:
        new = stream_block(graph, seed, block)
        ops.extend(new)
        n += sum(len(o.src) for o in new)
        block += 1
    return ops


def loaded_graph(graph: dict, traffic: dict, seed: int) -> List[Op]:
    """``preload_share`` of the stream of the graph that the mix's
    ``graph_seed`` draws, its vertex labels permuted by ``seed`` (the
    Graph500 generator permutes labels too): every seed loads the same
    graph up to labels, so every seed does the same work."""
    ops = prefix(stream_block(graph, traffic["graph_seed"], 0),
                 traffic["preload_share"])
    perm = derived_rng(seed, 5).permutation(1 << graph["scale"])
    return [Op(op.delete, perm[op.src].astype(op.src.dtype),
               perm[op.dst].astype(op.dst.dtype)) for op in ops]


def n_records(ops) -> int:
    return sum(len(o.src) for o in ops)


def prefix(ops: List[Op], share: float) -> List[Op]:
    """The longest run of leading requests holding at most ``share`` of
    the records."""
    total = n_records(ops)
    keep, n = [], 0
    for op in ops:
        if n + len(op.src) > share * total:
            break
        keep.append(op)
        n += len(op.src)
    return keep
