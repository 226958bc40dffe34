"""pagerank_roofline: the least time the chip's HBM bandwidth allows for
the window's PageRank calls (``kernel_cost.pagerank_bytes`` over the peak
in ``peaks.json``), as a share of the device's busy time inside the
benchmark's ``pagerank`` spans (``_pagerank_impl``: the gather and the
sorted segment sum of kernels/segment_reduce.py, and the view's per-edge
source ids)."""
from chipbench.kernel_cost import pagerank_bytes


def read(run):
    pr = run.info.get("pagerank")
    t = run.trace
    if pr is None or t is None or not t.span_device_s.get("pagerank"):
        return None
    calls = t.span_count["pagerank"]
    least_s = calls * pagerank_bytes(pr["edges"], pr["vertices"],
                                     pr["iters"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t.span_device_s["pagerank"]
