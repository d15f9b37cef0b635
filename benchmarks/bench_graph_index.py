"""Adjacency-index microbenchmark (satellite of the pipeline refactor).

`OpGraph.consumers`/`producer` used to be O(N) linear scans and the
fusion pass's candidate search made Alg. C.1 O(N²) per fixpoint pass.
Both now run off O(1) adjacency indexes; this benchmark quantifies the
drop on a 500-op chain (residual conv + element-wise pairs, the shape
fusion stresses).  `scan` rows time the old approach inline for
comparison.
"""
from __future__ import annotations

import time

from repro.core.fusion import fuse_graph
from repro.core.ir import OpGraph
from repro.utils.compile_cache import enable_compile_cache
from benchmarks.common import emit_csv

N_OPS = 500


def build_chain(n_ops: int) -> OpGraph:
    g = OpGraph(f"chain{n_ops}")
    t = g.add_input((1, 16, 16, 32))
    for _ in range(n_ops // 2):
        (c,) = g.add_op("conv2d", [t], [(1, 16, 16, 32)],
                        {"kernel_h": 3, "kernel_w": 3, "stride": 1, "groups": 1})
        (t,) = g.add_op("elementwise", [c], [(1, 16, 16, 32)],
                        {"ew_kind": "add"})
    g.mark_output(t)
    return g


def _time(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run() -> None:
    g = build_chain(N_OPS)

    def sweep_indexed():
        for tid in g.tensors:
            g.consumers(tid)
            g.producer(tid)

    def sweep_scan():  # the pre-index implementation, for reference
        for tid in g.tensors:
            [n for n in g.nodes if tid in n.inputs]
            next((n for n in g.nodes if tid in n.outputs), None)

    t_indexed = _time(sweep_indexed)
    t_scan = _time(sweep_scan)
    t_fuse = _time(lambda: fuse_graph(g))

    emit_csv("graph_index", [
        {"name": "consumers_sweep_indexed_ms", "value": f"{1e3 * t_indexed:.2f}",
         "derived": f"{N_OPS}-op graph, all tensors"},
        {"name": "consumers_sweep_scan_ms", "value": f"{1e3 * t_scan:.2f}",
         "derived": f"{t_scan / max(t_indexed, 1e-9):.0f}x slower"},
        {"name": "fuse_graph_ms", "value": f"{1e3 * t_fuse:.2f}",
         "derived": "indexed candidate search (was O(N^2)/pass)"},
    ], fieldnames=["name", "value", "derived"])


if __name__ == "__main__":
    enable_compile_cache()
    run()
