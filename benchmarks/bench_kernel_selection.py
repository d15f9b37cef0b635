"""Paper Fig. 8/9 + Table 2 reproduction: kernel selection.

(a) Winograd vs direct conv wall-clock on the CPU device for ResNet-ish
    convolution shapes (Fig. 8's object of study), including the paper's
    Table 2 selection decisions per GPU family;
(b) optimized grouped_convolution_2d kernel vs the naive 3-stage
    split/conv/concat implementation (Fig. 9; e.g., RegNet shapes).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_csv
from repro.core.executor import GraphExecutor
from repro.core.ir import OpGraph
from repro.core.selection import check_winograd, get_device
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.timing import time_callable


def _conv_graph(in_c, out_c, hw, k=3, groups=1, winograd=False, naive=False):
    g = OpGraph("sel")
    x0 = g.add_input((1, hw, hw, in_c))
    op = "winograd_conv2d" if winograd else (
        "grouped_conv2d" if groups > 1 else "conv2d")
    params = {"kernel_h": k, "kernel_w": k, "stride": 1, "groups": groups}
    if naive:
        params["naive_split"] = True
    (c1,) = g.add_op(op, [x0], [(1, hw, hw, out_c)], params)
    g.mark_output(c1)
    return g


def _time_graph(g) -> float:
    ex = GraphExecutor(g, "op_by_op")
    inputs = ex.example_inputs()
    return time_callable(lambda *a: ex(*a), inputs, warmup=2, inner=8, repeats=3)


def run() -> List[Dict]:
    rows = []
    # (a) Winograd vs direct — paper Table 2 shapes (ResNet16 convs),
    # measured at profiling resolution (half the paper's 224 scale).
    for name, (c_in, c_out, hw) in {
        "resnet_conv1_64x56": (64, 64, 28),
        "resnet_conv2_128x28": (128, 128, 14),
        "resnet_conv3_256x14": (256, 256, 7),
    }.items():
        direct = _time_graph(_conv_graph(c_in, c_out, hw))
        wino = _time_graph(_conv_graph(c_in, c_out, hw, winograd=True))
        g = _conv_graph(c_in, c_out, hw)
        rows.append({
            "name": f"winograd_{name}",
            "us_per_call": round(1e6 * wino, 1),
            "direct_us": round(1e6 * direct, 1),
            "speedup": round(direct / wino, 3),
            "select_mali": check_winograd(get_device("mali_g76"), g.nodes[0], g),
            "select_adreno": check_winograd(get_device("adreno640"), g.nodes[0], g),
        })
    # (b) grouped conv: optimized single kernel vs naive 3-stage.
    for name, (c, hw, groups) in {
        "regnet_104c_g8": (104, 28, 8),
        "regnet_208c_g13": (208, 14, 13),
        "wide_256c_g4": (256, 14, 4),
    }.items():
        fused = _time_graph(_conv_graph(c, c, hw, groups=groups))
        naive = _time_graph(_conv_graph(c, c, hw, groups=groups, naive=True))
        rows.append({
            "name": f"grouped_{name}",
            "us_per_call": round(1e6 * fused, 1),
            "direct_us": round(1e6 * naive, 1),
            "speedup": round(naive / fused, 3),
        })
    emit_csv("bench_kernel_selection", rows,
             fieldnames=["name", "us_per_call", "direct_us", "speedup",
                         "select_mali", "select_adreno"])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
