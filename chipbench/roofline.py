"""Least work of one tree-ensemble traversal call, from shapes only.

Whatever the implementation (gather loop, one-hot matmul, Pallas), a
call that routes ``rows`` feature rows through ``trees`` trees of
depth ``depth`` has to read the bank once, read every feature row and
write one float32 result per row, and make one comparison per row,
tree and level plus one leaf read.  Bank bytes count each node's
feature index, threshold, two children and value at 4 bytes, and one
root index per tree.
"""
from __future__ import annotations

from typing import Dict, Tuple

NODE_BYTES = 5 * 4
ROOT_BYTES = 4


def bank_bytes(n_nodes: int, n_trees: int) -> int:
    return n_nodes * NODE_BYTES + n_trees * ROOT_BYTES


def traversal_work(rows: int, trees: int, depth: int, features: int,
                   bank_nbytes: int) -> Tuple[float, float]:
    """(operations, bytes) that any traversal of this call must do."""
    ops = float(rows) * trees * (depth + 1)
    nbytes = float(bank_nbytes) + rows * features * 4 + rows * 4
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: Dict[str, float]) -> float:
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
