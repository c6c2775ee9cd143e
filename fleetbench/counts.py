"""Operations and bytes of the aggregation, from shapes alone.

They do not depend on the model: a model's own counts (training and
evaluation FLOPs, the packed row length) are in its ``models/<kind>.py``.
"""
from __future__ import annotations


def agg_bytes(rows: int, dim: int) -> int:
    """One read of a (rows, dim) float32 buffer."""
    return 4 * int(rows) * int(dim)


def agg_flops(rows: int, dim: int) -> float:
    """A weighted sum over rows: one multiply and one add per element."""
    return 2.0 * rows * dim
