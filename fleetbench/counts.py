"""Operations and bytes the benchmark's work needs, from shapes alone."""
from __future__ import annotations


def mlp_macs(model: dict) -> int:
    """Multiply-accumulates of one sample through the classifier."""
    d, h = int(model["dim"]), int(model["hidden"])
    depth, k = int(model["depth"]), int(model["num_classes"])
    return d * h + (depth - 1) * h * h + h * k


def packed_dim(model: dict) -> int:
    """Parameters of the classifier (the packed row length D)."""
    d, h = int(model["dim"]), int(model["hidden"])
    depth, k = int(model["depth"]), int(model["num_classes"])
    return d * h + h + (depth - 1) * (h * h + h) + h * k + k


def train_flops(model: dict, samples: int) -> float:
    """Forward and backward passes of ``samples`` samples: 2 FLOPs per
    MAC forward, 4 backward (input and weight gradients); biases and
    activations are not counted."""
    return 6.0 * mlp_macs(model) * samples


def eval_flops(model: dict, samples: int) -> float:
    return 2.0 * mlp_macs(model) * samples


def agg_bytes(rows: int, dim: int) -> int:
    """One read of a (rows, dim) float32 buffer."""
    return 4 * int(rows) * int(dim)


def agg_flops(rows: int, dim: int) -> float:
    """A weighted sum over rows: one multiply and one add per element."""
    return 2.0 * rows * dim
