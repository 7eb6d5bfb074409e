"""Work counted from shapes alone: model FLOPs and kernel bytes.

These are the numerators of the roofline and MFU shares. They depend on
the published shapes and the call's arguments only, never on how the
program happens to implement the work (tile sizes, bisection trip
counts, recomputation), so every PR is measured against the same work.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

#: forward and backward passes that Algorithm 1 requires of each machine
#: in one quasi-Newton step: R1's local step at theta, R2's gradient at
#: theta_cq and R4's gradient at theta_os. R4's second gradient at
#: theta_cq repeats R2's, and rematerialisation recomputes; neither is
#: required work.
QN_PASSES_PER_STEP = 3


def xlstm_param_counts(cfg: dict) -> Dict[str, int]:
    """Parameters of the xLSTM of ``cfg`` (``d_model``, ``n_heads``,
    ``n_layers``, ``vocab``, ``slstm_at``, ``mlstm_up``): mLSTM blocks
    with a pre up-projection by ``mlstm_up`` and a SiLU gate branch,
    sLSTM blocks with block-diagonal recurrent weights, untied
    embedding and head."""
    d, h, v = cfg["d_model"], cfg["n_heads"], cfg["vocab"]
    di = cfg["mlstm_up"] * d
    mlstm = 2 * d * di + 3 * di * di + di * 2 * h + di * d
    slstm = 4 * d * d + h * (d // h) * 4 * (d // h) + d * d
    n_s = len(cfg["slstm_at"])
    n_m = cfg["n_layers"] - n_s
    return {"mlstm_matmul": n_m * mlstm, "slstm_matmul": n_s * slstm,
            "head": d * v, "embed": v * d,
            "vectors": cfg["n_layers"] * d + d + n_m * 2 * h
            + n_s * 4 * d}


def xlstm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Training FLOPs per token of one forward and backward pass (3x the
    forward): 2 FLOPs per multiply-add of every weight matrix a token
    passes (the embedding is a lookup), plus the mLSTM parallel form's
    sequence term, q.k and (q.k).v over the causal context, on average
    (seq + 1) / 2 positions."""
    c = xlstm_param_counts(cfg)
    matmul = c["mlstm_matmul"] + c["slstm_matmul"] + c["head"]
    di = cfg["mlstm_up"] * cfg["d_model"]
    n_m = cfg["n_layers"] - len(cfg["slstm_at"])
    seq_term = n_m * 2 * 2 * di * (seq + 1) / 2.0
    return 3.0 * (2.0 * matmul + seq_term)


def qn_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Required model FLOPs of one quasi-Newton step over the whole batch
    (all machines together)."""
    return (QN_PASSES_PER_STEP * batch * seq
            * xlstm_train_flops_per_token(cfg, seq))


def ostat_bytes(shape: Sequence[int], itemsize: int, n_out: int = 1,
                scale: bool = False) -> int:
    """HBM bytes of one order-statistics kernel call on values of
    ``shape`` = ``(*batch, m, p)``: the values read once, a per-coordinate
    scale read once where the rule takes one, and ``n_out`` outputs of
    ``(*batch, p)`` written. Independent of tile, ``inner`` and the
    bisection trip count."""
    *batch, m, p = shape
    rows = 1
    for b in batch:
        rows *= b
    per_coord = rows * p * itemsize
    return rows * m * p * itemsize + (per_coord if scale else 0) \
        + n_out * per_coord


def tree_aggregation_bytes(leaves: Iterable[Tuple[Tuple[int, ...], int]],
                           machines: int, transmissions: int) -> int:
    """Kernel bytes of aggregating every leaf of a parameter tree over
    ``machines`` rows, ``transmissions`` times: ``leaves`` gives each
    leaf's shape (without the machine axis) and itemsize. A leaf goes to
    the kernel as ``(rows, m, cols)``."""
    total = 0
    for shape, itemsize in leaves:
        cols = shape[-1] if shape else 1
        rows = 1
        for s in shape[:-1]:
            rows *= s
        total += ostat_bytes((rows, machines, cols), itemsize)
    return transmissions * total
