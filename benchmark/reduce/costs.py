"""What a scoring call needs of the chip, computed from shapes: the
operations and the bytes that the algorithm cannot do without. Kept with
the benchmark so that a kernel's roofline share means the same in every
PR; from the program it takes nothing.
"""

from __future__ import annotations


def mlp_costs(dims: list[int], rows: int, dispatches: int, *,
              in_bytes_per_value: int, weight_bytes_per_value: int,
              out_bytes_per_row: int) -> tuple[float, float]:
    """``(operations, bytes)`` of scoring ``rows`` rows in ``dispatches``
    device calls with a dense MLP of layer widths ``dims`` (30, 256, 256,
    1 for the flagship: 147,004 operations a row).

    Operations: two per multiply-add of every layer, and two per feature
    for the standardiser. Bytes: each row read once at the width it is
    staged in and its probability written once; the weights (in the
    precision they are multiplied in) and the float32 biases read once per
    call. Padding rows, lane padding and intermediate copies are not
    needed by the algorithm and do not count, so a kernel that moves them
    reads a lower share."""
    macs = sum(a * b for a, b in zip(dims, dims[1:]))
    flop = rows * (2.0 * macs + 2.0 * dims[0])
    weights = macs * weight_bytes_per_value + 4 * sum(dims[1:])
    moved = (rows * (dims[0] * in_bytes_per_value + out_bytes_per_row)
             + dispatches * weights)
    return flop, float(moved)


def seq_costs(c: dict, rows: int, dispatches: int) -> tuple[float, float]:
    """``(operations, bytes)`` of scoring ``rows`` histories of ``length``
    records in ``dispatches`` device calls with the ``seq`` transformer:
    pre-norm blocks of full attention and a 4x feed-forward, read out at
    the newest position.

    Operations, two per multiply-add. A block needs all of its positions
    only while a later block attends to them, so every block but the last
    runs in full: 24 L d^2 for its four projections and its feed-forward
    (mlp_mult 4) and 4 L^2 d for scores and weighted values. Of the last
    block the algorithm needs keys and values at every position (4 L d^2)
    and everything else at one (query, output projection, feed-forward:
    (4 + 4 mlp_mult) d^2; attention 4 L d). Embedding 2 L F d, head 2 d.
    Layer norms, softmax, activation and the standardiser are left out, so
    the count is a floor. Bytes: each history read once as it is staged
    and its probability written once; the weights read once per call."""
    length, f, d = int(c["length"]), int(c["num_features"]), int(c["d_model"])
    blocks, mult = int(c["n_blocks"]), int(c["mlp_mult"])
    full = (8 + 4 * mult) * length * d * d + 4 * length * length * d
    last = 4 * length * d * d + (4 + 4 * mult) * d * d + 4 * length * d
    flop = rows * (2.0 * length * f * d + (blocks - 1) * full + last + 2 * d)
    weights = f * d + blocks * (4 + 2 * mult) * d * d + d
    moved = (rows * (length * f * int(c["in_bytes_per_value"])
                     + int(c["out_bytes_per_row"]))
             + dispatches * weights * int(c["weight_bytes_per_value"]))
    return float(flop), float(moved)


def of(c: dict, rows: int, dispatches: int) -> tuple[float, float]:
    """By the ``kind`` of a configuration's ``costs`` section (``mlp``
    where it names none)."""
    kind = c.get("kind", "mlp")
    if kind == "mlp":
        return mlp_costs(
            c["dims"], rows, dispatches,
            in_bytes_per_value=c["in_bytes_per_value"],
            weight_bytes_per_value=c["weight_bytes_per_value"],
            out_bytes_per_row=c["out_bytes_per_row"])
    if kind == "seq":
        return seq_costs(c, rows, dispatches)
    raise ValueError(f"unknown costs kind {kind!r}")
