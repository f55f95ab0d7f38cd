"""The replayed transaction table, made from ``--seed``: numpy only.

The source deployments replay Kaggle's ``creditcard.csv`` (284,807 rows of
``Time, V1..V28, Amount``; 492 frauds). That file is not in the repo, so
every run makes a surrogate of it from the seed: the same column layout,
the published per-component spreads, the fraud class's mean shifts and
its share, 492/284,807. The arithmetic is a copy of the program's
``ccfd_tpu/data/surrogate.py`` (the checkpoint was trained on that
generator's rows, so these rows span its whole probability range); it is
kept here so that no later PR can change what a cell sends.

The canonical form of a row is its text on the wire: ``wire_rows`` formats
every value with ``%.6g`` and the float32 table is what parsing that text
gives back, so the reference scores exactly the numbers the program was
sent. Imported by the traffic generators' subprocesses: no JAX, nothing
of the program.
"""

from __future__ import annotations

import numpy as np

NUM_FEATURES = 30
KAGGLE_ROWS = 284_807
KAGGLE_FRAUDS = 492

_LADDER = np.array([
    1.959, 1.651, 1.516, 1.416, 1.380, 1.332, 1.237, 1.194, 1.099, 1.089,
    1.021, 0.999, 0.995, 0.959, 0.915, 0.876, 0.850, 0.838, 0.814, 0.771,
    0.735, 0.726, 0.624, 0.606, 0.521, 0.482, 0.404, 0.330,
], np.float32)
_FRAUD_SHIFT = np.array([
    -4.77, 3.63, -7.03, 4.54, -3.15, -1.40, -5.57, 0.57, -2.58, -5.68,
    3.80, -6.26, -0.11, -6.97, -0.09, -4.14, -6.67, -2.25, 0.68, 0.37,
    0.71, 0.014, -0.04, -0.105, 0.042, 0.051, 0.17, 0.075,
], np.float32)
_MAX_AMOUNT = 25_691.16


def _time_column(rng, n: int, night_weight: float) -> np.ndarray:
    day = rng.integers(0, 2, size=n) * 86_400.0
    bulge = rng.random(n) >= night_weight
    tod = np.where(
        bulge,
        np.clip(rng.normal(14 * 3600, 4.5 * 3600, size=n), 0, 86_399),
        rng.uniform(0, 86_400, size=n))
    return (day + tod).astype(np.float32)


def _licit_amounts(rng, n: int) -> np.ndarray:
    body = np.exp(rng.normal(np.log(22.0), 1.35, size=n))
    tail = rng.random(n) < 0.015
    pareto = (rng.pareto(1.1, size=n) + 1.0) * 150.0
    return np.clip(np.where(tail, pareto, body), 0.0, _MAX_AMOUNT)


def _fraud_amounts(rng, n: int) -> np.ndarray:
    small = np.exp(rng.normal(np.log(9.2), 1.2, size=n))
    big = rng.random(n) < 0.06
    large = np.exp(rng.normal(np.log(350.0), 1.0, size=n))
    return np.clip(np.where(big, large, small), 0.0, 2_125.87)


def surrogate_rows(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(n, 30)`` float32 rows and their 0/1 class, in a seeded shuffle
    (the stream must not send its frauds in one block)."""
    rng = np.random.default_rng(int(seed))
    n_fraud = max(1, round(n * KAGGLE_FRAUDS / KAGGLE_ROWS))
    n_licit = n - n_fraud
    v_licit = rng.normal(0.0, 1.0, size=(n_licit, 28)).astype(np.float32)
    v_licit[rng.random(n_licit) < 0.02] *= 3.0
    v_licit *= _LADDER[None, :]
    v_fraud = rng.normal(0.0, 1.0, size=(n_fraud, 28)).astype(np.float32)
    u = rng.random(n_fraud)
    stealth, mode_c = u < 0.40, u > 0.85
    scale = np.where(stealth[:, None], 1.25, 2.2)
    scale = np.where(mode_c[:, None], 1.5, scale).astype(np.float32)
    shift = _FRAUD_SHIFT[None, :] * np.where(stealth[:, None], 0.15, 0.9)
    c_shift = 0.3 * _FRAUD_SHIFT + np.concatenate(
        [np.zeros(21, np.float32), 2.5 * _LADDER[21:]])
    shift = np.where(mode_c[:, None], c_shift[None, :], shift)
    v_fraud = v_fraud * _LADDER[None, :] * scale + shift.astype(np.float32)
    x = np.concatenate([
        np.concatenate([_time_column(rng, n_licit, 0.25)[:, None], v_licit,
                        _licit_amounts(rng, n_licit)[:, None]], axis=1),
        np.concatenate([_time_column(rng, n_fraud, 0.45)[:, None], v_fraud,
                        _fraud_amounts(rng, n_fraud)[:, None]], axis=1),
    ]).astype(np.float32)
    y = np.concatenate([np.zeros(n_licit, np.int32),
                        np.ones(n_fraud, np.int32)])
    order = rng.permutation(n)
    return np.ascontiguousarray(x[order]), np.ascontiguousarray(y[order])


def wire_rows(x: np.ndarray) -> list[str]:
    """One ``v0,v1,...,v29`` line per row, every value as ``%.6g``."""
    fmt = ",".join(["%.6g"] * x.shape[1])
    return [fmt % tuple(r) for r in x.tolist()]


def parse_wire(lines: list[str], width: int = NUM_FEATURES) -> np.ndarray:
    """The float32 table that the wire text stands for."""
    flat = np.array(",".join(lines).split(","), dtype=np.float64)
    return flat.reshape(len(lines), width).astype(np.float32)


def make_table(n: int, seed: int) -> tuple[list[str], np.ndarray, np.ndarray]:
    """``(lines, rows, labels)``: the wire text of ``n`` rows, the float32
    table it parses to, and the generator's class of each row."""
    x, y = surrogate_rows(n, seed)
    lines = wire_rows(x)
    return lines, parse_wire(lines), y
