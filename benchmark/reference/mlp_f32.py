"""The plain reference of the ``mlp`` scorer, and the comparison that
decides ``correct`` for every cell that serves it.

``forward`` is the model's published arithmetic in float32 numpy:
standardise, two ReLU layers, a sigmoid head. No kernels, no buckets, no
batching, nothing imported from the program, and nothing the program made:
the weights are read from the committed checkpoint with orbax directly.

``compare`` holds the served probabilities against it by two numbers:

- ``mean_abs_dlogit``: the mean over the sampled rows of |logit(served) -
  logit(reference)|, probabilities clipped to [1e-7, 1 - 1e-7]. Steady from
  seed to seed (the mean of tens of thousands of rows) and it separates
  precisions: the limit sits between what the bf16 kernel reads and what
  the int8 path reads (PERF.md has the readings).
- ``max_abs_dp``: the widest |served - reference| probability. It swings by
  its nature and hardly tells bf16 from int8; it is there for the fault it
  catches, an answer altered, swapped or misordered, which moves it to ~1.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.reference import table

CLIP = 1e-7


def load_checkpoint(checkpoint_dir: str) -> dict:
    """The newest ``step_<n>`` under ``checkpoint_dir`` as float32 numpy:
    ``{"norm": {"mu", "sigma"}, "layers": [{"w", "b"}, ...]}``."""
    import orbax.checkpoint as ocp

    steps = sorted(
        (int(d.split("_", 1)[1]), d) for d in os.listdir(checkpoint_dir)
        if d.startswith("step_") and d.split("_", 1)[1].isdigit())
    if not steps:
        raise FileNotFoundError(f"no step_<n> under {checkpoint_dir}")
    path = os.path.abspath(os.path.join(checkpoint_dir, steps[-1][1]))
    tree = ocp.PyTreeCheckpointer().restore(path)
    layers = tree["layers"]
    if isinstance(layers, dict):  # orbax restores a list as {"0": ..}
        layers = [layers[k] for k in sorted(layers, key=int)]
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    return {
        "norm": {"mu": f32(tree["norm"]["mu"]),
                 "sigma": f32(tree["norm"]["sigma"])},
        "layers": [{"w": f32(l["w"]), "b": f32(l["b"])} for l in layers],
    }


def logits(params: dict, x: np.ndarray, block: int = 16384) -> np.ndarray:
    """(n, 30) float32 -> (n,) float32 logits, in blocks of rows."""
    mu, sigma = params["norm"]["mu"], params["norm"]["sigma"]
    sigma = np.where(sigma == 0.0, 1.0, sigma).astype(np.float32)
    out = np.empty(len(x), np.float32)
    for lo in range(0, len(x), block):
        h = (np.asarray(x[lo:lo + block], np.float32) - mu) / sigma
        for layer in params["layers"][:-1]:
            h = np.maximum(h @ layer["w"] + layer["b"], 0.0)
        last = params["layers"][-1]
        out[lo:lo + block] = (h @ last["w"] + last["b"]).reshape(-1)
    return out


def sigmoid(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.float64)
    return np.where(z >= 0, 1.0 / (1.0 + np.exp(-np.abs(z))),
                    np.exp(-np.abs(z)) / (1.0 + np.exp(-np.abs(z))))


def forward(params: dict, x: np.ndarray) -> np.ndarray:
    """(n, 30) -> (n,) fraud probability, float64 of a float32 logit."""
    return sigmoid(logits(params, x))


def served_and_expected(config: dict, outcome, *, seed: int,
                        root: str) -> tuple[np.ndarray, np.ndarray, str]:
    """Every served verdict of the run, and the reference's probability of
    the table row it was served for."""
    ref_doc = config["reference"]
    _, rows, _ = table.make_table(int(config["table_rows"]), seed)
    params = load_checkpoint(os.path.join(root, ref_doc["checkpoint_dir"]))
    expect = forward(params, rows)
    return (outcome.served_proba, expect[outcome.served_rows],
            f"{len(rows)} rows, {len(outcome.served_rows)} served verdicts")


def flop_per_row(params: dict) -> int:
    """Multiply-adds of the three layers and the standardiser, times two."""
    return int(sum(2 * l["w"].shape[0] * l["w"].shape[1]
                   for l in params["layers"])
               + 2 * params["norm"]["mu"].shape[0])


def _logit(p: np.ndarray) -> np.ndarray:
    p = np.clip(np.asarray(p, np.float64), CLIP, 1.0 - CLIP)
    return np.log(p) - np.log1p(-p)


def compare(served: np.ndarray, reference: np.ndarray) -> dict[str, float]:
    """The two numbers of the module docstring, for rows in one order."""
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    if served.shape != reference.shape or served.size == 0:
        return {"mean_abs_dlogit": float("inf"), "max_abs_dp": float("inf")}
    if not np.isfinite(served).all():
        return {"mean_abs_dlogit": float("inf"), "max_abs_dp": float("inf")}
    return {
        "mean_abs_dlogit": float(
            np.abs(_logit(served) - _logit(reference)).mean()),
        "max_abs_dp": float(np.abs(served - reference).max()),
    }
