"""The plain reference of the history scorer (``seq``), its weights, and
the comparison that decides ``correct`` for every cell that serves it.

The program has no committed checkpoint of this family, so the benchmark
draws one (``make_params``: numpy, the widths and the ``weights_seed`` of
the configuration's ``model`` section, the standardiser from a table of
that seed) and hands the same tree to the program and to ``forward``. It
is the same in every run, as a deployment serves one checkpoint: rows,
customers and their order are what ``--seed`` changes. Nothing here is
imported from the program.

``forward`` is the published arithmetic of ``models/seq.py`` in float32
numpy: standardise, project the 30 features to ``d_model``, add sinusoidal
positions, ``n_blocks`` pre-norm transformer blocks (full attention over
the history, no mask: a history shorter than ``length`` is zero rows on
the left, and those rows are attended like any other, which is the
program's stated behaviour), layer norm and a single logit at the newest
position. No kernels, no buckets, no read-out shortcut, no cache.

It stays float32 throughout, on purpose. A reference that rounds to
bfloat16 where the model's code casts does not come closer to the served
path: XLA drops and moves those roundings when it fuses (on the CPU
backend the jitted bf16 graph is as far from the same graph run op by op,
0.0049 in mean |dlogit|, as from float32), so any two bf16 evaluations
differ by about what either differs from float32 (PERF.md has the chip's
readings).

``histories`` rebuilds what the store must have held: the served path
appends a record to its customer's history in the order the records were
consumed, so the context of a customer's k-th record is its last
``length`` records, oldest first, zero rows before them.

``compare`` is ``mlp_f32``'s: ``mean_abs_dlogit`` (steady, separates the
precisions) and ``max_abs_dp`` (swings; there for an answer altered,
swapped or misordered).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import table
from benchmark.reference.mlp_f32 import compare, sigmoid  # noqa: F401

LN_EPS = 1e-6
HEAD_SHIFT = -2.2


def make_params(model: dict) -> dict:
    """The served weights, float32 numpy, in the layout of the program's
    ``models/seq.py`` tree. Dense weights are normal with variance
    1/fan-in; biases and layer-norm terms are small and not zero, so that
    a term left out shows."""
    seed = int(model["weights_seed"])
    rng = np.random.default_rng([seed, 0x5E9])
    rows, _ = table.surrogate_rows(8192, seed)
    d, f = int(model["d_model"]), int(model["num_features"])
    hidden = int(model["mlp_mult"]) * d

    def dense(fan_in: int, fan_out: int) -> dict:
        return {"w": (rng.normal(size=(fan_in, fan_out))
                      / np.sqrt(fan_in)).astype(np.float32),
                "b": (0.1 * rng.normal(size=fan_out)).astype(np.float32)}

    def norm() -> dict:
        return {"scale": (1.0 + 0.1 * rng.normal(size=d)).astype(np.float32),
                "bias": (0.1 * rng.normal(size=d)).astype(np.float32)}

    sigma = rows.std(axis=0)
    params = {
        "norm": {"mu": rows.mean(axis=0).astype(np.float32),
                 "sigma": np.where(sigma == 0.0, 1.0,
                                   sigma).astype(np.float32)},
        "embed": dense(f, d),
        "blocks": [{"ln1": norm(), "qkv": dense(d, 3 * d),
                    "proj": dense(d, d), "ln2": norm(),
                    "mlp_in": dense(d, hidden), "mlp_out": dense(hidden, d)}
                   for _ in range(int(model["n_blocks"]))],
    }
    head = dense(d, 1)
    # the logit comes out near normal(0, 0.7); the shift keeps the share of
    # fraud starts near the source's 492/284,807 and not near one half
    params["head"] = {"ln": norm(), "w": head["w"],
                      "b": head["b"] + np.float32(HEAD_SHIFT)}
    return params


def _layer_norm(x: np.ndarray, p: dict) -> np.ndarray:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x: np.ndarray) -> np.ndarray:
    # the tanh form, which is what the model states (jax.nn.gelu's default)
    inner = np.float32(np.sqrt(2.0 / np.pi)) * (
        x + np.float32(0.044715) * (x * x * x))
    return np.float32(0.5) * x * (np.float32(1.0) + np.tanh(inner))


def positions(length: int, d_model: int) -> np.ndarray:
    pos = np.arange(length, dtype=np.float32)[:, None]
    dim = np.arange(d_model // 2, dtype=np.float32)[None, :]
    angles = pos * np.exp(-np.log(10000.0) * 2.0 * dim / d_model)
    return np.concatenate([np.sin(angles), np.cos(angles)],
                          axis=-1).astype(np.float32)


def logits(params: dict, hist: np.ndarray, n_heads: int,
           block: int = 32) -> np.ndarray:
    """(n, L, F) float32 histories -> (n,) float32 logits of the newest
    record, in blocks of histories."""
    out = np.empty(len(hist), np.float32)
    for lo in range(0, len(hist), block):
        out[lo:lo + block] = _logits_block(
            params, np.asarray(hist[lo:lo + block], np.float32), n_heads)
    return out


def _logits_block(params: dict, x: np.ndarray, n_heads: int) -> np.ndarray:
    n, length, _ = x.shape

    def dense(t: np.ndarray, p: dict) -> np.ndarray:
        return t @ p["w"] + p["b"]

    h = dense((x - params["norm"]["mu"]) / params["norm"]["sigma"],
              params["embed"])
    d = h.shape[-1]
    h = h + positions(length, d)[None]
    dh = d // n_heads
    for blk in params["blocks"]:
        qkv = dense(_layer_norm(h, blk["ln1"]), blk["qkv"])
        q, k, v = (t.reshape(n, length, n_heads, dh).transpose(0, 2, 1, 3)
                   for t in np.split(qkv, 3, axis=-1))
        s = (q @ k.transpose(0, 1, 3, 2)) / np.float32(np.sqrt(dh))
        s = np.exp(s - s.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        a = s @ v
        a = a.transpose(0, 2, 1, 3).reshape(n, length, d)
        h = h + dense(a, blk["proj"])
        m = _gelu(dense(_layer_norm(h, blk["ln2"]), blk["mlp_in"]))
        h = h + dense(m, blk["mlp_out"])
    last = _layer_norm(h[:, -1, :], params["head"]["ln"])
    return dense(last, params["head"]).reshape(n)


def forward(params: dict, hist: np.ndarray, n_heads: int) -> np.ndarray:
    return sigmoid(logits(params, hist, n_heads))


def histories(customer: np.ndarray, row_of: np.ndarray, rows: np.ndarray,
              which: np.ndarray, length: int) -> np.ndarray:
    """The contexts of the records ``which`` (positions in consumption
    order): ``customer[i]`` is the key of the i-th consumed record and
    ``row_of[i]`` its row of the table ``rows``."""
    order = np.argsort(customer, kind="stable")
    rank_in_customer = np.empty(len(customer), np.int64)
    starts = np.flatnonzero(np.r_[True, np.diff(customer[order]) != 0])
    first = np.repeat(starts, np.diff(np.r_[starts, len(order)]))
    rank_in_customer[order] = np.arange(len(order)) - first
    at = np.empty(len(customer), np.int64)  # position inside ``order``
    at[order] = np.arange(len(order))
    out = np.zeros((len(which), length, rows.shape[1]), np.float32)
    for j, i in enumerate(which):
        depth = min(int(rank_in_customer[i]) + 1, length)
        mine = order[at[i] - depth + 1:at[i] + 1]
        out[j, length - depth:] = rows[row_of[mine]]
    return out


def served_and_expected(config: dict, outcome, *, seed: int,
                        root: str) -> tuple[np.ndarray, np.ndarray, str]:
    """A sample, drawn from the seed, of the verdicts the run served, with
    the deepest history in it, and the reference's probability of each."""
    stream = outcome.stream
    model, ref = config["model"], config["reference"]
    length = int(config["serving"]["length"])
    _, rows, _ = table.make_table(int(config["table_rows"]), seed)
    params = make_params(model)
    customer, row_of = stream["customer"], stream["row"]
    n = len(customer)
    rng = np.random.default_rng([int(seed), 0x5A3])
    take = min(int(ref["sample_records"]), n)
    which = rng.choice(n, size=take, replace=False) if n else np.zeros(0, int)
    if n:  # the newest record of the customer with the most records
        counts = np.bincount(customer)
        deepest = np.flatnonzero(customer == counts.argmax())[-1]
        which = np.unique(np.r_[which, deepest])
    hist = histories(customer, row_of, rows, which, length)
    depth = (np.abs(hist).sum(-1) > 0).sum(-1)
    expect = forward(params, hist, int(model["n_heads"]))
    note = (f"{len(which)} of {n} served verdicts, history depth "
            f"min {depth.min() if n else 0} median "
            f"{int(np.median(depth)) if n else 0} max "
            f"{depth.max() if n else 0} of {length}")
    return stream["proba"][which], expect, note
