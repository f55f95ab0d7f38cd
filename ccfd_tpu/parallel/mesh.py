"""Device-mesh construction for sharded scoring and retraining.

The reference scales by Kafka partitions and k8s replicas (SURVEY.md §2,
"Parallelism strategies"); the TPU-native analog is a 2-D
``jax.sharding.Mesh`` over the pod:

- axis ``"data"`` — batch shards (data parallelism): each chip scores or
  trains on its slice of the micro-batch; gradient psum rides the ICI.
- axis ``"model"`` — hidden-dimension shards (tensor parallelism) for wide
  models; matmul partials reduce over ICI.

For the tabular CCFD models the data axis does nearly all the work
(BASELINE.json configs[4]: "SGD on TPU, pmap over v5e-4" — here expressed
as pjit over the data axis); the model axis exists so the same code drives
wide-MLP experiments and validates the collective layout.
"""

from __future__ import annotations

from jax.sharding import Mesh
import jax
import numpy as np

DATA_AXIS = "data"
MODEL_AXIS = "model"

# first-class partitioning-layer axis names (parallel/partition.py): the
# canonical data/fsdp/tp vocabulary the rule tables speak. ``MODEL_AXIS``
# stays as the legacy 2-D mesh's second axis name; the named mesh below is
# the serving platform's shape.
FSDP_AXIS = "fsdp"
TP_AXIS = "tp"
NAMED_AXES = (DATA_AXIS, FSDP_AXIS, TP_AXIS)
# the axis the chips that share each layer of an expert model lie on:
# experts and the vocabulary divide over it, attention replicates
# (partition.hybrid_moe_rules / expert_share). One chip of such a group
# serves its share without a mesh; the exchange across the axis is not in
# the program yet (ROADMAP B2).
EXPERT_AXIS = "ep"


def make_mesh(
    devices: list | None = None, model_parallel: int = 1
) -> Mesh:
    """(n/model_parallel) x model_parallel mesh over the given devices."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if n % model_parallel != 0:
        raise ValueError(
            f"{n} devices not divisible by model_parallel={model_parallel}"
        )
    grid = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def make_named_mesh(
    devices: list | None = None, fsdp: int = 1, tp: int = 1
) -> Mesh:
    """3-D ``(data, fsdp, tp)`` named mesh; data absorbs the remainder.

    The partitioning layer's canonical shape (parallel/partition.py):
    batches shard over ``data``, param rules speak ``fsdp``/``tp``. Axes
    an operator leaves at 1 cost nothing — a pure data-parallel serving
    mesh is ``(n, 1, 1)`` and every rule's fsdp/tp entry lands on a
    size-1 axis (replication)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    fsdp, tp = max(1, int(fsdp)), max(1, int(tp))
    if n % (fsdp * tp) != 0:
        raise ValueError(
            f"{n} devices not divisible by fsdp*tp={fsdp * tp}"
        )
    grid = np.asarray(devices).reshape(n // (fsdp * tp), fsdp, tp)
    return Mesh(grid, NAMED_AXES)
