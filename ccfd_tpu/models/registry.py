"""Model registry: name -> (init, apply) the way the reference selects its
Seldon graph node by image name (reference deploy/model/modelfull.json:37-44,
``{"name": "modelfull", "type": "MODEL"}``). The serving layer and router look
models up here by the ``CCFD_MODEL`` / ``SELDON_ENDPOINT`` name.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax

from ccfd_tpu.models import logreg, mlp, trees


@dataclass(frozen=True)
class ModelSpec:
    name: str
    init: Callable[..., Any]
    apply: Callable[..., jax.Array]  # (params, x) -> proba_1 (B,)
    logits: Callable[..., jax.Array]
    trainable: bool
    # optional pure-numpy forward: the host reference, the wedge fallback,
    # the challenger slot and an explicit host tier score with it
    apply_numpy: Callable[..., Any] | None = None


@dataclass(frozen=True)
class HistorySpec:
    """A history family as :class:`~ccfd_tpu.serving.history.SeqScorer`
    serves it: the scorer finds its device program here, by name, once.

    ``make_apply(dtype, pos_length, config)`` returns the program
    ``fn(params, hist)``, or ``fn(params, hist, filled)`` where
    ``reads_filled``: ``hist`` (B, L, F) float32 windows, ``filled`` (B,)
    int32 real records per row (right-aligned; a family without a padding
    mask never sees it). It returns ``proba`` (B,), or ``(proba, aux)``
    with ``aux`` a dict of small device arrays. ``make_observer(registry)``
    returns ``observe(aux) -> stats``: it feeds the family's counters from
    a resolved dispatch's ``aux`` (numpy by then) and returns what the
    ``seq.wait`` phase should carry. ``owns(params)`` says whether a
    parameter tree that arrives without a name is the family's.
    ``mesh_logits`` is the logits function the mesh / sequence-parallel
    path jits (None: the family is not served over a mesh).
    ``describe(config)`` adds to ``executable_grid()``. ``config_from``
    builds the family's settings from a configuration under the served
    model's published key names (a deployment that knows the family by
    name only). ``scan_chunk(config, tokens)``: the tokens a step of the
    state-space scan takes at once in the executable of ``tokens``-token
    windows, None where the model has no such scan (a family without the
    function has none). ``swappable``:
    whether ``swap_params`` may stage a second tree beside the served
    one."""

    name: str
    owns: Callable[[Any], bool]
    make_apply: Callable[[Any, int, Any], Callable[..., Any]]
    reads_filled: bool = False
    make_observer: Callable[[Any], Callable[[dict], dict]] | None = None
    mesh_logits: Callable[..., jax.Array] | None = None
    describe: Callable[[Any], dict] | None = None
    config_from: Callable[[Any], Any] | None = None
    scan_chunk: Callable[[Any, int], int | None] | None = None
    swappable: bool = True


_REGISTRY: dict[str, ModelSpec] = {}
_HISTORY: dict[str, HistorySpec] = {}


def register_history(spec: HistorySpec) -> None:
    _HISTORY[spec.name] = spec


def get_history(name: str) -> HistorySpec:
    try:
        return _HISTORY[name]
    except KeyError:
        raise KeyError(f"unknown history family {name!r}; known: "
                       f"{sorted(_HISTORY)}") from None


def history_family_of(params: Any) -> HistorySpec:
    """The family a nameless parameter tree belongs to (a lifecycle
    promotion hands ``swap_params`` a tree, not a name)."""
    for spec in _HISTORY.values():
        if spec.owns(params):
            return spec
    raise ValueError("no registered history family owns this parameter "
                     f"tree; known: {sorted(_HISTORY)}")


def register_model(spec: ModelSpec) -> None:
    _REGISTRY[spec.name] = spec


def get_model(name: str) -> ModelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}") from None


register_model(
    ModelSpec("logreg", logreg.init, logreg.apply, logreg.logits,
              trainable=True, apply_numpy=logreg.apply_numpy)
)
register_model(
    ModelSpec("modelfull", logreg.init, logreg.apply, logreg.logits,
              trainable=True, apply_numpy=logreg.apply_numpy)
)  # reference alias: the Seldon graph node name (modelfull.json:38)
register_model(ModelSpec("mlp", mlp.init, mlp.apply, mlp.logits,
                         trainable=True, apply_numpy=mlp.apply_numpy))
register_model(
    ModelSpec(
        "gbt",
        lambda key=None, n_trees=50, depth=4: trees.init_empty(n_trees, depth),
        trees.apply,
        trees.logits,
        trainable=False,
        apply_numpy=trees.apply_numpy,
    )
)

register_model(
    ModelSpec(
        "gbt_mxu",
        lambda key=None, n_trees=50, depth=4: trees.init_empty(n_trees, depth),
        trees.apply_mxu,
        trees.logits_mxu,
        trainable=False,
        apply_numpy=trees.apply_numpy,
    )
)  # gather-free MXU evaluation of the SAME tree params (trees.logits_mxu)

# int8 quantized serving graph: registered here so CCFD_MODEL=mlp_q8 is a
# working drop-in everywhere models resolve by name (quant.py's imports of
# this module are all deferred inside register(), so no cycle)
from ccfd_tpu.ops import quant as _quant  # noqa: E402

_quant.register()

# sequence family: seq (bf16 champion) + seq_q8 (int8 lifecycle-gated
# variant); served through SeqScorer, not the row Scorer — see
# ops/seq_quant.register for the contract
from ccfd_tpu.ops import seq_quant as _seq_quant  # noqa: E402

_seq_quant.register()

# hybrid_moe: the KDA / MLA / CCA + sparse-expert backbones over a tokenised
# window (models/hybrid_moe.py), a third history family behind SeqScorer
from ccfd_tpu.models import hybrid_moe as _hybrid_moe  # noqa: E402

_hybrid_moe.register()
