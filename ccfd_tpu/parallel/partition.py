"""First-class partitioning layer: named mesh, regex rules, partitioners.

The multichip dryrun (``__graft_entry__.dryrun_multichip``) proved sharded
serving/retrain compiles and answers on 8 devices, but every sharding
decision lived ad hoc at its call site — the Scorer hand-rolled its batch
NamedSharding, the train step hand-rolled ``mlp_param_spec``, and nothing
owned the questions the LIVE platform has to answer: which axis does a
param shard over, how do host trees get on and off the mesh, and how does
a hot swap publish sharded params under in-flight SPMD dispatches.

This module is that owner (ROADMAP item 2; SNIPPETS.md [1]-[3]):

- :func:`match_partition_rules` — regex rules over ``/``-joined param
  pytree paths -> a pytree of ``PartitionSpec``. Scalars and size-1
  leaves never partition; a param no rule covers raises (an unsharded
  wide layer silently replicating is exactly the OOM-later bug the rule
  table exists to catch).
- :class:`SpecLayout` — the canonical ``data``/``fsdp``/``tp`` spec
  vocabulary plus the stock rule tables for the model families
  (:func:`mlp_rules`, :func:`seq_rules`).
- :class:`DataParallelPartitioner` / :class:`SPMDPartitioner` — shard /
  gather fns over a named mesh, explicit-sharding entry points for the
  donated train step, and the **publish path**: a param swap takes the
  ParallelRouter's group pause barrier so no worker's in-flight sharded
  dispatch interleaves with the re-layout (:class:`PublishGate`, armed
  via ``set_barrier`` and entered by the scorers' ``swap_params``).
- :func:`params_fingerprint` — sha256 over the FULLY-GATHERED leaf bytes
  (path-sorted, dtype+shape framed), so a checkpoint lineage hash is
  identical whether the params lived on 1 chip or 8 (device-count-
  invariant provenance; lifecycle/versions.py records it).

Everything drills on CPU CI under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exactly like the
dryrun.
"""

from __future__ import annotations

import hashlib
import re
import threading
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ccfd_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, TP_AXIS


# -- pytree path naming ------------------------------------------------------

def _path_str(path: Any) -> str:
    """``/``-joined human path for one pytree leaf (dict keys, sequence
    indices, dataclass fields)."""
    parts: list[str] = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:  # pragma: no cover - exotic path entry
            parts.append(str(p))
    return "/".join(parts)


def named_tree_map(fn: Callable[[str, Any], Any], tree: Any) -> Any:
    """``jax.tree.map`` with the leaf's ``/``-joined path as first arg."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(_path_str(path), leaf), tree
    )


def tree_paths(tree: Any) -> list[str]:
    """Every leaf path in ``tree``, ``/``-joined (rule-table authoring aid)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [_path_str(path) for path, _ in leaves]


# -- regex partition rules ---------------------------------------------------

def match_partition_rules(
    rules: Sequence[tuple[str, P]], params: Any
) -> Any:
    """Pytree of ``PartitionSpec`` from ``(regex, spec)`` rules.

    Scalars and single-element leaves always replicate (``P()``) without
    consulting the rules — partitioning a step counter or a 1-element
    bias is never meaningful. First matching rule wins (``re.search``
    over the ``/``-joined path). A leaf NO rule covers raises: silence
    here would hand a caller who needed the sharded layout a replicated
    tree and an OOM later. Works over optimizer-state trees too — optax
    states embed param-structured subtrees whose leaf paths end with the
    same param names, so the same table covers them.
    """

    def spec_for(name: str, leaf: Any) -> P:
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        raise ValueError(f"partition rule not found for param: {name!r}")

    return named_tree_map(spec_for, params)


class SpecLayout:
    """Canonical PartitionSpecs aligned with the named mesh axes.

    One place spells how each tensor role lays out over
    ``data``/``fsdp``/``tp``; the per-family rule tables below only bind
    regexes to these roles. Axis names are parameters so the same layout
    drives the legacy 2-D ``(data, model)`` mesh (``tp_axis="model"``).
    """

    def __init__(self, data_axis: str = DATA_AXIS,
                 fsdp_axis: str = FSDP_AXIS, tp_axis: str = TP_AXIS):
        self.data_axis = data_axis
        self.fsdp_axis = fsdp_axis
        self.tp_axis = tp_axis

    def batch(self) -> P:
        """Row batches shard over data; feature dim stays whole."""
        return P(self.data_axis, None)

    def rows(self) -> P:
        """Per-row outputs (probabilities/labels) shard over data."""
        return P(self.data_axis)

    def replicated(self) -> P:
        return P()

    def col_parallel(self) -> P:
        """(in, out) weight, column-sharded: activations come out sharded
        on the hidden dim, no collective needed going in."""
        return P(self.fsdp_axis, self.tp_axis)

    def row_parallel(self) -> P:
        """(in, out) weight, row-sharded: each chip contracts its hidden
        slice; XLA inserts the psum."""
        return P(self.tp_axis, None)

    def hidden_bias(self) -> P:
        """Bias on a tp-sharded hidden dim follows its activations."""
        return P(self.tp_axis)


def mlp_rules(layout: SpecLayout | None = None) -> list[tuple[str, P]]:
    """Megatron layout for the flagship MLP (models/mlp.py tree:
    ``norm/{mu,sigma}`` + ``layers/<i>/{w,b}``) — the same layout
    ``sharding.mlp_param_spec`` hand-writes, expressed as rules (parity
    is test-pinned)."""
    lo = layout or SpecLayout()
    return [
        (r"norm/", lo.replicated()),
        # first layer: column-parallel in; its bias rides the sharded
        # hidden dim
        (r"layers/0/w", P(None, lo.tp_axis)),
        (r"layers/0/b", lo.hidden_bias()),
        # last layer: row-parallel out (psum produces replicated logits);
        # the matching is ordered, so the generic hidden rule below only
        # sees the middle layers
        (r"layers/\d+/w$", lo.row_parallel()),
        (r"layers/\d+/b$", lo.replicated()),
    ]


def seq_rules(layout: SpecLayout | None = None) -> list[tuple[str, P]]:
    """Transformer layout for the history model (models/seq.py tree:
    embed / blocks/<i>/{ln1,qkv,proj,ln2,mlp_in,mlp_out} / head):
    attention + MLP matmuls shard fsdp x tp, norms/bias replicate."""
    lo = layout or SpecLayout()
    return [
        (r"embed/w", P(None, lo.tp_axis)),
        (r"embed/b", lo.hidden_bias()),
        (r"blocks/\d+/(qkv|mlp_in)/w", lo.col_parallel()),
        (r"blocks/\d+/(proj|mlp_out)/w", lo.row_parallel()),
        (r"blocks/\d+/.*/(b|scale|bias)", lo.replicated()),
        (r"head/", lo.replicated()),
        (r"norm/", lo.replicated()),
    ]


def expert_share(num_experts: int, shares: int, index: int) -> dict:
    """The experts chip ``index`` of ``shares`` chips holds when they divide
    ``num_experts`` among them in order: ``{"first", "count"}``, what an
    expert layer is told (``models/hybrid_moe.py`` ``experts_held``). The
    router keeps its full width on every chip; a chip computes its own
    experts' part and leaves the rest to the others."""
    if num_experts % shares or not 0 <= index < shares:
        raise ValueError(
            f"{num_experts} experts over {shares} chips, chip {index}")
    count = num_experts // shares
    return {"first": index * count, "count": count}


def hybrid_moe_rules(expert_axis: str = "ep") -> list[tuple[str, P]]:
    """Layout of the ``hybrid_moe`` tree (models/hybrid_moe.py) over the
    chips that share each layer: the experts' matrices and the vocabulary
    (embedding rows, head columns) divide over ``expert_axis``; mixers,
    router, shared expert, norms and the tokeniser's edges replicate (each
    chip runs attention on its own micro-batches). What one chip of the
    group holds is one shard of this layout; serving over the whole mesh
    also needs the exchange of routed tokens, which the program does not
    have yet. A stack of identical layers may arrive as one tree with the
    layers on every leaf's leading axis (``layers/ffn/...``, scanned) or
    as a list (``layers/<i>/ffn/...``): the experts' axis is the second
    or the first."""
    return [
        (r"^layers/ffn/experts/(gate|up|down)",
         P(None, expert_axis, None, None)),
        (r"ffn/experts/(gate|up|down)", P(expert_axis, None, None)),
        (r"^embed$", P(expert_axis, None)),
        (r"^head$", P(None, expert_axis)),
        (r".*", P()),
    ]


# -- shard / gather ----------------------------------------------------------

def make_shard_and_gather_fns(
    mesh: Mesh, partition_specs: Any
) -> tuple[Any, Any]:
    """Pytrees of per-leaf shard (host -> mesh) and gather (mesh -> host
    numpy) callables from a pytree of PartitionSpecs.

    Gather is a plain ``np.asarray``: every serving mesh here is fully
    addressable (one process), so the conversion materializes the global
    array — giving byte-identical host trees regardless of device count
    (what :func:`params_fingerprint` relies on)."""

    def make_shard(spec: P):
        sh = NamedSharding(mesh, spec)
        return lambda leaf: jax.device_put(leaf, sh)

    def make_gather(_spec: P):
        return lambda leaf: np.asarray(leaf)

    shard_fns = jax.tree.map(make_shard, partition_specs,
                             is_leaf=lambda x: isinstance(x, P))
    gather_fns = jax.tree.map(make_gather, partition_specs,
                              is_leaf=lambda x: isinstance(x, P))
    return shard_fns, gather_fns


def gather_params(params: Any) -> Any:
    """Fully-gathered host copy of a (possibly sharded) param tree.
    Floating dtypes are preserved — this is the byte-identity surface
    checkpoints and fingerprints read."""
    return jax.tree.map(lambda a: np.asarray(a), params)


def params_fingerprint(params: Any) -> str:
    """sha256 hex over the fully-gathered param bytes.

    Leaves hash in sorted-path order, each framed with its path, dtype
    and shape, so the digest is invariant to device count and sharding
    layout but NOT to a renamed/reshaped/retyped leaf. This is the
    checkpoint-lineage hash (lifecycle/versions.py): the same champion
    restored on a 1-chip laptop and an 8-chip mesh must audit as the
    same bytes."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    h = hashlib.sha256()
    for path, leaf in sorted(leaves, key=lambda pl: _path_str(pl[0])):
        a = np.asarray(leaf)
        h.update(_path_str(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# -- publish barrier ---------------------------------------------------------

class PublishGate:
    """Context manager a sharded scorer's ``swap_params`` enters: pauses
    the router pool (the existing group-wide batch-boundary barrier) for
    the duration of the publish, so no worker's in-flight sharded
    dispatch interleaves with the param re-layout.

    ``barrier`` is anything with ``pause(timeout_s) -> bool`` /
    ``resume()`` (Router and ParallelRouter both). A pause that times out
    (e.g. a wedged dispatch the watchdog is about to kill) does NOT block
    the publish — the scorer's double buffering keeps an interleaved swap
    safe, the barrier is what makes it *quiescent*; the timeout keeps a
    sick pool from deadlocking a rollback. The hold is ALWAYS released on
    exit once a pause was requested, ack or no ack — ``pause()`` takes
    its holders before awaiting acks, and an un-resumed hold would park
    every worker at its next batch boundary forever (the same
    resume-in-finally contract runtime/recovery.py keeps). Re-entrant so
    a respawn that swaps inside an outer publish doesn't self-deadlock."""

    def __init__(self, barrier: Any, timeout_s: float = 10.0,
                 c_publishes: Any = None, c_timeouts: Any = None):
        self.barrier = barrier
        self.timeout_s = float(timeout_s)
        self._local = threading.local()
        self.publishes = 0
        self.pause_timeouts = 0
        # optional prom counters (the operator passes its mesh registry's)
        self._c_publishes = c_publishes
        self._c_timeouts = c_timeouts

    def __enter__(self) -> "PublishGate":
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        self._local.requested = getattr(self._local, "requested", False)
        if depth == 0:
            self.publishes += 1
            if self._c_publishes is not None:
                self._c_publishes.inc()
            acked = False
            self._local.requested = True
            try:
                acked = bool(self.barrier.pause(self.timeout_s))
            except Exception:  # noqa: BLE001 - a dead pool must not block
                pass  # the publish (resume() on exit is defensive)
            if not acked:
                self.pause_timeouts += 1
                if self._c_timeouts is not None:
                    self._c_timeouts.inc()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._local.depth = depth = self._local.depth - 1
        if depth == 0 and self._local.requested:
            # release the hold even when the ack never arrived: pause()
            # takes its holders BEFORE awaiting acks, and a leaked hold
            # parks every worker at its next batch boundary forever
            self._local.requested = False
            try:
                self.barrier.resume()
            except Exception:  # noqa: BLE001
                pass


# -- partitioners ------------------------------------------------------------

class Partitioner:
    """Shared surface: mesh + layout + shard/gather + the publish path.

    Subclasses decide the PARAM layout; batches always shard over the
    data axis and per-row outputs come back data-sharded (never gathered
    onto one chip before D2H)."""

    def __init__(self, mesh: Mesh, data_axis: str = DATA_AXIS,
                 layout: SpecLayout | None = None):
        if data_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh {mesh.axis_names} has no axis {data_axis!r}")
        self.mesh = mesh
        self.data_axis = data_axis
        self.layout = layout or SpecLayout(data_axis=data_axis)
        self.batch_sharding = NamedSharding(mesh, self.layout.batch())
        self.out_sharding = NamedSharding(mesh, self.layout.rows())
        self.replicated = NamedSharding(mesh, P())
        # swap-vs-dispatch barrier: armed by the operator once the router
        # pool exists (set_barrier); None = publish without quiescing
        self.gate: PublishGate | None = None

    # - layout ---------------------------------------------------------------
    @property
    def data_size(self) -> int:
        return int(self.mesh.shape[self.data_axis])

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    def round_batch(self, b: int) -> int:
        """Smallest multiple of the data-axis size covering ``b`` — every
        bucket must split evenly over the data axis."""
        d = self.data_size
        return -(-int(b) // d) * d

    def param_specs(self, params: Any) -> Any:
        raise NotImplementedError

    def param_sharding(self, params: Any) -> Any:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.param_specs(params),
            is_leaf=lambda x: isinstance(x, P))

    # - shard / gather -------------------------------------------------------
    def shard_params(self, params: Any) -> Any:
        shard_fns, _ = make_shard_and_gather_fns(
            self.mesh, self.param_specs(params))
        return jax.tree.map(lambda fn, leaf: fn(leaf), shard_fns, params)

    def gather(self, params: Any) -> Any:
        return gather_params(params)

    def shard_batch(self, batch: Any) -> jax.Array:
        return jax.device_put(batch, self.batch_sharding)

    # - jit entry points -----------------------------------------------------
    def train_state_specs(self, state: Any) -> Any:
        """Shardings for an ``init_state``-shaped {params, opt_state,
        step} tree: params per the subclass layout, optimizer momentum
        sharded like its params, counters replicated."""
        pspec = self.param_specs(state["params"])
        ptree = jax.tree.structure(state["params"])

        def is_param_like(node: Any) -> bool:
            try:
                return jax.tree.structure(node) == ptree
            except TypeError:  # pragma: no cover
                return False

        opt = jax.tree.map(
            lambda node: pspec if is_param_like(node) else P(),
            state["opt_state"], is_leaf=is_param_like)
        return {"params": pspec, "opt_state": opt, "step": P()}

    def train_state_sharding(self, state: Any) -> Any:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.train_state_specs(state),
            is_leaf=lambda x: isinstance(x, P))

    def partition_train_step(
        self, step: Callable[..., Any], state: Any
    ) -> Callable[..., Any]:
        """Jit the ``(state, x, y) -> (state, loss)`` step with explicit
        shardings and DONATED state buffers — the whole step is one SPMD
        executable, state never round-trips through host."""
        sh = self.train_state_sharding(state)
        return jax.jit(
            step,
            in_shardings=(sh, self.batch_sharding, self.out_sharding),
            out_shardings=(sh, self.replicated),
            donate_argnums=(0,),
        )

    # - publish path ---------------------------------------------------------
    def set_barrier(self, barrier: Any, timeout_s: float = 10.0,
                    registry: Any = None) -> None:
        """Arm the swap-vs-dispatch barrier (the router pool's group
        pause). Idempotent re-arming follows the newest pool (crash
        recovery swaps router incarnations). With a ``registry`` the
        gate's publish/timeout tallies also export as prom counters
        (the Device board's Mesh row)."""
        if barrier is None:
            self.gate = None
            return
        c_pub = c_to = None
        if registry is not None:
            c_pub = registry.counter(
                "ccfd_mesh_publishes_total",
                "sharded param publishes through the pause-barrier gate")
            c_to = registry.counter(
                "ccfd_mesh_publish_pause_timeouts_total",
                "publishes whose router-pool pause timed out (published "
                "anyway under double buffering; the pool was not "
                "quiescent)")
        self.gate = PublishGate(barrier, timeout_s,
                                c_publishes=c_pub, c_timeouts=c_to)


class DataParallelPartitioner(Partitioner):
    """Pure data parallelism: params replicate, batches shard over
    ``data``. The serving default — for the tabular CCFD models the data
    axis does nearly all the work (the reference's "more replicas"
    scaling, one SPMD program instead of N processes)."""

    def param_specs(self, params: Any) -> Any:
        return jax.tree.map(lambda _: P(), params)


class SPMDPartitioner(Partitioner):
    """Rule-driven SPMD: params shard per a regex rule table
    (:func:`match_partition_rules`), batches over ``data``. The wide-
    model escape hatch — fsdp/tp columns per the :class:`SpecLayout`
    vocabulary; XLA's partitioner chooses the collective schedule."""

    def __init__(self, mesh: Mesh, rules: Sequence[tuple[str, P]],
                 data_axis: str = DATA_AXIS,
                 layout: SpecLayout | None = None):
        super().__init__(mesh, data_axis=data_axis, layout=layout)
        self.rules = list(rules)

    def param_specs(self, params: Any) -> Any:
        return match_partition_rules(self.rules, params)


def partitioner_from_config(
    mesh: Mesh,
    param_partition: str = "replicated",
    model: str = "mlp",
) -> Partitioner:
    """CR/env -> partitioner: ``replicated`` (data parallel) or ``rules``
    (the family's stock rule table over fsdp/tp)."""
    if param_partition in ("replicated", "data"):
        return DataParallelPartitioner(mesh)
    if param_partition in ("rules", "spmd"):
        layout = SpecLayout()
        table = (seq_rules(layout) if model.startswith("seq")
                 else mlp_rules(layout))
        return SPMDPartitioner(mesh, table, layout=layout)
    raise ValueError(
        f"unknown param_partition {param_partition!r} "
        "(expected replicated|rules)")
