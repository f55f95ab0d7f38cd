"""Compiled TPU scorer: fixed-shape bucketed dispatch + hot-swappable params.

This replaces the reference's Seldon-wrapped CPU model container
(reference deploy/model/modelfull.json:18-52) as the prediction hop. Design
follows the latency plan in SURVEY.md §7 "hard parts":

- **Fixed batch shapes.** XLA compiles one executable per input shape; a
  streaming workload with ragged batch sizes would re-trace constantly. The
  scorer pads every request batch up to a configured bucket
  (CCFD_BATCH_SIZES) so steady state reuses a handful of cached executables.
- **Warmup.** ``warmup()`` runs every bucket once so no request pays the
  compile cost.
- **Double-buffered params.** Online retrain (BASELINE.json configs[4])
  must not pause serving: ``swap_params`` device-puts the new pytree and
  swaps a reference atomically between dispatches — in-flight calls keep the
  old buffers alive, the next call picks up the new ones.
- **Mesh-sharded dispatch.** The reference scales serving by k8s replicas +
  Kafka partitioning (reference deploy/frauddetection_cr.yaml:76,
  router.yaml:32); the TPU analog is ONE scorer whose batch shards over the
  ``"data"`` axis of a ``jax.sharding.Mesh`` (SURVEY.md §7 stage 6).
  ``Scorer(mesh=...)`` keeps the exact same bucketing/warmup/swap surface:
  buckets round up to multiples of the data-axis size, inputs are
  device_put with a NamedSharding so each chip receives only its rows, and
  params ride replicated (default) or megatron-sharded over the ``"model"``
  axis (``param_partition="model"``, layout in ccfd_tpu/parallel/sharding.py).
  The fused Pallas kernel composes via ``shard_map``: every chip runs the
  single-chip kernel on its shard — collectives only appear if the model
  axis is used, and XLA schedules those.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from ccfd_tpu.data.ccfd import NUM_FEATURES
from ccfd_tpu.models.registry import ModelSpec, get_model
from ccfd_tpu.runtime.faults import device_seam

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def _host_cast(a: Any) -> np.ndarray:
    """Host copy of one param leaf for the numpy tier: floating leaves go to
    f32, integer leaves (tree feature indices) keep an integer dtype — a
    uniform f32 cast would turn gather indices into floats and crash
    ``apply_numpy`` for the tree family."""
    a = np.asarray(a)
    if np.issubdtype(a.dtype, np.floating):
        return np.asarray(a, np.float32)
    return a


class Scorer:
    def __init__(
        self,
        model_name: str = "mlp",
        params: Any = None,
        batch_sizes: Sequence[int] = (16, 128, 1024, 4096, 16384),
        compute_dtype: str = "bfloat16",
        num_features: int = NUM_FEATURES,
        seed: int = 0,
        use_fused: bool | None = None,
        mesh: Any = None,
        param_partition: str = "replicated",
        host_tier_rows: int | None = None,
        dispatch_deadline_ms: float | None = None,
        telemetry: Any = None,
        partitioner: Any = None,
    ):
        self.spec: ModelSpec = get_model(model_name)
        self.num_features = num_features
        # first-class partitioning layer (parallel/partition.py): when
        # given, the partitioner owns every sharding decision — batch over
        # its data axis, params per its layout (replicated or rule-table
        # SPMD), and param publishes route through its pause-barrier
        # publish path. The bare ``mesh=`` form keeps the historical
        # hand-rolled layout (the dryrun's shape).
        self.partitioner = partitioner
        if partitioner is not None:
            mesh = partitioner.mesh
        self.mesh = mesh
        # device telemetry plane (observability/device.py): when armed,
        # every staging put on the dispatch path is timed + byte-counted
        # (ccfd_h2d_bytes_total / ccfd_h2d_seconds — the measured numbers
        # the BudgetLedger's h2d layer reads). None resolves through the
        # module default so harnesses arm scorers built deep
        # inside helpers; the operator passes its instance explicitly.
        if telemetry is None:
            from ccfd_tpu.observability import device as _device

            telemetry = _device.get_default()
        self.telemetry = telemetry
        if param_partition not in ("replicated", "model"):
            raise ValueError(f"unknown param_partition {param_partition!r}")
        if param_partition == "model" and model_name != "mlp":
            # a silent fallback to replication would hand a caller who needs
            # the sharded layout (model too big replicated) an OOM later
            raise ValueError(
                f"param_partition='model' has a layout only for 'mlp', "
                f"not {model_name!r}"
            )
        self._param_partition = param_partition
        self._batch_sharding = None
        self._param_sharding = None
        if partitioner is not None:
            self._data_size = partitioner.data_size
            batch_sizes = {partitioner.round_batch(b) for b in batch_sizes}
            self._batch_sharding = partitioner.batch_sharding
            self._out_sharding = partitioner.out_sharding
        elif mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ccfd_tpu.parallel.mesh import DATA_AXIS

            self._data_size = mesh.shape[DATA_AXIS]
            # every bucket must split evenly over the data axis
            batch_sizes = {
                -(-b // self._data_size) * self._data_size for b in batch_sizes
            }
            self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
            self._out_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self.batch_sizes = tuple(sorted(batch_sizes))
        self._params = params if params is not None else self.spec.init(
            jax.random.PRNGKey(seed)
        )
        if partitioner is not None:
            self._param_sharding = partitioner.param_sharding(self._params)
            self._params = jax.device_put(self._params, self._param_sharding)
        elif mesh is not None:
            from ccfd_tpu.parallel import sharding as shardlib

            if param_partition == "model":
                self._param_sharding = shardlib.mlp_param_spec(self._params, mesh)
            else:
                rep = shardlib.replicated(mesh)
                self._param_sharding = jax.tree.map(lambda _: rep, self._params)
            self._params = jax.device_put(self._params, self._param_sharding)
        else:
            self._params = jax.device_put(self._params)
        # swap-vs-dispatch publish gate (parallel/partition.py
        # PublishGate): armed by the operator once the router pool exists;
        # every swap_params then quiesces the pool's in-flight sharded
        # dispatches at a batch boundary before re-laying params
        self._swap_gate: Any = None
        self._lock = threading.Lock()
        # per-bucket dispatch tally for the executable inventory (PR 10):
        # on a mesh every dispatch is one SPMD launch spanning all
        # devices, so per-device counts read straight off this grid
        self._dispatch_counts: dict[int, int] = {}
        dtype = _DTYPES.get(compute_dtype, jnp.float32)
        # models without a dtype knob (e.g. trees) take (params, x) only
        import inspect

        sig = inspect.signature(self.spec.apply)
        if "compute_dtype" in sig.parameters:
            self._apply = lambda p, x: self.spec.apply(p, x, compute_dtype=dtype)
        else:
            self._apply = self.spec.apply
        if mesh is not None:
            # constrain the output to stay data-sharded: the partitioner
            # must not all-gather probabilities onto one chip before D2H
            self._apply = jax.jit(self._apply, out_shardings=self._out_sharding)

        # Pallas fused path: the whole MLP in one kernel, weights VMEM-
        # resident (ccfd_tpu/ops/fused_mlp.py). Auto-on for the flagship MLP
        # in reduced precision; params are re-folded on every swap so online
        # retrain keeps working. ``use_fused=False`` forces the XLA path.
        self._fused_params = None
        self._preq_norm = None
        self._preq_wire = False
        if use_fused is None:
            # auto only on real TPU: the CPU interpreter runs the same kernel
            # body but orders of magnitude slower (tests opt in explicitly).
            # mlp_q8 has its own int8 kernel (ops/fused_mlp_q8.py) whose
            # compute precision is fixed by quantization, so no dtype gate.
            use_fused = jax.default_backend() == "tpu" and (
                (self.spec.name == "mlp" and dtype == jnp.bfloat16)
                or self.spec.name == "mlp_q8"
            )
        # Host tier: an EXPLICIT ``host_tier_rows`` > 0 scores request
        # batches of at most that many rows on a host copy of the params
        # in plain numpy, never reaching the device. Auto (None) is 0 on
        # every backend — the serving path the tests exercise on the CPU
        # is the path that runs on the chip, and nothing on the host
        # stands in for the device unless an operator asks for it.
        # Numerical note: the host tier computes f32, the device path
        # bf16 — within ~1e-2 in probability (asserted by tests).
        self.host_tier_rows = int(host_tier_rows or 0)
        self._host_params = None
        # swap listeners: components holding a derived copy of the params
        # (e.g. the C++ serving front's in-process host model) register to
        # be re-fed on every swap_params so online retrain reaches them too.
        # Delivery is serialized under _notify_lock and ordered by a swap
        # generation so two concurrent swap_params calls can't install their
        # listeners' copies in reverse order (stale params winning).
        self._swap_listeners: list[Any] = []
        self._notify_lock = threading.Lock()
        self._swap_gen = 0
        self._swap_delivered_gen = 0
        # prepublish hooks: planes that compile executables against the
        # params (the fused decision grid) precompile against the STAGED
        # buffers here, before the flip — so the swap publishes with every
        # bucket warm, exactly like the seq variant swap. Hooks run gate-
        # free (staging side); a failing hook never blocks the publish.
        self._prepublish_hooks: list[Any] = []
        # host materializations per score_pipelined call site: the staged
        # path pays one np.asarray(done) sync per chunk; the fused path
        # counts the same under the same name (serving/fused.py).
        self.host_syncs = 0
        # challenger slot (lifecycle/shadow.py): a second, double-buffered
        # (version, host_params) pair living NEXT TO the champion — shadow
        # and canary scoring read it via the host numpy forward, so the
        # challenger never contends for the device. Installed/cleared by
        # the lifecycle controller; swap_params does not touch it.
        self._challenger: tuple[int, Any] | None = None
        # Dispatch deadline (server-side SELDON_TIMEOUT analog,
        # /root/reference/README.md:386-393): the serving ``score`` path
        # bounds its device round trip; a device sync that never returns
        # times out, marks the device wedged, and serving continues on the
        # host forward until a probe sees recovery.
        # None = auto: SELDON_TIMEOUT ms on accelerator backends, off on CPU
        # and on meshes (the dryrun/virtual path).
        if dispatch_deadline_ms is None:
            if mesh is None and jax.default_backend() not in ("cpu",):
                from ccfd_tpu.config import Config

                # env-backed Config is the single parser for both knobs;
                # callers holding a programmatic Config pass
                # cfg.scorer_dispatch_deadline_ms() instead of None
                dispatch_deadline_ms = Config.from_env().scorer_dispatch_deadline_ms()
            else:
                dispatch_deadline_ms = 0.0
        self.dispatch_deadline_s = float(dispatch_deadline_ms) / 1e3
        self._dispatcher = None
        self._wedge = None
        self.dispatch_timeouts = 0
        self.host_fallback_scores = 0
        # Host params are kept whenever the family has a host forward: an
        # explicit host tier routes by host_tier_rows, the wedge fallback
        # needs them armed BEFORE a wedge (they cannot be pulled from a
        # hung device later), and the C++ front's in-IO-thread model
        # derives its copy from them. One numpy copy of the params;
        # refreshed on every swap.
        if self.spec.apply_numpy is not None:
            self._host_params = jax.tree.map(
                _host_cast, params if params is not None else self._params
            )
        if self.host_tier_rows > 0 and self._host_params is None:
            self.host_tier_rows = 0
        if self.dispatch_deadline_s > 0:
            from ccfd_tpu.serving.dispatch import DeviceDispatcher, WedgeMonitor

            self._dispatcher = DeviceDispatcher()
            probe_rows = min(self.batch_sizes)
            probe_x = np.zeros((probe_rows, self.num_features), np.float32)
            self._wedge = WedgeMonitor(
                self._dispatcher,
                lambda: self.score_pipelined(probe_x, depth=1),
                deadline_s=self.dispatch_deadline_s,
            )
        if use_fused:
            if self.spec.name == "mlp_q8":
                from ccfd_tpu.ops import fused_mlp_q8 as fused_mod
            else:
                from ccfd_tpu.ops import fused_mlp as fused_mod

            self._fused_mod = fused_mod
            # wire dtype is the kernel's call: bf16 halves H2D bytes for
            # the bf16 kernel; the q8 kernel keeps f32 for exact parity
            # with the served XLA graph (its docstring has the numbers)
            self._fused_in_dtype = (
                ml_dtypes.bfloat16
                if fused_mod.INPUT_DTYPE == "bfloat16" else np.float32
            )
            try:
                folded = fused_mod.fold_for_kernel(self._params)
                self._fused_params = self._put_fused(folded)
                self._preq_norm = self._preq_norm_of(folded)
            except (KeyError, TypeError, ValueError):
                self._fused_params = None  # incompatible layout: XLA path
            self._fused_interpret = jax.default_backend() == "cpu"
            self._fused_sharded_cache: dict[int, Any] = {}
            # int8 wire (q8 kernel, single device): on by default — the
            # math is bit-identical and only the H2D bytes change;
            # CCFD_Q8_WIRE=f32 opts out (e.g. when the serving host's CPU,
            # not the wire, is the bottleneck). Mesh serving keeps the
            # f32 wire: the preq arrays would need their own shard_map
            # composition, unwarranted before a multi-chip number exists.
            # static capability/env flag only: whether CURRENT params
            # fold is the dynamic `preq_norm is not None` check at
            # dispatch, so a later foldable swap re-enables the wire
            self._preq_wire = (
                hasattr(fused_mod, "prequantize_rows_numpy")
                and os.environ.get("CCFD_Q8_WIRE", "int8") != "f32"
            )

    @staticmethod
    def _preq_norm_of(folded: Any) -> dict | None:
        """Host copies of the folded normalizer for the int8 wire's
        host-side requantization — the SAME arrays the kernel normalizes
        with, so there is no second zero-sigma guard to drift."""
        if not isinstance(folded, dict) or "sigma" not in folded:
            return None
        return {"mu": np.asarray(folded["mu"]),
                "sigma": np.asarray(folded["sigma"])}

    def _put_fused(self, folded: Any) -> Any:
        """Fused weights live whole in every chip's VMEM: replicate on mesh."""
        if self.mesh is None:
            return folded
        from ccfd_tpu.parallel.sharding import replicated

        return jax.device_put(folded, replicated(self.mesh))

    def _put_batch(self, chunk: np.ndarray) -> jax.Array:
        """H2D with placement: on a mesh each chip gets only its row shard.
        With the device telemetry plane armed the put is timed and byte-
        counted (the measured H2D accounting; two perf_counter reads).
        The staging seam consults the device-fault plan (runtime/faults.py
        ``put_fail``) INSIDE the put, so an injected staging failure rides
        the same path — and the same telemetry failure count — a real one
        would."""
        if self._batch_sharding is None:
            def put():
                device_seam("put")
                return jnp.asarray(chunk)
        else:
            def put():
                device_seam("put")
                return jax.device_put(chunk, self._batch_sharding)
        if self.telemetry is None:
            return put()
        from ccfd_tpu.observability.device import timed_put

        return timed_put(self.telemetry, chunk.nbytes, put)

    def _fused_apply(self, fused_params: Any, x: jax.Array) -> jax.Array:
        rows = x.shape[0] if self.mesh is None else x.shape[0] // self._data_size
        tile = self._fused_mod.fit_tile(rows)
        if self.mesh is None:
            return self._fused_mod.fused_score(
                fused_params, x, tile=tile, interpret=self._fused_interpret
            )
        return self._fused_sharded(tile)(fused_params, x)

    _PREQ_LIVE = object()  # sentinel: "read the live grid", distinct from
    # an explicit None snapshot (a non-preq model's locked snapshot) — the
    # live fallback on None would pair a concurrently-swapped preq grid
    # with the snapshot's old kernel weights

    def _fused_dispatch(self, fused_params: Any, chunk: np.ndarray,
                        preq_norm: Any = _PREQ_LIVE) -> Any:
        """Host chunk -> device probabilities through the active fused
        path. The int8 WIRE mode (q8 kernel, single device): the host runs
        the model's OWN first requantization (prequantize_rows_numpy) and
        ships 34 B/row instead of 120 — bit-identical math, the H2D
        transfer is what changes. Everything else ships rows in the
        kernel's wire dtype (bf16 for the bf16 kernel, f32 for q8).
        ``preq_norm`` must be snapshotted together with ``fused_params``
        when a concurrent swap is possible (pass the snapshot even when
        it is None — only the default reads the live grid)."""
        if preq_norm is Scorer._PREQ_LIVE:
            preq_norm = self._preq_norm
        if self._preq_wire and preq_norm is not None and self.mesh is None:
            q, s = self._fused_mod.prequantize_rows_numpy(preq_norm, chunk)
            tile = self._fused_mod.fit_tile(q.shape[0])
            if self.telemetry is None:
                qd, sd = jnp.asarray(q), jnp.asarray(s)
            else:
                from ccfd_tpu.observability.device import timed_put

                # the int8 wire's whole point is fewer H2D bytes — count
                # the bytes actually shipped, not the f32 equivalent
                qd = timed_put(self.telemetry, q.nbytes,
                               lambda: jnp.asarray(q))
                sd = timed_put(self.telemetry, s.nbytes,
                               lambda: jnp.asarray(s))
            return self._fused_mod.fused_mlp_q8_score_preq(
                fused_params, qd, sd, tile=tile,
                interpret=self._fused_interpret,
            )
        return self._fused_apply(
            fused_params,
            self._put_batch(chunk.astype(self._fused_in_dtype, copy=False)),
        )

    def _fused_sharded(self, tile: int) -> Any:
        """SPMD composition of the single-chip Pallas kernel: ``shard_map``
        over the data axis runs the kernel on each chip's row shard with the
        full (replicated) weights — the TPU-native form of the reference's
        "more replicas" scaling (reference deploy/frauddetection_cr.yaml:76).
        Cached per tile so each bucket compiles once."""
        fn = self._fused_sharded_cache.get(tile)
        if fn is None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from ccfd_tpu.parallel.mesh import DATA_AXIS

            def per_chip(p, xs):
                return self._fused_mod.fused_score(
                    p, xs, tile=tile, interpret=self._fused_interpret
                )

            fn = jax.jit(
                shard_map(
                    per_chip,
                    mesh=self.mesh,
                    in_specs=(P(), P(DATA_AXIS, None)),
                    out_specs=P(DATA_AXIS),
                    # pallas_call emits ShapeDtypeStructs without a vma
                    # annotation; the kernel is elementwise-per-shard, so
                    # the varying-across-mesh check adds nothing here
                    check_vma=False,
                )
            )
            self._fused_sharded_cache[tile] = fn
        return fn

    @property
    def params(self) -> Any:
        return self._params

    def bucket(self, n: int) -> int:
        for b in self.batch_sizes:
            if n <= b:
                return b
        return self.batch_sizes[-1]

    @property
    def fused(self) -> bool:
        return self._fused_params is not None

    def executable_grid(self) -> dict:
        """The compiled-executable set this scorer serves from — the row
        family's entry in the device telemetry plane's inventory (the seq
        family reports its (L, B) grid the same way)."""
        with self._lock:  # a first-dispatch of a new bucket inserts a
            # key; an unlocked scrape-iteration would race the resize
            counts = dict(self._dispatch_counts)
        out = {
            "model": self.spec.name,
            "batch_sizes": list(self.batch_sizes),
            "fused": self.fused,
            "int8_wire": bool(self._preq_wire
                              and self._preq_norm is not None),
            "host_tier_rows": self.host_tier_rows,
            "dispatches": {str(b): int(n)
                           for b, n in sorted(counts.items())},
        }
        if self.mesh is not None:
            out["mesh_devices"] = int(self.mesh.size)
            out["mesh_axes"] = {str(a): int(s)
                                for a, s in self.mesh.shape.items()}
        return out

    def warmup(self) -> None:
        """Compile every bucket through the serving dispatch path.

        Anything that goes wrong here raises: a fused kernel the installed
        compiler refuses is a bug to fix in the kernel (the chip run is
        the CI for it), and a warm-up that outlives
        ``CCFD_WARMUP_DEADLINE_S`` (default 180 s, enforced when the
        dispatch guard is on) means the device is not usable — serving
        does not start on the host in its place."""
        from ccfd_tpu.observability.profile import compile_stage

        def body() -> None:
            # compile attribution: warmup compiles the whole bucket grid;
            # the label rides the contextvar on whichever thread runs it
            with compile_stage("scorer.warmup"):
                self._warmup_body()

        if self._dispatcher is None:
            body()
            return
        budget_s = float(os.environ.get("CCFD_WARMUP_DEADLINE_S", "180"))
        self._dispatcher.call(body, budget_s)  # ScorerTimeout propagates

    def _warmup_body(self) -> None:
        zeros = np.zeros((max(self.batch_sizes), self.num_features),
                         np.float32)
        from ccfd_tpu.observability.profile import billed

        for b in self.batch_sizes:
            # the finer name under ``scorer.warmup``: what JAX traced,
            # lowered, compiled or loaded for this bucket's executable
            with billed("startup.executable", b_bucket=int(b)):
                if self._fused_params is not None:
                    # through _fused_dispatch so the SERVING wire path
                    # (incl. the q8 int8 wire) is what compiles here
                    out = self._fused_dispatch(self._fused_params,
                                               zeros[:b])
                else:
                    out = self._apply(self._params,
                                      self._put_batch(zeros[:b]))
                jax.block_until_ready(out)

    def set_swap_gate(self, gate: Any) -> None:
        """Arm the partitioner's publish gate: every ``swap_params`` then
        pauses the router pool at a batch boundary first, so no worker's
        in-flight SPMD dispatch interleaves with the sharded re-layout
        (parallel/partition.py PublishGate; None disarms)."""
        self._swap_gate = gate

    def swap_params(self, new_params: Any) -> None:
        """Atomically publish retrained params without pausing serving.

        All staging (host gather, sharded H2D re-layout, fused fold, host
        casts) happens BEFORE the publish gate: double buffering keeps an
        in-flight dispatch safe against new buffers landing, so only the
        reference flip needs the router pool quiescent — a gated swap
        pauses the pool for a pointer swap, not a tree transfer."""
        staged = self._stage_swap(new_params)
        # prepublish: let dependent planes (fused decision grid) precompile
        # against the staged buffers BEFORE the gate/flip, so the first
        # serving dispatch after publish finds every bucket warm. Still on
        # the staging side — a slow or failing hook delays the publish, but
        # never pauses the pool and never blocks the flip itself.
        for hook in list(self._prepublish_hooks):
            try:
                hook(*staged)
            except Exception:  # noqa: BLE001 - must not break swaps
                logging.getLogger("ccfd_tpu.scorer").warning(
                    "prepublish hook %r raised; first serving dispatch "
                    "after this swap may pay its compile", hook,
                    exc_info=True)
        gate = self._swap_gate
        if gate is None:
            listeners, gen = self._commit_swap(*staged)
        else:
            with gate:
                listeners, gen = self._commit_swap(*staged)
        # listener delivery runs OUTSIDE the gate and the params lock
        # (listeners may be slow; the pool must not stay paused for them)
        self._notify_swap(new_params, staged[3], listeners, gen)

    def _stage_swap(self, new_params: Any) -> tuple:
        """Gate-free staging: every buffer the flip will install, built
        and device-committed up front.

        Copies into fresh buffers: ``device_put`` on already-committed arrays
        is an aliasing no-op, and aliased buffers would be deleted under us
        when the trainer's next donated step consumes its argument.
        """
        if self._param_sharding is not None:
            # re-lay the fresh tree onto the mesh with the serving sharding
            staged = jax.device_put(
                jax.tree.map(lambda a: np.array(a), new_params),
                self._param_sharding,
            )
        else:
            staged = jax.tree.map(lambda a: jnp.array(a, copy=True), new_params)
        jax.block_until_ready(staged)
        staged_fused = None
        staged_preq_norm = None
        # gate on the fused MODULE, not the current fused params: one
        # unfoldable swap drops to the XLA path, but a later foldable tree
        # must re-enable the kernel
        if getattr(self, "_fused_mod", None) is not None:
            try:
                folded = self._fused_mod.fold_for_kernel(staged)
                staged_fused = self._put_fused(folded)
                staged_preq_norm = self._preq_norm_of(folded)
                jax.block_until_ready(staged_fused)
            except (KeyError, TypeError, ValueError):
                staged_fused = None  # incompatible layout: drop to XLA path
                staged_preq_norm = None
        staged_host = None
        if self._host_params is not None:
            staged_host = jax.tree.map(_host_cast, new_params)
        return staged, staged_fused, staged_preq_norm, staged_host

    def _commit_swap(self, staged: Any, staged_fused: Any,
                     staged_preq_norm: Any, staged_host: Any
                     ) -> tuple[list, int]:
        """The flip: swap the serving references under the lock (the only
        part a publish gate quiesces the pool for)."""
        with self._lock:
            self._params = staged
            # never keep serving stale fused weights: an unfoldable tree
            # disables the fused path rather than pinning the old params
            self._fused_params = staged_fused
            if staged_fused is not None:
                # the int8 wire quantizes against the CURRENT normalizer;
                # a stale one would ship rows quantized on the old grid
                self._preq_norm = staged_preq_norm
            if staged_host is not None:
                self._host_params = staged_host
            listeners = list(self._swap_listeners)
            self._swap_gen += 1
            return listeners, self._swap_gen

    def _notify_swap(self, new_params: Any, staged_host: Any,
                     listeners: list, gen: int) -> None:
        if not listeners:
            return
        host_tree = (
            staged_host
            if staged_host is not None
            else jax.tree.map(_host_cast, new_params)
        )
        # outside the params lock (listeners may be slow), but serialized
        # and generation-checked: if a newer swap already delivered, this
        # older tree must not overwrite the listeners' copies
        with self._notify_lock:
            if gen <= self._swap_delivered_gen:
                return
            self._swap_delivered_gen = gen
            for fn in listeners:
                try:
                    fn(host_tree)
                except Exception:  # noqa: BLE001 - must not break swaps
                    # a listener that can't take the new tree (the shadow
                    # tap, the native host model) is now serving STALE
                    # params — that must be visible, not silent
                    logging.getLogger("ccfd_tpu.scorer").warning(
                        "swap listener %r raised; it may be serving stale "
                        "params", fn, exc_info=True)

    def add_prepublish_hook(self, fn: Any) -> None:
        """``fn(staged, staged_fused, staged_preq_norm, staged_host)`` runs
        inside every ``swap_params`` AFTER staging and BEFORE the publish
        gate/flip — the seam where the fused decision plane precompiles its
        (L, B) executable grid against the incoming params so the swap
        publishes warm. Hook errors are logged, never propagated."""
        with self._lock:
            self._prepublish_hooks.append(fn)

    def add_swap_listener(self, fn: Any) -> None:
        """``fn(host_params_numpy_tree)`` runs after every ``swap_params``."""
        with self._lock:
            self._swap_listeners.append(fn)

    def remove_swap_listener(self, fn: Any) -> None:
        with self._lock:
            if fn in self._swap_listeners:
                self._swap_listeners.remove(fn)

    # -- challenger slot (model lifecycle: shadow/canary scoring) ----------
    def install_challenger(self, version: int, params: Any) -> None:
        """Stage a challenger's host-params copy beside the champion.

        Double-buffered like ``swap_params``: the host cast happens into
        fresh buffers before the reference swaps under the lock, so an
        in-flight ``challenger_score`` keeps the old tree alive and the
        next call sees the new one. Requires a numpy host forward — the
        whole point of the slot is scoring off the device's critical path.
        """
        if self.spec.apply_numpy is None:
            raise RuntimeError(
                f"model {self.spec.name!r} has no host forward; the "
                f"challenger slot scores on the host by design")
        staged = jax.tree.map(_host_cast, params)
        with self._lock:
            self._challenger = (int(version), staged)

    def clear_challenger(self, version: int | None = None) -> None:
        """Remove the challenger; with ``version`` given, only that one
        (a stale clear must not evict a newer candidate)."""
        with self._lock:
            if (self._challenger is not None
                    and (version is None
                         or self._challenger[0] == int(version))):
                self._challenger = None

    @property
    def challenger_version(self) -> int | None:
        ch = self._challenger
        return ch[0] if ch is not None else None

    def challenger_score(self, x: np.ndarray) -> np.ndarray:
        """(n, F) -> (n,) proba_1 on the challenger slot's host params —
        no device round trip, never touches the champion path."""
        ch = self._challenger
        if ch is None:
            raise RuntimeError("no challenger installed")
        return np.asarray(
            self.spec.apply_numpy(ch[1], np.asarray(x, np.float32)),
            np.float32,
        )

    def score_pipelined(self, x: np.ndarray, depth: int = 2) -> np.ndarray:
        """Bulk scoring with ``depth`` dispatches in flight.

        JAX dispatch is async: by enqueuing the next chunk's H2D + kernel
        before blocking on the previous chunk's D2H, transfer and compute
        overlap. Wins when the host<->device wire dominates (large offline
        scoring runs); the synchronous ``score`` stays the latency path.
        """
        x = np.asarray(x, dtype=np.float32)
        n = x.shape[0]
        if n == 0:
            return np.zeros((0,), np.float32)
        with self._lock:
            params = self._params
            fused_params = self._fused_params
            preq_norm = self._preq_norm  # same snapshot as the weights: a
            # concurrent swap must not pair a new quantization grid with
            # the old kernel weights
        largest = self.batch_sizes[-1]
        pending: list[tuple[jax.Array, int]] = []
        chunks: list[np.ndarray] = []
        start = 0
        while start < n:
            take = min(n - start, largest)
            b = self.bucket(take)
            chunk = x[start : start + take]
            if take < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - take, x.shape[1]), np.float32)]
                )
            # device-fault dispatch seam (runtime/faults.py): device_hang
            # stalls this dispatch past its watchdog, compile_stall bills
            # a synthetic re-trace — the taxonomy the heal ladder drills
            device_seam("dispatch")
            with self._lock:  # router workers share this scorer: the
                # read-modify-write must not lose increments
                self._dispatch_counts[b] = self._dispatch_counts.get(b, 0) + 1
            if fused_params is not None:
                # a failure here raises to the caller (the REST front
                # answers 500, the router's degradation ladder takes the
                # batch): the XLA graph never stands in for a kernel that
                # failed
                out = self._fused_dispatch(fused_params, chunk, preq_norm)
            else:
                out = self._apply(params, self._put_batch(chunk))
            pending.append((out, take))
            if len(pending) >= depth:
                done, took = pending.pop(0)
                self.host_syncs += 1
                chunks.append(np.asarray(done)[:took])
            start += take
        for done, took in pending:
            self.host_syncs += 1
            chunks.append(np.asarray(done)[:took])
        return np.concatenate(chunks).astype(np.float32)

    @property
    def has_host_forward(self) -> bool:
        """True when a numpy host forward (and a host params copy) exists —
        what the router's degraded host tier needs."""
        return self._host_params is not None and self.spec.apply_numpy is not None

    def host_score(self, x: np.ndarray) -> np.ndarray:
        """(n, F) -> (n,) proba_1 on the HOST params copy, no device
        round trip. This is the router degradation ladder's host tier
        (router/router.py): unlike ``score`` — whose own host fallback
        only engages on a wedge — this never touches the device edge, so
        it stays alive when that edge is partitioned or fault-injected."""
        with self._lock:
            host_params = self._host_params
        if host_params is None or self.spec.apply_numpy is None:
            raise RuntimeError(
                f"model {self.spec.name!r} has no host forward")
        return np.asarray(
            self.spec.apply_numpy(host_params, np.asarray(x, np.float32)),
            np.float32,
        )

    def score(self, x: np.ndarray) -> np.ndarray:
        """(n, F) float32 -> (n,) float32 proba_1, padding to a shape bucket.

        The synchronous latency path: one chunk in flight, same
        bucketing/padding as the pipelined bulk path. With an explicit
        ``host_tier_rows`` > 0, batches at or under it take the numpy
        forward instead (off by default).
        """
        x = np.asarray(x, dtype=np.float32)
        if 0 < x.shape[0] <= self.host_tier_rows:
            with self._lock:
                host_params = self._host_params
            return np.asarray(
                self.spec.apply_numpy(host_params, x), np.float32
            )
        if self._dispatcher is None:
            return self.score_pipelined(x, depth=1)
        return self._device_score_deadline(x)

    def _device_score_deadline(self, x: np.ndarray) -> np.ndarray:
        """Device path with a bounded round trip (serving latency path only;
        ``score_pipelined`` called directly — bulk scoring — is unbounded by
        design). Timeout => host fallback at ANY batch size, or
        :class:`~ccfd_tpu.serving.dispatch.ScorerTimeout` for the fronts to
        map to 503 when the model has no host forward."""
        from ccfd_tpu.serving.dispatch import ScorerTimeout

        if not self._wedge.wedged:
            # The deadline is calibrated for one bucketed dispatch; a
            # legitimately huge request scores as ceil(n/largest_bucket)
            # sequential chunks, and a healthy device must not be marked
            # wedged just because the request was big — scale the budget
            # by the chunk count (ADVICE r3).
            n_chunks = max(1, -(-len(x) // max(self.batch_sizes)))
            try:
                return self._dispatcher.call(
                    lambda: self.score_pipelined(x, depth=1),
                    self.dispatch_deadline_s * n_chunks,
                )
            except ScorerTimeout:
                self.dispatch_timeouts += 1
                self._wedge.mark_wedged()
        # wedged (now or already): no new device work queues behind the hang
        with self._lock:
            host_params = self._host_params
        if host_params is None or self.spec.apply_numpy is None:
            raise ScorerTimeout(
                f"device wedged for {self._wedge.wedged_for_s:.1f}s and "
                f"model {self.spec.name!r} has no host forward"
            )
        self.host_fallback_scores += 1
        return np.asarray(self.spec.apply_numpy(host_params, x), np.float32)
