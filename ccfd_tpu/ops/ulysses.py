"""Ulysses-style all-to-all sequence parallelism: the second long-context
strategy next to ring attention (ops/ring_attention.py).

The reference has no sequence dimension (SURVEY.md §5 "long-context: N/A"),
but this framework treats long-context as first-class. Two exact-attention
shardings over per-customer transaction histories, chosen by regime:

- **Ring** (ring_attention): K/V shards rotate around the mesh axis with
  ``ppermute`` (neighbor ICI hops), online-softmax accumulation. Peak
  memory O(L_local) per device; n_devices pipeline steps. The choice for
  EXTREME sequence lengths.
- **Ulysses** (this module): two ``all_to_all`` reshards. The sequence
  axis is traded for the head axis — each device goes from holding all
  heads of its L/n sequence shard to holding H/n heads of the FULL
  sequence — then attention runs locally as ONE dense einsum (best MXU
  utilization, no scan), and a reverse all-to-all restores the sequence
  sharding. Communication is 2 all-to-alls over q/k/v/out instead of n-1
  ppermute rounds; memory holds (B, H/n, L, L) scores, so it is the
  choice when L is moderate and heads are plentiful (H % n == 0).

Both ops share one contract: (B, H, L, D) in and out, sequence axis
sharded over the named mesh axis, non-causal (histories attend
bidirectionally), exact softmax attention (parity-tested against the
single-device reference and each other).
"""

from __future__ import annotations

from functools import partial

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ccfd_tpu.ops.ring_attention import reference_attention


def _ulysses_body(q, k, v, axis_name: str):
    """Per-device program. Local shapes: (B, H, L/n, D) in and out."""
    # resharding all-to-all: scatter heads (axis 1), gather sequence
    # (axis 2) -> (B, H/n, L, D) per device, full sequence locally
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=1, concat_axis=2,
        tiled=True,
    )
    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    # full attention over the device's head group: one dense einsum on
    # the MXU — this is the whole point of trading L-sharding for
    # H-sharding
    oh = reference_attention(qh, kh, vh)
    # reverse reshard: scatter sequence, gather heads -> (B, H, L/n, D)
    return jax.lax.all_to_all(
        oh, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str,
) -> jax.Array:
    """Exact attention with L sharded over ``axis_name``. (B, H, L, D) in/out.

    Requires H and L both divisible by the axis size (the all-to-alls
    redistribute heads across devices and sequence across the local dim).
    """
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(
            f"ulysses_attention needs heads ({q.shape[1]}) divisible by "
            f"mesh axis {axis_name!r} size ({n}); use ring_attention for "
            f"head counts below the axis size"
        )
    if q.shape[2] % n:
        raise ValueError(
            f"sequence length {q.shape[2]} must divide evenly over mesh "
            f"axis {axis_name!r} size ({n})"
        )
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(_ulysses_body, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
