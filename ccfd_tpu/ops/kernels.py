"""The Pallas kernel families of the served history programs, said once.

A family is what a dashboard asks about: a key on ``seq.enqueue`` and in
``SeqScorer.executable_grid()``, a counter beside
``seq_bucket_dispatch_total``, and the ``pallas_call`` names that count as
it. The kernel modules pick their kernel while the program is traced
(``kernel_fits``: shapes, blocks and VMEM are theirs, the rest is composed
from the predicates below) and the model calls them; ``serving/history.py``
loops over :data:`FAMILIES`, reports what :func:`held` reads from the
program's own jaxpr and names no kernel: a new family is a row here.
"""

from __future__ import annotations

import importlib
from typing import Iterator, NamedTuple

import jax
import jax.numpy as jnp


class Family(NamedTuple):
    key: str  # on ``seq.enqueue`` and in ``executable_grid()``
    counter: str  # dispatches of executables that hold the family
    does: str  # the counter's help: what such an executable does ..
    rest: str  # .. and what the others do
    modules: tuple  # of ``ccfd_tpu.ops``: their ``pallas_call`` names count

    @property
    def help(self) -> str:
        return (f"seq dispatches of executables whose {self.does} (beside "
                f"seq_bucket_dispatch_total: the rest {self.rest})")

    @property
    def names(self) -> tuple:
        """The modules' ``KERNEL`` / ``KERNELS``; read when asked, because
        those modules import this one."""
        found = [importlib.import_module(f"{__package__}.{name}")
                 for name in self.modules]
        return sum((getattr(module, "KERNELS", None) or (module.KERNEL,)
                    for module in found), ())


FAMILIES = (
    Family("attn_kernel", "seq_attention_kernel_dispatch_total",
           "attention holds a kernel that keeps the scores on the chip",
           "attended through XLA", ("seq_attention", "causal_attention")),
    Family("expert_kernel", "seq_expert_kernel_dispatch_total",
           "held experts multiply through the grouped-matmul kernels",
           "looped over tiles through XLA, or have no experts",
           ("grouped_experts",)),
    Family("ssd_kernel", "seq_ssd_kernel_dispatch_total",
           "state-space mixers scan through the kernel that keeps a chunk's "
           "decays and the heads' states on the chip",
           "scanned through XLA, or have no such mixer", ("ssd_scan",)),
    Family("kda_kernel", "seq_kda_kernel_dispatch_total",
           "KDA mixers scan through the kernel that keeps a chunk's matrices "
           "and the heads' states on the chip",
           "looped over chunks through XLA, or have no such mixer",
           ("kda_scan",)),
    Family("conv_kernel", "seq_conv_kernel_dispatch_total",
           "state-space mixers convolve through the token-major kernel that "
           "hands the scan kernel x, B and C as it takes them",
           "convolved through XLA, or have no such mixer", ("short_conv",)),
    Family("cca_kernel", "seq_cca_kernel_dispatch_total",
           "CCA mixers run everything between their projections and the "
           "attention in the token-major kernel that convolves the latent",
           "walked that chain through XLA, or have no such mixer",
           ("cca_conv",)),
    Family("gdn_kernel", "seq_gdn_kernel_dispatch_total",
           "Gated DeltaNet mixers scan through the kernel that keeps a "
           "span's factors and the value heads' states on the chip",
           "looped over chunks through XLA, or have no such mixer",
           ("gdn_scan",)),
)


def equations(jaxpr) -> Iterator:
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def kernels_of(program, *args) -> frozenset:
    """The names of the Pallas kernels in the program that
    ``program(*args)`` traces to: its jaxpr is read, so the answer is what
    the trace chose and not a second reckoning of it. ``args`` may be
    shapes (``jax.ShapeDtypeStruct``); for a jitted program traced at them
    before, this costs a look-up in its trace cache."""
    return frozenset(
        eqn.params.get("name")
        for eqn in equations(jax.make_jaxpr(program)(*args).jaxpr)
        if eqn.primitive.name == "pallas_call")


def held_by(program, *args, names: tuple) -> bool:
    """Whether :func:`kernels_of` the program holds one of these names."""
    return not kernels_of(program, *args).isdisjoint(names)


def held(program, *args) -> dict:
    """Each family's key -> 1 where :func:`kernels_of` the program holds
    one of the family's kernels, else 0."""
    found = kernels_of(program, *args)
    return {f.key: int(not found.isdisjoint(f.names)) for f in FAMILIES}


def backend_runs_pallas() -> bool:
    """Mosaic on the TPU, the interpreter on the CPU; no other backend."""
    return jax.default_backend() in ("tpu", "cpu")


def interpreted() -> bool:
    """Whether a ``pallas_call`` made now runs under the interpreter (and a
    scan's ``exact=None`` multiplies in float32 as it does): off the TPU."""
    return jax.default_backend() != "tpu"


def serves(dtype) -> bool:
    """bfloat16 (the serving path) or float32 (the tests' second opinion)."""
    return jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)


def off_mesh(*operands) -> bool:
    """None of these operands (arrays or ``jax.ShapeDtypeStruct``) lies on
    a mesh and no abstract mesh is set: a kernel is not partitioned for
    us, so a mesh keeps the plain path until someone measures one."""
    meshes = (getattr(getattr(jax.typeof(x), "sharding", None), "mesh", None)
              for x in operands)
    return (all(mesh is None or mesh.empty for mesh in meshes)
            and jax.sharding.get_abstract_mesh().empty)
