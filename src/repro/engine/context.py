"""PEContext: the per-trace execution context of the PE engine.

Grown out of ``models/layers.Sharder`` (still importable under that name):
it keeps the dataflow program's layout duties — ``with_sharding_constraint``
at the points the paper would re-program the PMAG — and adds the dispatch
seam :meth:`dot`, which fuses the weight's layout constraint with the
op's :class:`~repro.core.program.PEWord` kernel dispatch.  Every
weight-bearing matmul in the model zoo calls ``sh.dot(...)``; none call
``jnp.einsum``/``@`` on a weight directly.

mesh=None (smoke tests) makes every constraint the identity and
backend='reference' makes every dot plain jnp, so the same model code runs
single-device reference, multi-pod GSPMD, and Pallas-kernel execution.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.phases import Phase
from repro.engine.dispatch import DEFAULT_WORD, op_key, pe_dot


@dataclass
class PEContext:
    """Applies the dataflow program's layouts and dispatches its kernels.

    backend: 'reference' (plain jnp, bit-identical to the pre-engine code)
    or 'pallas' (sr_matmul/outer_accum per PE program word).  `key` seeds
    the UP-phase SR entropy; thread the per-step key via :meth:`with_key`.
    `phase` tags which program word column the trace executes: FF (default,
    autodiff dispatches BP/UP) or the serving words PREFILL/DECODE — set it
    via :meth:`with_phase` when building serve steps.
    """
    mesh: Optional[object] = None        # jax.sharding.Mesh
    program: Optional[object] = None     # core.program.Program
    backend: str = "reference"           # kernel_backend: reference | pallas
    interpret: Optional[bool] = None     # pallas interpret mode (None = auto)
    key: Optional[jax.Array] = None      # phase key for UP-phase SR entropy
    phase: Phase = Phase.FF              # program-word column this trace runs

    def __post_init__(self):
        # Mosaic kernels cannot be auto-partitioned by GSPMD; refuse here,
        # when the step is built, rather than deep inside the compiler.
        if (self.backend == "pallas" and self.mesh is not None
                and self.mesh.devices.size > 1):
            raise NotImplementedError(
                f"kernel_backend='pallas' on a {self.mesh.devices.size}-"
                f"device mesh: the Pallas PE kernels are not yet wrapped in "
                f"shard_map, so they cannot be partitioned across devices; "
                f"use kernel_backend='reference' on a mesh, or one device")

    # --- engine dispatch ---------------------------------------------------

    def with_key(self, key: jax.Array) -> "PEContext":
        """Per-step copy carrying the step's SR entropy key."""
        return dataclasses.replace(self, key=key)

    def with_phase(self, phase: Phase) -> "PEContext":
        """Copy tagged with the phase whose program word :meth:`dot` runs."""
        return dataclasses.replace(self, phase=phase)

    def word(self, op_name: str):
        if self.program is not None:
            return self.program.pe_word(op_name)
        return dataclasses.replace(DEFAULT_WORD, op=op_name)

    def dot(self, op_name: str, x: jax.Array, w: jax.Array, *,
            stacked: bool = False, constrain: bool = True,
            transpose_w: bool = False) -> jax.Array:
        """THE seam: one weight-bearing matmul under op_name's program word.

        constrain=False for call sites that pre-constrained (or shard_map-
        sliced, or split) the weight; the kernel dispatch still applies.
        """
        if constrain:
            w = self.weight(w, op_name, stacked=stacked)
        # key folding only on the kernel path: the reference backend never
        # consumes entropy, so don't spend threefry ops deriving it
        key = op_key(self.key, op_name) if self.backend == "pallas" else None
        return pe_dot(x, w, word=self.word(op_name), backend=self.backend,
                      key=key, interpret=self.interpret,
                      transpose_w=transpose_w, phase=self.phase)

    def shard_map(self, *, in_specs, out_specs, check_vma: bool = True):
        """Decorator: ``jax.shard_map`` over THIS context's mesh.  The
        sharded-MoE block (and any future per-shard region) enters manual
        mode here, so model code never names the mesh itself."""
        if self.mesh is None:
            raise ValueError("shard_map needs a mesh-backed PEContext")
        return jax.shard_map(mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)

    # --- layout constraints (the PMAG re-programming points) ---------------

    def act(self, x: jax.Array, *spec) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def residual(self, x: jax.Array) -> jax.Array:
        """(B, S, D) residual-stream layout between blocks."""
        if self.mesh is None or self.program is None:
            return x
        plan = self.program.plan
        return self.act(x, plan.batch_spec or None, plan.seq_spec, None)

    def weight(self, w: jax.Array, op_name: str, *, stacked: bool = False) -> jax.Array:
        """Constrain a weight to its *compute* layout (GATHER ops broadcast
        here — the paper's just-in-time common-vault read), and program the
        layout of its GRADIENT: the per-layer dW cotangent is cast to bf16
        and constrained to the storage sharding INSIDE the backward scan.
        Without this GSPMD emits the per-layer dW DP-sync as an f32
        all-reduce-to-replicated (measured 1.14 TB/device/step on
        deepseek-33b — EXPERIMENTS.md §Perf D2/D3)."""
        if self.mesh is None or self.program is None:
            return w
        storage = self.program.weight_spec(op_name, stacked=stacked)
        if storage is not None and jnp.issubdtype(w.dtype, jnp.floating):
            w = _grad_layout(w, NamedSharding(self.mesh, storage))
        spec = self.program.compute_spec(op_name, stacked=stacked)
        if spec is None:
            return w
        return jax.lax.with_sharding_constraint(w, NamedSharding(self.mesh, spec))

    @property
    def batch_spec(self):
        if self.program is None:
            return None
        return self.program.plan.batch_spec or None

    @property
    def seq_axis(self):
        if self.program is None:
            return None
        return self.program.plan.seq_spec

    @property
    def n_chips(self) -> int:
        if self.program is None:
            return 1
        return self.program.mesh_spec.n_devices

    def heads(self, x: jax.Array) -> jax.Array:
        """(B, S, H, hd) head-sharded over `model` (GSPMD pads when H % tp).

        This is the Megatron attention layout: annotated explicitly so
        sharding propagation never re-shards per flash-chunk (observed:
        an involuntary 0.7 GB all-to-all PER kv-chunk without this)."""
        if self.mesh is None or self.program is None:
            return x
        return self.act(x, self.batch_spec, None, "model", None)

    def features(self, x: jax.Array) -> jax.Array:
        """(B, S, F) with F sharded over `model` (mamba/rwkv inner dims)."""
        if self.mesh is None or self.program is None:
            return x
        return self.act(x, self.batch_spec, None, "model")


def _grad_layout(w: jax.Array, sharding) -> jax.Array:
    """Identity whose transpose programs the cotangent's dtype + layout.

    The paper programs the PMAG separately for FF and BP/UP; this is the
    same move for autodiff: the forward value is untouched, the backward
    value (dW) is emitted bf16 and shard-constrained at its creation site,
    so the compiler reduces it sharded instead of replicated-f32."""

    dtype = w.dtype     # cotangent dtype must match the primal: fp32
                        # presets keep f32 grads (faithful reference path)

    @jax.custom_vjp
    def ident(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        g = g.astype(dtype)
        g = jax.lax.with_sharding_constraint(g, sharding)
        return (g,)

    ident.defvjp(fwd, bwd)
    return ident(w)


# Back-compat name: the pre-engine Sharder grew into PEContext.
Sharder = PEContext
