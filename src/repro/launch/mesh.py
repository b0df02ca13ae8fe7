"""Production mesh definitions.

FUNCTIONS, not module constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).

The module-cloud additions (`make_module_mesh`, `module_mesh_spec`) lay
the memory modules out as the OUTERMOST mesh axis, so a collective that
stays inside the inner axes never leaves a module — which is exactly the
property the planner's hop-class cost model prices
(`core.dataflow.ModuleTopology`).

Every mesh is built by :func:`auto_mesh`: the program places arrays with
``with_sharding_constraint``, which only accepts ``Auto`` mesh axes
(``jax.make_mesh`` defaults to ``Explicit`` ones).
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType

from repro.core.dataflow import MeshSpec, ModuleTopology


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagation)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_spec_for(mesh, *, topology: ModuleTopology | None = None) -> MeshSpec:
    """Planner-facing description of a jax Mesh.

    Axes are DERIVED from the mesh rather than assumed: the tensor axis
    is ``model`` when present (else the innermost axis), the ``stage``
    axis (the inter-module pipeline dimension) is never a batch axis —
    it slices *layers*, not data — and every remaining axis carries
    batch (``pod``, ``data``, ``module``, whatever the mesh names them).

    topology: the module-level link shape; when its module axis names a
    mesh axis, the planner splits collective bytes into intra-/inter-
    module hop classes and prices them at per-class bandwidth.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_axis = "model" if "model" in axis_sizes else mesh.axis_names[-1]
    batch_axes = tuple(a for a in mesh.axis_names
                       if a not in (tp_axis, "stage"))
    if (topology is not None and topology.module_axis in axis_sizes
            and axis_sizes[topology.module_axis] % topology.n_modules != 0):
        raise ValueError(
            f"mesh axis {topology.module_axis!r} has size "
            f"{axis_sizes[topology.module_axis]}, not divisible by the "
            f"topology's {topology.n_modules} modules")
    return MeshSpec(axis_sizes=axis_sizes, batch_axes=batch_axes,
                    tp_axis=tp_axis, topology=topology)


def make_host_mesh(n_devices: int | None = None, *, data: int | None = None,
                   model: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    if data is None or model is None:
        model = 1
        data = n
    return auto_mesh((data, model), ("data", "model"))


def make_module_mesh(topology: ModuleTopology, *, model: int = 1,
                     n_devices: int | None = None):
    """("module", "data", "model") mesh: one module row per memory module.

    The module axis is outermost, so the inner data x model block of each
    row lives entirely inside one module — collectives that avoid the
    module axis never touch the inter-module network.  Returns None (with
    a one-line warning naming why) when the host devices cannot honour
    the topology; callers then plan against :func:`module_mesh_spec`.
    """
    n = n_devices or len(jax.devices())
    if topology.pes_per_module % model != 0:
        warnings.warn(
            f"make_module_mesh: {topology.pes_per_module} PEs/module not "
            f"divisible by model={model}; no module mesh", stacklevel=2)
        return None
    if n != topology.n_pes:
        warnings.warn(
            f"make_module_mesh: host has {n} devices but the topology "
            f"needs {topology.n_modules}x{topology.pes_per_module}="
            f"{topology.n_pes}; no module mesh", stacklevel=2)
        return None
    return auto_mesh(
        (topology.n_modules, topology.pes_per_module // model, model),
        (topology.module_axis, "data", "model"))


def module_mesh_spec(topology: ModuleTopology, *, model: int = 1) -> MeshSpec:
    """Planner MeshSpec for a module cloud, no devices required.

    Mirrors :func:`make_module_mesh`'s layout — (module, data, model)
    with the module axis outermost and joining the batch axes (modules
    carry data-parallel replicas unless the planner shards state over
    them) — so plans made from the spec match plans made from the mesh.
    """
    if topology.pes_per_module % model != 0:
        raise ValueError(f"{topology.pes_per_module} PEs/module not "
                         f"divisible by model={model}")
    sizes = {topology.module_axis: topology.n_modules,
             "data": topology.pes_per_module // model, "model": model}
    return MeshSpec(axis_sizes=sizes,
                    batch_axes=(topology.module_axis, "data"),
                    tp_axis="model", topology=topology)


def make_pipeline_mesh(num_stages: int, n_devices: int | None = None):
    """("stage", "data", "model") mesh: one stage row per memory module.

    Returns None when the host devices cannot honour the stage axis —
    the pipeline runner then executes the same schedule with virtual
    stages and identity handoffs, which is bit-identical to the ppermute
    path.  The fallback is announced with a one-line warning naming why
    (it used to be silent, leaving users guessing which path ran).
    """
    n = n_devices or len(jax.devices())
    if num_stages < 2:
        warnings.warn(
            f"make_pipeline_mesh: num_stages={num_stages} < 2; falling "
            f"back to virtual stages", stacklevel=2)
        return None
    if n % num_stages != 0:
        warnings.warn(
            f"make_pipeline_mesh: {n} host devices not divisible by "
            f"{num_stages} stages; falling back to virtual stages",
            stacklevel=2)
        return None
    return auto_mesh((num_stages, n // num_stages, 1),
                     ("stage", "data", "model"))


def pipeline_mesh_spec(num_stages: int, base: MeshSpec | None = None) -> MeshSpec:
    """MeshSpec with the stage axis prepended (base defaults to 1x1)."""
    sizes = dict(base.axis_sizes) if base is not None else {"data": 1,
                                                            "model": 1}
    sizes = {"stage": num_stages, **{k: v for k, v in sizes.items()
                                     if k != "stage"}}
    return MeshSpec(axis_sizes=sizes,
                    batch_axes=base.batch_axes if base else ("data",),
                    tp_axis=base.tp_axis if base else "model",
                    topology=base.topology if base else None)
