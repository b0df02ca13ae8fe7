"""Topology-aware planning: hop-class cost model properties, module-aware
stage placement, module-loss recovery, mesh-spec derivation.

The property suite over the cost model runs under hypothesis when the
package is available (CI installs it via requirements-dev.txt); every
property also has a deterministic pinned case below so the invariants
stay covered in bare containers.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import (HOP_INTER, HOP_INTRA, MeshSpec, ModuleTopology,
                        compile_program, extract_ops, plan_model,
                        split_hop_bytes)
from repro.core.dataflow import ICI_BW
from repro.launch.mesh import (auto_mesh, make_module_mesh,
                               make_pipeline_mesh, mesh_spec_for,
                               module_mesh_spec)
from repro.pipeline.partition import (partition_model, place_stages,
                                      stage_edges)
from repro.runtime.fault_tolerance import elastic_replan, surviving_topology
from repro.tuner.cost import comm_time_s

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container without hypothesis: pinned cases only
    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):           # decorator shims so the property class
        return lambda f: f          # still *defines* (it is skipped whole)

    def settings(*_a, **_k):
        return lambda f: f

    class st:  # noqa: N801 — stands in for hypothesis.strategies
        floats = integers = sampled_from = staticmethod(
            lambda *_a, **_k: None)

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")

OPS = extract_ops(get_reduced("qwen2-0.5b"))


def _hop_cost(nbytes, group, modules, intra_bw, inter_bw):
    """Seconds for one collective under the hop-class split."""
    hop = split_hop_bytes(nbytes, group, modules)
    return hop[HOP_INTRA] / intra_bw + hop[HOP_INTER] / inter_bw


def _plan(mesh, *, hbm_budget=0.0):
    return plan_model(OPS, mesh, global_batch=8, seq_len=64, kind="train",
                      hbm_budget=hbm_budget)


def _plans_equal(pa, pb):
    """Strategy + comm bytes bit-for-bit equal between two DataflowPlans."""
    assert set(pa.ops) == set(pb.ops)
    for name in pa.ops:
        a, b = pa.ops[name], pb.ops[name]
        assert a.strategy == b.strategy, name
        assert a.comm_bytes == b.comm_bytes, name


# ---------------------------------------------------------------------------
# Cost-model properties (hypothesis + pinned)
# ---------------------------------------------------------------------------


@needs_hypothesis
class TestCostModelProperties:
    @settings(max_examples=200, deadline=None)
    @given(nbytes=st.floats(0, 1e12), group=st.integers(1, 4096),
           modules=st.integers(1, 64))
    def test_hop_split_sums_exactly(self, nbytes, group, modules):
        hop = split_hop_bytes(nbytes, group, modules)
        assert hop[HOP_INTRA] + hop[HOP_INTER] == nbytes
        assert hop[HOP_INTER] >= 0.0

    @settings(max_examples=200, deadline=None)
    @given(nbytes=st.floats(1, 1e12), group=st.integers(2, 4096),
           m1=st.integers(1, 64), m2=st.integers(1, 64),
           intra=st.floats(1e9, 1e12), ratio=st.floats(1.0, 64.0))
    def test_cost_monotone_in_hop_count(self, nbytes, group, m1, m2,
                                        intra, ratio):
        """More module crossings never make a collective cheaper."""
        lo, hi = sorted((m1, m2))
        inter = intra / ratio            # inter link never faster
        assert (_hop_cost(nbytes, group, hi, intra, inter)
                >= _hop_cost(nbytes, group, lo, intra, inter) - 1e-12)

    @settings(max_examples=200, deadline=None)
    @given(nbytes=st.floats(1, 1e12), group=st.integers(2, 4096),
           modules=st.integers(2, 64), intra=st.floats(1e9, 1e12),
           bw1=st.floats(1e8, 1e12), bw2=st.floats(1e8, 1e12))
    def test_cost_non_increasing_in_bandwidth(self, nbytes, group, modules,
                                              intra, bw1, bw2):
        """A faster inter-module link never makes a collective slower."""
        lo, hi = sorted((bw1, bw2))
        assert (_hop_cost(nbytes, group, modules, intra, hi)
                <= _hop_cost(nbytes, group, modules, intra, lo) + 1e-12)

    @settings(max_examples=20, deadline=None)
    @given(data=st.sampled_from((1, 2, 4)), model=st.sampled_from((1, 2)),
           pes=st.integers(1, 8))
    def test_one_module_topology_is_cost_identical(self, data, model, pes):
        """Degenerate 1-module cloud == the pre-topology planner, bitwise."""
        sizes = {"data": data, "model": model}
        bare = MeshSpec(axis_sizes=sizes, batch_axes=("data",),
                        tp_axis="model")
        topo = ModuleTopology(n_modules=1, pes_per_module=pes)
        spec = MeshSpec(axis_sizes=sizes, batch_axes=("data",),
                        tp_axis="model", topology=topo)
        _plans_equal(_plan(bare), _plan(spec))

    @settings(max_examples=20, deadline=None)
    @given(modules=st.sampled_from((2, 4)), data=st.sampled_from((1, 2)),
           model=st.sampled_from((1, 2)))
    def test_hop_totals_sum_to_untyped_bytes(self, modules, data, model):
        topo = ModuleTopology(n_modules=modules,
                              pes_per_module=data * model,
                              inter_bw=ICI_BW / 8)
        plan = _plan(module_mesh_spec(topo, model=model), hbm_budget=1e4)
        untyped = sum(sum(p.comm_bytes.values()) for p in plan.ops.values())
        hop = plan.total_comm_hop_bytes()
        assert hop[HOP_INTRA] + hop[HOP_INTER] == pytest.approx(
            untyped, rel=1e-9, abs=1e-6)


# pinned cases: the same invariants without hypothesis


def test_hop_split_pinned():
    assert split_hop_bytes(100.0, 8, 4) == {HOP_INTRA: 50.0, HOP_INTER: 50.0}
    assert split_hop_bytes(100.0, 8, 1) == {HOP_INTRA: 100.0, HOP_INTER: 0.0}
    assert split_hop_bytes(100.0, 1, 4) == {HOP_INTRA: 100.0, HOP_INTER: 0.0}
    # modules can never exceed the group: clamps rather than over-splits
    assert split_hop_bytes(100.0, 4, 99)[HOP_INTER] == 100.0


def test_cost_monotone_pinned():
    costs = [_hop_cost(1e9, 64, m, ICI_BW, ICI_BW / 8)
             for m in (1, 2, 4, 8, 16)]
    assert costs == sorted(costs)
    bws = [_hop_cost(1e9, 64, 8, ICI_BW, bw)
           for bw in (1e9, 1e10, 1e11, 1e12)]
    assert bws == sorted(bws, reverse=True)


def test_one_module_parity_pinned():
    for sizes, baxes in (({"data": 4, "model": 1}, ("data",)),
                         ({"data": 2, "model": 2}, ("data",)),
                         ({"pod": 2, "data": 2, "model": 2},
                          ("pod", "data"))):
        bare = MeshSpec(axis_sizes=sizes, batch_axes=baxes, tp_axis="model")
        spec = MeshSpec(axis_sizes=sizes, batch_axes=baxes, tp_axis="model",
                        topology=ModuleTopology(n_modules=1,
                                                pes_per_module=4))
        _plans_equal(_plan(bare), _plan(spec))
        # the tuner's comm pricing is the same seconds, too
        for a, b in zip(_plan(bare).ops.values(), _plan(spec).ops.values()):
            assert comm_time_s(a) == comm_time_s(b, spec.topology)


def test_hop_totals_sum_pinned():
    topo = ModuleTopology(n_modules=4, pes_per_module=2, inter_bw=ICI_BW / 8)
    plan = _plan(module_mesh_spec(topo, model=2), hbm_budget=1e4)
    untyped = sum(sum(p.comm_bytes.values()) for p in plan.ops.values())
    hop = plan.total_comm_hop_bytes()
    assert hop[HOP_INTRA] + hop[HOP_INTER] == pytest.approx(
        untyped, rel=1e-9, abs=1e-6)
    assert hop[HOP_INTER] > 0  # the multi-module cloud really splits


def test_multi_module_comm_prices_higher():
    """The tuner charges the slow network for inter-module bytes."""
    topo = ModuleTopology(n_modules=4, pes_per_module=2, inter_bw=ICI_BW / 8)
    plan = _plan(module_mesh_spec(topo, model=2), hbm_budget=1e4)
    flat = sum(sum(p.comm_bytes.values()) / ICI_BW
               for p in plan.ops.values())
    priced = sum(comm_time_s(p, topo) for p in plan.ops.values())
    assert priced > flat


def test_describe_and_table_show_hop_classes():
    topo = ModuleTopology(n_modules=4, pes_per_module=2, inter_bw=ICI_BW / 8)
    plan = _plan(module_mesh_spec(topo, model=2), hbm_budget=1e4)
    assert "hops=intra:" in plan.table()
    assert "4 modules x 2 PEs" in plan.table()
    op = next(p for p in plan.ops.values()
              if p.hop_totals().get(HOP_INTER, 0) > 0)
    assert "inter" in op.describe()


def test_topology_validation():
    with pytest.raises(ValueError):
        ModuleTopology(n_modules=0)
    with pytest.raises(ValueError):
        ModuleTopology(intra_bw=-1.0)
    assert ModuleTopology(n_modules=4, pes_per_module=8).n_pes == 32


# ---------------------------------------------------------------------------
# Mesh-spec derivation + pipeline-mesh warning (satellite fixes)
# ---------------------------------------------------------------------------


def test_mesh_spec_for_derives_axes_from_mesh():
    spec = mesh_spec_for(auto_mesh((1, 1), ("replica", "tensor")))
    assert spec.tp_axis == "tensor"          # no "model": innermost wins
    assert spec.batch_axes == ("replica",)
    spec = mesh_spec_for(auto_mesh((1, 1, 1), ("pod", "data", "model")))
    assert spec.tp_axis == "model"
    assert spec.batch_axes == ("pod", "data")
    # stage slices layers, never batch
    spec = mesh_spec_for(auto_mesh((1, 1, 1), ("stage", "data", "model")))
    assert spec.batch_axes == ("data",)


def test_mesh_spec_for_threads_topology():
    topo = ModuleTopology(n_modules=1, pes_per_module=1)
    mesh = auto_mesh((1, 1, 1), ("module", "data", "model"))
    spec = mesh_spec_for(mesh, topology=topo)
    assert spec.topology is topo
    assert spec.batch_axes == ("module", "data")
    with pytest.raises(ValueError):
        mesh_spec_for(mesh, topology=ModuleTopology(n_modules=3,
                                                    pes_per_module=1))


def test_make_pipeline_mesh_warns_why():
    with pytest.warns(UserWarning, match="not divisible by 3 stages"):
        assert make_pipeline_mesh(3, n_devices=4) is None
    with pytest.warns(UserWarning, match="num_stages=1 < 2"):
        assert make_pipeline_mesh(1, n_devices=4) is None


def test_make_module_mesh_warns_on_mismatch():
    topo = ModuleTopology(n_modules=2, pes_per_module=4)
    with pytest.warns(UserWarning, match="needs 2x4=8"):
        assert make_module_mesh(topo, n_devices=4) is None
    with pytest.warns(UserWarning, match="not divisible by model=3"):
        assert make_module_mesh(topo, model=3, n_devices=8) is None


def test_module_mesh_spec_layout():
    topo = ModuleTopology(n_modules=2, pes_per_module=4)
    spec = module_mesh_spec(topo, model=2)
    assert spec.axis_sizes == {"module": 2, "data": 2, "model": 2}
    assert spec.batch_axes == ("module", "data")
    assert spec.topology is topo
    with pytest.raises(ValueError):
        module_mesh_spec(topo, model=3)


# ---------------------------------------------------------------------------
# Module-aware stage placement (satellite regression)
# ---------------------------------------------------------------------------


def test_placement_keeps_heaviest_edge_intra_module():
    """qwen2's tied-embedding edge (stage 0 <-> head stage) dwarfs the
    activation handoffs; a skewed inter-module link must keep it on-module
    even though that breaks stage contiguity."""
    cfg = get_config("qwen2-0.5b")
    assert cfg.tie_embeddings
    topo = ModuleTopology(n_modules=2, pes_per_module=2, inter_bw=ICI_BW / 16)
    plan = partition_model(cfg, 4, global_batch=8, seq_len=128,
                           topology=topo)
    a = plan.module_assignment
    assert len(a) == 4
    heaviest = max(plan.edges, key=lambda e: e.nbytes)
    assert heaviest.kind == "tied_embed"
    assert a[heaviest.src] == a[heaviest.dst]
    # capacity respected: 2 stages per module
    assert sorted(a) == [0, 0, 1, 1]
    d = plan.to_dict()
    assert d["module_assignment"] == list(a)
    assert d["inter_module_bytes"] == plan.inter_module_bytes
    assert d["inter_module_bytes"] < d["intra_module_bytes"]
    assert "placement:" in plan.table()


def test_placement_beats_contiguous_blocks():
    cfg = get_config("qwen2-0.5b")
    topo = ModuleTopology(n_modules=2, pes_per_module=2)
    plan = partition_model(cfg, 4, topology=topo)
    naive = (0, 0, 1, 1)
    naive_inter = sum(e.nbytes for e in plan.edges
                      if naive[e.src] != naive[e.dst])
    assert plan.inter_module_bytes < naive_inter


def test_no_topology_means_no_assignment():
    cfg = get_config("qwen2-0.5b")
    plan = partition_model(cfg, 4)
    assert plan.module_assignment == ()
    assert plan.edges              # edges are still recorded
    assert plan.inter_module_bytes == 0.0


def test_place_stages_determinism_and_capacity():
    edges = stage_edges(get_config("qwen2-0.5b"), 6,
                        tokens_per_step=1024.0)
    a1 = place_stages(edges, 6, 3)
    a2 = place_stages(edges, 6, 3)
    assert a1 == a2
    assert max(a1.count(m) for m in set(a1)) <= 2
    assert place_stages((), 4, 1) == (0, 0, 0, 0)
    with pytest.raises(ValueError):
        place_stages(edges, 6, 0)


# ---------------------------------------------------------------------------
# Module-loss fault injection (satellite parity test)
# ---------------------------------------------------------------------------


SMOKE = ShapeConfig("smoke", seq_len=16, global_batch=2, kind="train")


def test_surviving_topology():
    topo = ModuleTopology(n_modules=4, pes_per_module=8,
                          inter_bw=ICI_BW / 8)
    s = surviving_topology(topo, 1)
    assert s.n_modules == 3 and s.pes_per_module == 8
    assert s.inter_bw == topo.inter_bw
    with pytest.raises(ValueError):
        surviving_topology(topo, 4)
    with pytest.raises(ValueError):
        surviving_topology(topo, -1)


def test_module_loss_replan_parity(tmp_path):
    """Drop a whole module after step 2: checkpoint reshards onto the
    surviving 1-module cloud, elastic_replan recompiles — and the recovered
    run matches an uninterrupted run on the survivor shape (reference
    backend, fp32: training math is program-independent)."""
    from repro.checkpoint import Checkpointer
    from repro.data import SyntheticLM
    from repro.runtime import train_loop as tl

    cfg = get_reduced("qwen2-0.5b")
    tc = TrainConfig(optimizer="sgdm", lr=1e-2, precision="fp32")
    pipe = SyntheticLM(cfg, SMOKE)
    key = jax.random.PRNGKey(0)

    # 2-module cloud program (planning-level: the container has 1 device,
    # so execution runs unsharded — the parity property under test)
    topo2 = ModuleTopology(n_modules=2, pes_per_module=1,
                           inter_bw=ICI_BW / 8)
    prog2 = compile_program(cfg, SMOKE, module_mesh_spec(topo2),
                            precision="fp32")
    step2, opt2 = tl.make_train_step(cfg, prog2, tc, mesh=None)
    step2 = jax.jit(step2)
    state = tl.init_state(cfg, prog2, tc, key, opt2)

    losses, gnorms = [], []
    for i in range(2):
        state, m = step2(state, pipe.batch_at(i), jax.random.key(i))
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))

    # module 1 dies: checkpoint out, replan onto the survivor
    ck = Checkpointer(str(tmp_path))
    ck.save(2, state, {"arch": cfg.name}, blocking=True)
    host_state, step, _ = ck.restore(jax.device_get(state))
    assert step == 2
    survivor = surviving_topology(topo2, 1)
    new_mesh = auto_mesh((1, 1), ("data", "model"))
    prog1, step1, state1, _ = elastic_replan(
        cfg, SMOKE, new_mesh, host_state, tc, "fp32", topology=survivor)
    step1 = jax.jit(step1)
    for i in range(2, 4):
        state1, m = step1(state1, pipe.batch_at(i), jax.random.key(i))
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))

    # uninterrupted reference on the surviving shape, same seeds
    spec1 = mesh_spec_for(new_mesh, topology=survivor)
    prog_ref = compile_program(cfg, SMOKE, spec1, precision="fp32")
    step_ref, opt_ref = tl.make_train_step(cfg, prog_ref, tc, mesh=None)
    step_ref = jax.jit(step_ref)
    state_ref = tl.init_state(cfg, prog_ref, tc, key, opt_ref)
    ref_losses, ref_gnorms = [], []
    for i in range(4):
        state_ref, m = step_ref(state_ref, pipe.batch_at(i),
                                jax.random.key(i))
        ref_losses.append(float(m["loss"]))
        ref_gnorms.append(float(m["grad_norm"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-6)
    np.testing.assert_allclose(gnorms, ref_gnorms, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state1["params"]),
                    jax.tree.leaves(state_ref["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_every_mesh_has_auto_axes():
    """with_sharding_constraint only accepts Auto axes; jax.make_mesh
    defaults to Explicit ones."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    for mesh in (auto_mesh((1, 1), ("data", "model")), make_host_mesh(),
                 make_module_mesh(ModuleTopology(n_modules=1,
                                                 pes_per_module=1))):
        assert set(mesh.axis_types) == {AxisType.Auto}
