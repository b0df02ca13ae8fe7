"""Main-path Pallas kernels, and the serving engine's decode step, compile
for a TPU v5e at qwen2-0.5b widths.

The TPU compiler is installed with jaxlib, so each kernel is lowered and
compiled here for one chip of a described-but-not-attached ``v5e:2x2``
topology.  That catches what interpret mode cannot: blocks that break the
(8, 128) tiling rule, VMEM overruns, programs that do not fit HBM.  A
compile that passes is not a chip run: nothing executes.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.  Kernels are called with
``interpret=False``: the backend here is the CPU, which would otherwise
pick interpret mode.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_fused as kdf
from repro.kernels import ops as kops

# qwen2-0.5b widths (configs/qwen2_0_5b.py) and the train-step token count
D, F, V = 896, 4864, 151936
HEADS, KV_HEADS, HEAD_DIM = 14, 2, 64
M = 16384
SLOTS, MAX_LEN = 16, 1088


@pytest.fixture(scope="module")
def topo():
    # conftest.py keeps the persistent compilation cache off: a compile for
    # a described chip could be written to it but never read back here
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KEY = (2,)


@pytest.mark.parametrize("name,a_shape,b_shape,sr,trans_b", [
    ("ff_ffn_in_sr", (M, D), (D, F), True, False),
    ("bp_ffn_in_trans_b", (M, F), (D, F), False, True),
    ("ff_lm_head_trans_b", (M, D), (V, D), False, True),
])
def test_sr_matmul_compiles(one_chip, name, a_shape, b_shape, sr, trans_b):
    bf = jnp.bfloat16
    key = _sds(KEY, jnp.uint32, one_chip) if sr else None

    def f(a, b, *k):
        return kops.sr_matmul(a, b, k[0] if k else None, sr=sr,
                              trans_b=trans_b, interpret=False)

    args = [_sds(a_shape, bf, one_chip), _sds(b_shape, bf, one_chip)]
    _compile(f, *args, *([key] if sr else []))


def test_outer_accum_sr_compiles(one_chip):
    bf = jnp.bfloat16

    def f(x, dy, k):
        return kops.outer_accum(x, dy, k, sr=True, interpret=False)

    _compile(f, _sds((M, D), bf, one_chip), _sds((M, F), bf, one_chip),
             _sds(KEY, jnp.uint32, one_chip))


def _ffn_weights(one_chip):
    bf = jnp.bfloat16
    return (_sds((D, 2 * F), bf, one_chip), _sds((F, D), bf, one_chip))


# The fused-decode kernels map each weight whole into VMEM (`_whole`):
# at published width w_in alone is 896 x 9728 x 2 B = 17.4 MB.  ROADMAP
# speed 6 tracks tiling them; until then these compiles are refused.
_VMEM = pytest.mark.xfail(
    strict=True, raises=jax.errors.JaxRuntimeError,
    reason="RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem ... "
           "Scoped allocation with size 24.94M and limit 16.00M exceeded "
           "scoped vmem limit by 8.94M")
_HEAD_SPLIT = pytest.mark.xfail(
    strict=True,
    reason="Mosaic failed to compile TPU kernel: infer-vector-layout: "
           "unsupported shape cast (the (1, H*hd) -> (H, hd) head split "
           "of the qkv row)")


@_VMEM
def test_fused_ffn_compiles(one_chip):
    w_in, w_out = _ffn_weights(one_chip)

    def f(x, w_in, w_out):
        return kdf.fused_ffn(x, norm2_scale=None, norm2_bias=None,
                             w_in=w_in, w_out=w_out, interpret=False)

    _compile(f, _sds((SLOTS, D), jnp.bfloat16, one_chip), w_in, w_out)


@_HEAD_SPLIT
def test_fused_attn_unit_compiles(one_chip):
    bf = jnp.bfloat16
    w_in, w_out = _ffn_weights(one_chip)
    qn = (HEADS + 2 * KV_HEADS) * HEAD_DIM

    def f(x, kc, vc, kp, pos, qkv_w, o_w, w_in, w_out):
        return kdf.fused_attn_unit(
            x, kc, vc, kp, pos, norm1_scale=None, norm1_bias=None,
            qkv_w=qkv_w, qkv_bias=None, o_w=o_w, w_in=w_in, w_out=w_out,
            heads=HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
            rope_theta=1e6, interpret=False)

    cache = (SLOTS, MAX_LEN, KV_HEADS, HEAD_DIM)
    _compile(f, _sds((SLOTS, D), bf, one_chip), _sds(cache, bf, one_chip),
             _sds(cache, bf, one_chip),
             _sds((SLOTS, MAX_LEN), jnp.int32, one_chip),
             _sds((SLOTS,), jnp.int32, one_chip),
             _sds((D, qn), bf, one_chip), _sds((HEADS * HEAD_DIM, D), bf,
                                               one_chip), w_in, w_out)


def _engine_decode(one_chip, arch):
    """The engine's masked decode (``_decode``) for `arch` at published
    widths, 2 layers, SLOTS x MAX_LEN: (compiled program, count of copy or
    select ops as large as one layer's K leaf (a relayout of the arena, or
    a row-restoring ``jnp.where`` over it), that leaf's bytes)."""
    import dataclasses
    import math
    import re

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core import MeshSpec, compile_program
    from repro.runtime import train_loop as tl
    from repro.serving.engine import ServingEngine

    cfg = dataclasses.replace(get_config(arch), n_layers=2)
    shape = ShapeConfig("serve", seq_len=MAX_LEN, global_batch=SLOTS,
                        kind="decode")
    program = compile_program(cfg, shape,
                              MeshSpec(axis_sizes={"data": 1, "model": 1}))
    eng = ServingEngine(cfg, program, None, n_slots=SLOTS, max_len=MAX_LEN,
                        prefill_chunk=256, kernel_backend="pallas")
    params = jax.eval_shape(
        lambda k: tl.cast_params(tl.model_module(cfg).init(k, cfg),
                                 jnp.bfloat16), jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    compiled = eng._decode.lower(
        on_chip(params), on_chip(eng.cache),
        _sds((SLOTS, 1), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.bool_, one_chip)).compile()
    a = cfg.attention
    k_leaf = SLOTS * MAX_LEN * a.n_kv_heads * a.head_dim * 2   # bf16
    width = {"pred": 1, "bf16": 2, "s32": 4, "f32": 4}
    arena_ops = [
        op for dtype, dims, op in re.findall(
            r"= (\w+)\[([\d,]+)\]\S* (copy|select)\(", compiled.as_text())
        if width.get(dtype, 4) * math.prod(map(int, dims.split(",")))
        >= k_leaf]
    return compiled, len(arena_ops), k_leaf


def test_engine_decode_moves_no_arena_copy(one_chip):
    """The engine's masked decode writes one position per active row into
    the donated arena in place: the compiled program holds no copy or
    select shaped like an arena leaf, and no temporary as large as one
    layer's K leaf.  At this size a copy can sit in on-chip memory outside
    the temporaries, so the ops are counted in the program text too."""
    compiled, arena_ops, k_leaf = _engine_decode(one_chip, "qwen2-0.5b")
    assert arena_ops == 0
    assert compiled.memory_analysis().temp_size_in_bytes < k_leaf


def test_engine_decode_moves_no_arena_copy_at_head_dim_128(one_chip):
    """The same at olmo-1b's widths (16 KV heads of 128: one head a cache
    row, where qwen2-0.5b's two heads of 64 share one)."""
    compiled, arena_ops, k_leaf = _engine_decode(one_chip, "olmo-1b")
    assert arena_ops == 0
    assert compiled.memory_analysis().temp_size_in_bytes < k_leaf
