"""The TPU compiler accepts the main path's kernels at real widths.

Interpret mode accepts blocks Mosaic refuses (``tests/test_ft_fused.py``
runs ``(1, 1, k)`` element blocks), so these tests compile for a described
``v5e:2x2`` — nothing runs, no chip is needed — and check that the compiled
program holds the Pallas kernel (``tpu_custom_call``):

  * ``ft_matmul`` at qwen1.5-0.5b's decode and prefill projection shapes,
    with no prune mask, a full ``(m, n)`` mask and a periodic tile;
  * ``ft_matmul`` fed bf16 operands at starcoder2-3b's decode projection
    shapes, on 64 slots directly and on 4 slots through the fused
    ``FTContext`` dispatch (block from the bf16 heuristic, rows padded);
  * ``ft_matmul_batched`` at deepseek-moe-16b's expert shapes (64 experts,
    d_model 2048, d_expert 1408);
  * ``ft_matmul_batched`` fed bf16 through the fused ``FTContext`` expert
    dispatch at deepseek-v2-lite's decode share (128 slots, 16 held
    experts, d_model 2048, d_expert 1408);
  * both dispatches with a 256-token prompt chunk beside the slots' rows
    (starcoder2-3b: 320 rows; deepseek-v2-lite's experts: 384), each one
    row block of the heuristic's;
  * ``probe_check`` at the server's probe shapes (8x8 and 32x32 arrays) and
    at K > window.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.hyca_dla import dla_config
from repro.core.engine import empty_fault_state
from repro.core.ftcontext import build_ftcontext
from repro.kernels.dppu_recompute import probe_check
from repro.kernels.ft_matmul import ft_matmul, ft_matmul_batched

ROWS = COLS = 32

# starcoder2-3b decode projections (k, n): q and out, k and v, up, down, the
# tied head
STARCODER2_DECODE = [(3072, 3072), (3072, 256), (3072, 12288), (12288, 3072), (3072, 49152)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,k,n,block,mask", [
    (8, 1024, 2816, (8, 128, 128), None),          # decode ffn up/gate
    (8, 2816, 1024, (8, 128, 128), "full"),        # decode ffn down, plan active
    (8, 1024, 152064, (8, 128, 128), None),        # decode LM head (padded vocab)
    (512, 1024, 2816, (128, 128, 128), "tile"),    # prefill 8 x 64 tokens, plan active
])
def test_ft_matmul_compiles(one_chip, m, k, n, block, mask):
    bm, bn, bk = block
    mask_shape = {"full": (m, n), "tile": (bm, bn)}.get(mask)
    args = [_sds((m, k), jnp.float32, one_chip), _sds((k, n), jnp.float32, one_chip),
            _sds((ROWS, COLS), jnp.int32, one_chip)]
    if mask_shape is not None:
        args.append(_sds(mask_shape, jnp.int32, one_chip))
    _assert_kernel(lambda *a: ft_matmul(*a, bm=bm, bn=bn, bk=bk), *args)


@pytest.mark.parametrize("k,n", STARCODER2_DECODE)
def test_ft_matmul_bf16_compiles(one_chip, k, n):
    """bf16 operands straight into the kernel, 64 decode slots in one block."""
    _assert_kernel(
        lambda x, w, meta: ft_matmul(x, w, meta, bm=64, bn=128, bk=128),
        _sds((64, k), jnp.bfloat16, one_chip), _sds((k, n), jnp.bfloat16, one_chip),
        _sds((ROWS, COLS), jnp.int32, one_chip),
    )


@pytest.mark.parametrize("k,n", STARCODER2_DECODE)
def test_fused_dispatch_bf16_compiles_at_4_slots(one_chip, k, n):
    """A 4-slot bf16 decode through ``FTContext``: the block follows the
    bf16 sublane tile and the rows are padded up to it."""
    hyca = dla_config()
    ctx = dataclasses.replace(
        build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused"),
        fused_backend="pallas",
    )
    _assert_kernel(
        lambda x, w: ctx.matmul(x, w, site="ffn"),
        _sds((4, k), jnp.bfloat16, one_chip), _sds((k, n), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize("k,n", STARCODER2_DECODE)
def test_fused_dispatch_bf16_compiles_with_a_prompt_chunk(one_chip, k, n):
    """64 slots' rows and a 256-token prompt chunk through ``FTContext``:
    320 rows, one row block, so the grid reads each weight tile once."""
    hyca = dla_config()
    ctx = dataclasses.replace(
        build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused"),
        fused_backend="pallas",
    )
    assert ctx._block_for(320, n, k, jnp.bfloat16) == (320, 128, 128)
    _assert_kernel(
        lambda x, w: ctx.matmul(x, w, site="ffn"),
        _sds((320, 1, k), jnp.bfloat16, one_chip), _sds((k, n), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_ft_matmul_batched_compiles(one_chip, k, n):
    e, m = 64, 8
    _assert_kernel(
        lambda x, w, meta: ft_matmul_batched(x, w, meta, bm=8, bn=128, bk=128),
        _sds((e, m, k), jnp.float32, one_chip), _sds((e, k, n), jnp.float32, one_chip),
        _sds((ROWS, COLS), jnp.int32, one_chip),
    )


@pytest.mark.parametrize("spec,k,n", [("becd,edf->becf", 2048, 1408),
                                       ("becf,efd->becd", 1408, 2048)])
def test_fused_expert_dispatch_bf16_compiles_at_the_v2lite_share(one_chip, spec, k, n):
    """128 decode slots' one-token groups over 16 held experts, bf16."""
    hyca = dla_config()
    ctx = dataclasses.replace(
        build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused"),
        fused_backend="pallas",
    )
    _assert_kernel(
        lambda x, w: ctx.einsum(spec, x, w, site="moe.expert"),
        _sds((128, 16, 1, k), jnp.bfloat16, one_chip), _sds((16, k, n), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize("spec,k,n", [("becd,edf->becf", 2048, 1408),
                                       ("becf,efd->becd", 1408, 2048)])
def test_fused_expert_dispatch_bf16_compiles_with_a_prompt_chunk(one_chip, spec, k, n):
    """128 slots and a 256-token prompt chunk as one-token groups over 16
    held experts, bf16: 384 rows an expert, one row block."""
    hyca = dla_config()
    ctx = dataclasses.replace(
        build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused"),
        fused_backend="pallas",
    )
    _assert_kernel(
        lambda x, w: ctx.einsum(spec, x, w, site="moe.expert"),
        _sds((384, 16, 1, k), jnp.bfloat16, one_chip), _sds((16, k, n), jnp.bfloat16, one_chip),
    )


@pytest.mark.parametrize("block,k,cols", [
    (1, 8, 8),      # 8x8 serving array, one grid row per scan step
    (1, 8, 32),     # the paper's 32x32 array
    (32, 8, 32),    # a whole-array sweep block
    (1, 16, 32),    # K > window
    (4, 256, 32),   # K a multiple of the 128-lane panel
])
def test_probe_check_compiles(one_chip, block, k, cols):
    _assert_kernel(
        probe_check,
        _sds((block, k), jnp.int32, one_chip), _sds((k, cols), jnp.int32, one_chip),
        _sds((block, cols), jnp.int32, one_chip),
    )
