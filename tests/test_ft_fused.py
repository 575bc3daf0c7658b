"""Fused fast-path acceptance tests — the PR-7 "kill the protection tax" layer.

  * the single-pass fused dispatch (ref backend: packed-meta mask-pair
    epilogue) is bit-identical to the two-pass engine for every site shape
    class — N-D projections, both MoE expert einsum specs, LM-head streamed
    chunks — across modes, with and without a RepairPlan (remap + prune),
    per-site plan dicts, over-capacity fault sets, and int datapaths;
  * the Pallas kernel (interpret mode) at bm = bn = 1 — where tile
    granularity IS element granularity — matches ``hyca_matmul`` bit-exactly
    including the in-kernel plan epilogue (col_map gather + prune zeroing),
    and the batched expert kernel matches the vmapped engine path;
  * fused dispatch never retraces on fault-table OR plan swaps;
  * ``FTContext.einsum`` validates the spec before anything else (same
    clear error on every dispatch path);
  * ``build_ftcontext`` validates explicit ``fused_block`` tuples against
    backend tile constraints at build time;
  * the block autotuner: heuristic defaults, cache round-trip through
    ``REPRO_AUTOTUNE_DIR``, and ``resolve_block`` hit/miss behavior;
  * fallbacks are visible: the kernel backends route int datapaths to
    twopass and count it in ``site_fallback_total`` (with a one-time
    warning) — and with ``dispatch="fused"`` on this backend, tracing a
    decode step of ALL TEN registry configs records ZERO fallbacks.
"""
import dataclasses
import functools
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.engine import (
    FaultState,
    HyCAConfig,
    RepairPlan,
    empty_fault_state,
    fault_state_from_map,
    hyca_matmul,
    identity_plan,
)
from repro.core.ftcontext import EINSUM_SPECS, ProtectPolicy, build_ftcontext
from repro.core.redundancy import DPPUConfig
from repro.kernels import autotune
from repro.models.layers import streamed_cross_entropy
from repro.models.lm import decode_step, init_cache, init_params
from repro.obs import reset_site_fallbacks, site_fallback_total

ROWS = COLS = 8


def _hyca(mode: str, dppu: int = 8) -> HyCAConfig:
    return HyCAConfig(
        rows=ROWS, cols=COLS, dppu=DPPUConfig(size=dppu, group_size=min(8, dppu)),
        mode=mode,
    )


def _state(n_faults: int, seed: int) -> FaultState:
    rng = np.random.default_rng(seed)
    fmap = np.zeros((ROWS, COLS), bool)
    fmap.reshape(-1)[rng.choice(ROWS * COLS, size=n_faults, replace=False)] = True
    return fault_state_from_map(fmap, max_faults=max(n_faults, 1), rng=rng)


def _plan(seed: int) -> RepairPlan:
    """Non-trivial plan: a rolled column permutation + a sparse prune mask."""
    rng = np.random.default_rng(seed)
    cm = np.roll(np.arange(COLS), 1 + seed % (COLS - 1)).astype(np.int32)
    pr = np.zeros((ROWS, COLS), bool)
    pr.reshape(-1)[rng.choice(ROWS * COLS, size=5, replace=False)] = True
    return RepairPlan(jnp.asarray(cm), jnp.asarray(pr))


def _bits_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    if a.dtype.kind == "f":
        return np.array_equal(a.view(np.uint32 if a.itemsize == 4 else np.uint16),
                              b.view(np.uint32 if b.itemsize == 4 else np.uint16))
    return np.array_equal(a, b)


@pytest.fixture
def rng():
    return np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _isolate_autotune(tmp_path, monkeypatch):
    """Point the autotune cache at a throwaway dir: tests must neither read
    nor write the committed experiments/autotune cache."""
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path / "autotune"))
    autotune.reset_cache()
    yield
    autotune.reset_cache()


# --------------------------------------------------------------------------- #
# fused (ref backend) == twopass, bit for bit, across site shape classes
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["protected", "unprotected"])
@pytest.mark.parametrize("n_faults,planned", [(4, False), (12, False), (12, True)])
def test_fused_ref_matmul_bitexact_nd(rng, mode, n_faults, planned):
    """N-D projections (attention/SSM/RWKV shapes): the single-pass epilogue
    must equal the engine's corrupt + DPPU-overwrite + prune sequence even
    past DPPU capacity and under a remap+prune plan."""
    state = _state(n_faults, seed=n_faults)
    plan = _plan(3) if planned else None
    hyca = _hyca(mode)
    tw = build_ftcontext(state, hyca, dispatch="twopass", plan=plan)
    fu = build_ftcontext(state, hyca, dispatch="fused", plan=plan)
    assert fu.fused_backend == "ref"  # this suite runs on CPU
    for shape in [(4, 64), (3, 5, 64), (2, 1, 4, 64)]:
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        w = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
        a = tw.matmul(x, w, site="attn.qkv")
        b = fu.matmul(x, w, site="attn.qkv")
        assert _bits_equal(a, b), shape


@pytest.mark.parametrize("spec", EINSUM_SPECS)
@pytest.mark.parametrize("planned", [False, True])
def test_fused_ref_einsum_bitexact(rng, spec, planned):
    """Both MoE expert einsum patterns: one clean einsum + one broadcast
    epilogue must equal the vmapped two-pass engine, bit for bit."""
    state = _state(12, seed=5)  # over capacity: unrepaired faults corrupt
    plan = _plan(1) if planned else None
    hyca = _hyca("protected")
    tw = build_ftcontext(state, hyca, dispatch="twopass", plan=plan)
    fu = build_ftcontext(state, hyca, dispatch="fused", plan=plan)
    b, e, c = 2, 4, 3
    din, dout = (64, 48) if spec == EINSUM_SPECS[0] else (48, 64)
    x = jnp.asarray(rng.standard_normal((b, e, c, din)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, din, dout)), jnp.float32)
    assert _bits_equal(
        tw.einsum(spec, x, w, site="moe.expert"),
        fu.einsum(spec, x, w, site="moe.expert"),
    )


def test_fused_ref_per_site_plan_dict(rng):
    """{site: RepairPlan} dicts resolve identically on both dispatches —
    including a site the dict does not name (plan=None for it)."""
    state = _state(12, seed=9)
    plans = {"ffn": _plan(2), "moe.expert": _plan(4)}
    hyca = _hyca("protected")
    tw = build_ftcontext(state, hyca, dispatch="twopass", plan=plans)
    fu = build_ftcontext(state, hyca, dispatch="fused", plan=plans)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    for site in ("ffn", "attn.out"):  # planned and unplanned
        assert _bits_equal(tw.matmul(x, w, site=site), fu.matmul(x, w, site=site))
    xe = jnp.asarray(rng.standard_normal((2, 3, 4, 64)), jnp.float32)
    we = jnp.asarray(rng.standard_normal((3, 64, 16)), jnp.float32)
    assert _bits_equal(
        tw.einsum("becd,edf->becf", xe, we, site="moe.expert"),
        fu.einsum("becd,edf->becf", xe, we, site="moe.expert"),
    )


def test_fused_ref_int_datapath_bitexact(rng):
    """The int8 datapath (int32 accumulator stuck-at model) stays exact on
    the ref backend's integer epilogue branch."""
    state = _state(12, seed=2)
    hyca = _hyca("protected")
    tw = build_ftcontext(state, hyca, dispatch="twopass")
    fu = build_ftcontext(state, hyca, dispatch="fused")
    x = jnp.asarray(rng.integers(-8, 8, (7, 32)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (32, 24)), jnp.int8)
    assert _bits_equal(tw.matmul(x, w, site="ffn"), fu.matmul(x, w, site="ffn"))


def test_fused_ref_head_streamed_chunks_bitexact(rng):
    """The LM-head streamed-chunk panels (layers.streamed_cross_entropy):
    fused and twopass must agree bit for bit on the loss — the head site's
    chunked (N, d) @ (d, V/n) panels route through the fused path."""
    state = _state(6, seed=3)
    hyca = _hyca("protected")
    tw = build_ftcontext(state, hyca, dispatch="twopass")
    fu = build_ftcontext(state, hyca, dispatch="fused")
    x = jnp.asarray(rng.standard_normal((2, 4, 32)), jnp.float32)
    table = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 60, (2, 4)), jnp.int32)
    a = streamed_cross_entropy(x, table, labels, n_chunks=4, true_vocab=60, ftc=tw)
    b = streamed_cross_entropy(x, table, labels, n_chunks=4, true_vocab=60, ftc=fu)
    assert _bits_equal(a, b)


def test_fused_identity_plan_bitexact_with_no_plan(rng):
    """identity_plan == plan=None on the fused path (the in-epilogue gather
    with an identity col_map and an all-false prune mask is a no-op)."""
    state = _state(4, seed=1)
    hyca = _hyca("protected")
    fu0 = build_ftcontext(state, hyca, dispatch="fused")
    fu1 = build_ftcontext(state, hyca, dispatch="fused", plan=identity_plan(ROWS, COLS))
    x = jnp.asarray(rng.standard_normal((4, 4, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    assert _bits_equal(fu0.matmul(x, w, site="ffn"), fu1.matmul(x, w, site="ffn"))


# --------------------------------------------------------------------------- #
# kernel parity (interpret mode): bm = bn = 1 makes tiles == elements
# --------------------------------------------------------------------------- #
def _interpret_ctx(state, hyca, *, block, plan=None):
    ctx = build_ftcontext(state, hyca, dispatch="fused", fused_block=block, plan=plan)
    return dataclasses.replace(ctx, fused_backend="interpret")


@pytest.mark.slow
@pytest.mark.parametrize("planned", [False, True])
def test_kernel_element_parity_with_engine(rng, planned):
    """At bm = bn = 1 the kernel's tile→PE map IS the engine's element map:
    the drain epilogue (stuck-at mux + plan prune) must reproduce
    ``hyca_matmul`` bit for bit, over-capacity faults included."""
    state = _state(12, seed=11)
    plan = _plan(6) if planned else None
    hyca = _hyca("protected")
    fu = _interpret_ctx(state, hyca, block=(1, 1, 64), plan=plan)
    x = jnp.asarray(rng.standard_normal((10, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 12)), jnp.float32)
    a = hyca_matmul(x, w, state, cfg=hyca, plan=plan)
    b = fu.matmul(x, w, site="ffn")
    assert _bits_equal(a, b)


@pytest.mark.slow
def test_kernel_element_parity_over_capacity_clamp(rng):
    """DPPU capacity clamping inside the kernel grids: with capacity 2 and
    12 faults, exactly the two leftmost FPT entries are repaired."""
    state = _state(12, seed=13)
    hyca = _hyca("protected", dppu=2)
    fu = _interpret_ctx(state, hyca, block=(1, 1, 32))
    x = jnp.asarray(rng.standard_normal((9, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 10)), jnp.float32)
    assert _bits_equal(hyca_matmul(x, w, state, cfg=hyca), fu.matmul(x, w, site="ffn"))


@pytest.mark.slow
def test_kernel_ragged_nd_padding(rng):
    """Ragged N-D shapes exercise the zero-pad + slice path around the
    kernel; all faults repaired → must equal the clean matmul exactly."""
    state = _state(4, seed=17)
    hyca = _hyca("protected")
    fu = _interpret_ctx(state, hyca, block=(8, 128, 128))
    x = jnp.asarray(rng.standard_normal((3, 7, 50)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((50, 65)), jnp.float32)
    a = hyca_matmul(x, w, state, cfg=hyca)
    b = fu.matmul(x, w, site="ssm.in")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("planned", [False, True])
def test_batched_kernel_matches_vmapped_engine(rng, planned):
    """ft_matmul_batched (expert axis in the kernel grid) vs the vmapped
    two-pass engine, element-granular blocks, both einsum specs."""
    state = _state(12, seed=19)
    plan = _plan(8) if planned else None
    hyca = _hyca("protected")
    tw = build_ftcontext(state, hyca, dispatch="twopass", plan=plan)
    fu = _interpret_ctx(state, hyca, block=(1, 1, 32), plan=plan)
    for spec in EINSUM_SPECS:
        din, dout = (32, 16) if spec == EINSUM_SPECS[0] else (16, 32)
        x = jnp.asarray(rng.standard_normal((2, 3, 4, din)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((3, din, dout)), jnp.float32)
        assert _bits_equal(
            tw.einsum(spec, x, w, site="moe.expert"),
            fu.einsum(spec, x, w, site="moe.expert"),
        ), spec


# --------------------------------------------------------------------------- #
# no retrace on fault-table / plan swaps under fused dispatch
# --------------------------------------------------------------------------- #
def test_fused_no_retrace_on_state_and_plan_swap(rng):
    state = _state(4, seed=23)
    hyca = _hyca("protected")
    ftc = build_ftcontext(state, hyca, dispatch="fused", plan=identity_plan(ROWS, COLS))
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    traces = 0

    @jax.jit
    def run(ftc, x, w):
        nonlocal traces
        traces += 1
        return ftc.einsum(
            "becd,edf->becf",
            x.reshape(1, 2, 2, 64), w.reshape(2, 64, 48)[:, :, :48],
            site="moe.expert",
        ) + ftc.matmul(x, w, site="ffn").sum()

    # swaps keep leaf SHAPES fixed (same max_faults) — only values change
    run(ftc, x, w)
    run(ftc.with_state(_state(4, seed=29)), x, w)          # new fault table
    run(ftc.with_plan(_plan(5)), x, w)                     # new plan values
    run(ftc.with_state(empty_fault_state(4)).with_plan(_plan(7)), x, w)
    assert traces == 1, "fused dispatch retraced on a leaf-only swap"


# --------------------------------------------------------------------------- #
# einsum spec validation + fused_block validation
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dispatch", ["plain", "twopass", "fused"])
def test_einsum_rejects_unsupported_spec_before_shape_access(rng, dispatch):
    """The spec check runs FIRST: a 3-D x (which the old obs-record path
    would have indexed as 4-D) still gets the clear ValueError, on every
    dispatch path and even for unprotected sites."""
    ftc = build_ftcontext(_state(2, seed=0), _hyca("protected"), dispatch=dispatch,
                          policy=ProtectPolicy(sites=frozenset({"ffn"})))
    x3 = jnp.zeros((2, 3, 4), jnp.float32)
    w = jnp.zeros((4, 5), jnp.float32)
    with pytest.raises(ValueError, match="expert-matmul patterns"):
        ftc.einsum("bij,jk->bik", x3, w, site="moe.expert")


def test_build_validates_fused_block():
    state, hyca = _state(2, seed=0), _hyca("protected")
    with pytest.raises(ValueError, match="fused_block"):
        build_ftcontext(state, hyca, dispatch="fused", fused_block=(0, 128, 128))
    with pytest.raises(ValueError, match="fused_block"):
        build_ftcontext(state, hyca, dispatch="fused", fused_block=(128, 128))
    with pytest.raises(ValueError, match="fused_block"):
        build_ftcontext(state, hyca, dispatch="fused", fused_block="wide")
    # "auto" and explicit well-formed tuples build fine
    assert build_ftcontext(state, hyca, dispatch="fused").fused_block == "auto"
    ctx = build_ftcontext(state, hyca, dispatch="fused", fused_block=(64, 128, 128))
    assert ctx.fused_block == (64, 128, 128)


def test_pallas_tile_alignment_rejected():
    """The compiled-TPU constraint check (bm % 8, bn/bk % 128) — exercised
    directly since this host builds ref-backend contexts."""
    with pytest.raises(ValueError, match="tile constraints"):
        autotune.validate_fused_block((12, 128, 128), backend="pallas")
    with pytest.raises(ValueError, match="tile constraints"):
        autotune.validate_fused_block((128, 64, 128), backend="pallas")
    assert autotune.validate_fused_block((8, 256, 128), backend="pallas") == (8, 256, 128)
    # ref/interpret backends skip the alignment constraint, not the shape one
    assert autotune.validate_fused_block((1, 1, 64), backend="ref") == (1, 1, 64)


# --------------------------------------------------------------------------- #
# block autotuner
# --------------------------------------------------------------------------- #
def test_default_block_heuristic():
    assert autotune.default_block(4, 512, 64) == (8, 128, 128)    # decode row
    assert autotune.default_block(100, 512, 64) == (104, 128, 128)
    # a decode step with its prompt chunk: one row block, each weight read once
    assert autotune.default_block(320, 512, 64, dtype=jnp.bfloat16) == (320, 128, 128)
    assert autotune.default_block(512, 512, 64) == (512, 128, 128)
    assert autotune.default_block(513, 512, 64) == (128, 128, 128)
    assert autotune.default_block(4096, 512, 64) == (128, 128, 128)


@pytest.mark.parametrize("pe_row", [0, 1])
def test_one_row_block_places_unrepaired_faults_of_a_200_row_matmul(rng, pe_row):
    """Up to ``ONE_BLOCK_ROWS`` rows run as one row block, so every row of
    an unprotected 200-row matmul maps to PE row 0 in the kernel: a stuck-at
    fault at PE (0, 0) corrupts columns 0-127 of every row, one at PE (1, 0)
    nothing (128-row blocks would put rows 128-199 on PE row 1)."""
    x = jnp.asarray(rng.standard_normal((200, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    fault = FaultState(jnp.asarray([[pe_row, 0]], jnp.int32), jnp.asarray([30], jnp.int32),
                       jnp.asarray([1], jnp.int32))

    def run(state):
        ctx = build_ftcontext(state, _hyca("unprotected"), dispatch="fused")
        ctx = dataclasses.replace(ctx, fused_backend="interpret")
        return np.asarray(ctx.matmul(x, w, site="ffn"))

    assert autotune.default_block(200, 256, 64) == (200, 128, 128)
    bad = run(fault) != run(empty_fault_state(1))
    assert not bad[:, 128:].any()
    if pe_row == 0:
        assert bad[:, :128].any(axis=1).all()
    else:
        assert not bad.any()


def test_resolve_block_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.reset_cache()
    # miss → heuristic
    assert autotune.resolve_block(4, 512, 64, backend="interpret") == (8, 128, 128)
    # persist an entry, drop the in-memory cache, resolve again → hit
    path = autotune.save_cache(
        {"4x512x64:float32:interpret": {"block": [16, 256, 128], "ms": 0.5}}
    )
    autotune.reset_cache()
    assert autotune.resolve_block(4, 512, 64, backend="interpret") == (16, 256, 128)
    # other shapes / backends still miss to the heuristic
    assert autotune.resolve_block(4, 512, 64, backend="pallas") == (8, 128, 128)
    with open(path) as f:
        assert "4x512x64:float32:interpret" in json.load(f)


def test_corrupt_cache_is_ignored(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.reset_cache()
    cache_file = tmp_path / "ft_matmul.json"
    cache_file.write_text("{not json")
    assert autotune.resolve_block(4, 512, 64, backend="interpret") == (8, 128, 128)
    cache_file.write_text(json.dumps({"4x512x64:float32:interpret": {"block": [0, -1]}}))
    autotune.reset_cache()
    assert autotune.resolve_block(4, 512, 64, backend="interpret") == (8, 128, 128)


@pytest.mark.slow
def test_autotune_block_measures_and_persists(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.reset_cache()
    blk, ms = autotune.autotune_block(
        8, 128, 128, backend="interpret",
        candidates=((8, 128, 128), (16, 128, 128)),
        rows=ROWS, cols=COLS, repeats=1, steps=1,
    )
    assert blk in ((8, 128, 128), (16, 128, 128)) and ms > 0
    autotune.reset_cache()
    assert autotune.resolve_block(8, 128, 128, backend="interpret") == blk


# --------------------------------------------------------------------------- #
# fallback visibility
# --------------------------------------------------------------------------- #
def test_int_dtype_kernel_fallback_is_counted(rng):
    """Forcing a kernel backend with an int datapath must fall back to
    twopass — visibly: one warning, counted in site_fallback_total."""
    reset_site_fallbacks()
    fu = _interpret_ctx(_state(4, seed=31), _hyca("protected"), block=(1, 1, 16))
    x = jnp.asarray(rng.integers(-4, 4, (4, 16)), jnp.int8)
    w = jnp.asarray(rng.integers(-4, 4, (16, 8)), jnp.int8)
    tw = build_ftcontext(_state(4, seed=31), _hyca("protected"), dispatch="twopass")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = fu.matmul(x, w, site="ffn")
        fu.matmul(x, w, site="ffn")  # second call: counted, NOT re-warned
    assert _bits_equal(out, tw.matmul(x, w, site="ffn"))
    assert site_fallback_total() == {("ffn", "int-dtype-kernel"): 2}
    assert sum(issubclass(c.category, RuntimeWarning) for c in caught) == 1
    reset_site_fallbacks()
    assert site_fallback_total() == {}


@pytest.mark.slow
def test_zero_fallbacks_across_all_registry_configs():
    """The acceptance bar: with dispatch="fused" on this backend, tracing a
    decode step of every registry config records ZERO twopass fallbacks —
    every protected site lowers through the fused path."""
    reset_site_fallbacks()
    state = _state(4, seed=37)
    hyca = _hyca("protected")
    for arch in ARCH_IDS:
        cfg = dataclasses.replace(get_smoke_config(arch), dtype=jnp.float32)
        ftc = build_ftcontext(state, hyca, dispatch="fused")
        params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
        cache = init_cache(cfg, 2, 8)
        tok = jnp.zeros((2, 1), jnp.int32)
        jax.eval_shape(
            lambda p, c, t, ftc=ftc, cfg=cfg: decode_step(
                p, cfg, c, {"token": t}, ftc=ftc
            ),
            params, cache, tok,
        )
    assert site_fallback_total() == {}, (
        f"silent twopass fallbacks under dispatch='fused': {site_fallback_total()}"
    )


# --------------------------------------------------------------------------- #
# operand dtype: bf16 operands enter the kernel as bf16, accumulate in f32
# --------------------------------------------------------------------------- #
def _meta_and_mask(case: str, bm: int, bn: int):
    """Packed meta grid and prune mask for one fault case: ``unrepaired``
    (faults on an unprotected array: the stuck-at mux engages), ``repaired``
    (every fault within DPPU capacity) or ``pruned`` (over capacity under a
    remap + prune RepairPlan)."""
    from repro.core.engine import META_PRUNE_SHIFT, fault_meta_grid

    mode, n_faults, plan = {
        "unrepaired": ("unprotected", 12, None),
        "repaired": ("protected", 4, None),
        "pruned": ("protected", 12, _plan(3)),
    }[case]
    meta = fault_meta_grid(_state(n_faults, seed=n_faults), _hyca(mode), plan)
    if plan is None:
        return meta, None
    keep = jnp.where((meta >> META_PRUNE_SHIFT) & 1 > 0, jnp.int32(0), jnp.int32(-1))
    return meta, jnp.tile(keep, (bm // ROWS, bn // COLS))


@pytest.mark.parametrize("case", ["unrepaired", "repaired", "pruned"])
@pytest.mark.parametrize("kernel", ["ft_matmul", "ft_matmul_batched"])
def test_kernel_bf16_operands_equal_f32_upcast(rng, kernel, case):
    """bf16 operands give output bit-equal to the same values up-cast to
    f32: the products are exact in the f32 accumulator, and the stuck-at
    mux and the prune mask act on the same accumulator bits.

    The operands are small integers, so every partial sum is exact too:
    under interpret mode the kernel's dot is XLA:CPU's, whose bf16 dot sums
    a row in another order than its f32 dot, and random operands would
    differ by that reassociation alone (an ulp or so), not by anything the
    kernel does with the operand dtype."""
    from repro.kernels import ft_matmul as fm

    bm, bn, bk = 16, 128, 128
    meta, pmask = _meta_and_mask(case, bm, bn)
    lead = (2,) if kernel == "ft_matmul_batched" else ()
    # 8 x 8 output tiles: every PE of the 8 x 8 array owns one
    x = jnp.asarray(rng.integers(-8, 9, lead + (8 * bm, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.integers(-8, 9, lead + (256, 8 * bn)), jnp.bfloat16)
    run = functools.partial(getattr(fm, kernel), bm=bm, bn=bn, bk=bk, interpret=True)
    got = run(x, w, meta, pmask)
    want = run(x.astype(jnp.float32), w.astype(jnp.float32), meta, pmask)
    assert got.dtype == jnp.float32
    assert _bits_equal(got, want)
    clean = run(x, w, jnp.zeros_like(meta), None)
    n_moved = int((np.asarray(got) != np.asarray(clean)).sum())
    if case == "repaired":
        assert n_moved == 0
    else:
        assert n_moved > 0, "the fault epilogue did not engage"


def _walk_eqns(jaxpr):
    """Every equation of a closed jaxpr and of the jaxprs it calls, without
    descending into a ``pallas_call``'s kernel body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


@pytest.mark.parametrize("op", ["matmul", "einsum"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_hands_operands_over_in_their_dtype(rng, op, dtype):
    """In the jaxpr of the interpret-backend fused dispatch, the kernel
    receives the operands in their own dtype, and no weight-sized array is
    converted to f32 on the way; the call ledger names that dtype."""
    from repro.obs.counters import trace_site_calls

    ctx = _interpret_ctx(_state(4, seed=3), _hyca("protected"), block=(16, 128, 128))
    if op == "matmul":
        x = jnp.asarray(rng.standard_normal((4, 256)), dtype)
        w = jnp.asarray(rng.standard_normal((256, 384)), dtype)
        fn = lambda c, x, w: c.matmul(x, w, site="ffn")  # noqa: E731
    else:
        x = jnp.asarray(rng.standard_normal((1, 2, 4, 256)), dtype)
        w = jnp.asarray(rng.standard_normal((2, 256, 384)), dtype)
        fn = lambda c, x, w: c.einsum(EINSUM_SPECS[0], x, w, site="moe.expert")  # noqa: E731
    jaxpr = jax.make_jaxpr(functools.partial(fn, ctx))(x, w)
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    # operands: scalar-prefetch meta, x, w, prune mask
    assert [v.aval.dtype for v in kernels[0].invars[1:3]] == [jnp.dtype(dtype)] * 2
    upcasts = [
        e for e in eqns
        if e.primitive.name == "convert_element_type"
        and e.params["new_dtype"] == jnp.float32
        and e.invars[0].aval.dtype != jnp.float32
        and e.outvars[0].aval.size >= w.size
    ]
    assert upcasts == []
    ledger = trace_site_calls(fn, ctx, x, w)
    assert [c.operand_dtype for c in ledger] == [jnp.dtype(dtype).name]


def test_starcoder2_decode_step_feeds_every_protected_call_bf16():
    """The batch benchmark's decode step (starcoder2-3b, first 15 layers, 64
    slots): every protected call of the step multiplies bf16 operands."""
    from repro.obs.counters import trace_site_calls

    cfg = dataclasses.replace(get_config("starcoder2-3b"), n_layers=15)
    ftc = build_ftcontext(_state(4, seed=41), _hyca("protected"), dispatch="fused")
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, 64, 16))
    tok = jax.ShapeDtypeStruct((64, 1), jnp.int32)
    ledger = trace_site_calls(
        lambda c, p, ch, t: decode_step(p, cfg, ch, {"token": t}, ftc=c), ftc, params, cache, tok)
    assert all(c.protected for c in ledger)
    # q, k, v, out, up, down per layer, and the tied head
    assert sum(c.count for c in ledger) == 6 * 15 + 1
    assert {c.operand_dtype for c in ledger} == {"bfloat16"}


@pytest.mark.parametrize("dtype,m,want_bm", [
    (jnp.float32, 4, 8), (jnp.bfloat16, 4, 16), (jnp.bfloat16, 17, 32),
    (jnp.bfloat16, 64, 64), (jnp.float32, 100, 104), (jnp.bfloat16, 100, 112),
])
def test_default_block_follows_operand_sublane_tile(dtype, m, want_bm):
    assert autotune.default_block(m, 512, 64, dtype=dtype) == (want_bm, 128, 128)
    # a cache miss resolves to the same heuristic on every backend
    for backend in ("pallas", "interpret"):
        assert autotune.resolve_block(m, 512, 64, dtype=dtype, backend=backend) == (want_bm, 128, 128)


def test_resolve_block_keys_on_operand_dtype(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.reset_cache()
    autotune.save_cache({"64x3072x3072:bfloat16:pallas": {"block": [64, 256, 128], "ms": 1.0}})
    autotune.reset_cache()
    assert autotune.resolve_block(64, 3072, 3072, dtype=jnp.bfloat16, backend="pallas") == (64, 256, 128)
    # the f32 key of the same shape misses to the heuristic
    assert autotune.resolve_block(64, 3072, 3072, dtype=jnp.float32, backend="pallas") == (64, 128, 128)


def test_pallas_bf16_block_needs_16_row_sublane_tile(rng):
    """bm 8 fits the f32 tile but not bf16's: on the pallas backend it is
    refused for bf16 operands when the call is dispatched, with the same
    error as validate_fused_block; f32 operands keep it."""
    with pytest.raises(ValueError, match="tile constraints"):
        autotune.validate_fused_block((8, 128, 128), backend="pallas", dtype=jnp.bfloat16)
    assert autotune.validate_fused_block((16, 128, 128), backend="pallas",
                                         dtype=jnp.bfloat16) == (16, 128, 128)
    assert autotune.validate_fused_block((8, 128, 128), backend="pallas") == (8, 128, 128)
    assert autotune.validate_fused_block((8, 128, 128), backend="interpret",
                                         dtype=jnp.bfloat16) == (8, 128, 128)
    ctx = dataclasses.replace(
        build_ftcontext(_state(2, seed=0), _hyca("protected"), dispatch="fused",
                        fused_block=(8, 128, 128)),
        fused_backend="pallas",
    )
    x = jax.ShapeDtypeStruct((4, 256), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="tile constraints"):
        jax.eval_shape(lambda x, w: ctx.matmul(x, w, site="ffn"), x, w)

