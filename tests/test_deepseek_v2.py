"""DeepSeek-V2's block in the program: latent attention without a query
LoRA, YaRN rope, unnormalised top-k gates and an expert layer that holds a
share of the experts; and the routing load an MoE server step reports."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import moe as moe_mod
from repro.models.attention import mla_init
from repro.models.layers import YaRN, apply_rope, yarn_freqs
from repro.models.lm import decode_step, forward, init_cache, init_params
from repro.models.moe import MoEConfig, moe_forward, moe_init

ARCH = "deepseek-v2-lite"


def _f32(cfg):
    return dataclasses.replace(cfg, dtype=jnp.float32)


# --------------------------------------------------------------------------- #
# rope
# --------------------------------------------------------------------------- #
def _apply_rope_before(x, positions, theta=10000.0):
    """``layers.apply_rope`` as it was before rope scaling, frozen."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("theta", [10000.0, 999999.4420358813])
def test_plain_rope_is_bit_identical_to_before(dtype, theta):
    x = jax.random.normal(jax.random.key(0), (3, 17, 4, 64), dtype)
    pos = jnp.broadcast_to(jnp.arange(17) + 100, (3, 17))
    now = lambda a, p: apply_rope(a, p, theta)              # noqa: E731
    before = lambda a, p: _apply_rope_before(a, p, theta)   # noqa: E731
    for wrap in (lambda f: f, jax.jit):                     # eager, and as one program
        np.testing.assert_array_equal(np.asarray(wrap(now)(x, pos), np.float32),
                                      np.asarray(wrap(before)(x, pos), np.float32))


def test_yarn_keeps_fast_pairs_and_stretches_slow_ones():
    s = YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0)
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    got = np.asarray(yarn_freqs(64, 10000.0, s))
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)       # below low = 10
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)  # from high = 23
    assert np.all((got[10:23] < plain[10:23]) & (got[10:23] > plain[10:23] / 40))
    assert np.all(np.diff(got) < 0)


# --------------------------------------------------------------------------- #
# latent attention
# --------------------------------------------------------------------------- #
def test_mla_without_a_query_lora_has_one_query_projection():
    mla = get_config(ARCH).mla
    shapes = jax.eval_shape(lambda: mla_init(jax.random.key(0), mla))
    assert set(shapes) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert shapes["wq"].shape == (2048, 16 * (128 + 64))
    assert shapes["wkv_a"].shape == (2048, 512 + 64)
    lora = get_config("minicpm3-4b").mla        # the query LoRA path is as it was
    assert set(jax.eval_shape(lambda: mla_init(jax.random.key(0), lora))) == {
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"}


@pytest.mark.parametrize("arch", [ARCH, "minicpm3-4b"])
def test_absorbed_decode_follows_the_full_forward(arch):
    """Decoding token by token through the latent cache (absorbed W_uk, W_uv)
    gives the forward's logits over the whole sequence, YaRN included."""
    cfg = _f32(get_smoke_config(arch))
    if cfg.moe is not None:      # the forward's expert capacity must not bind
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = init_params(jax.random.key(1), cfg)
    toks = jax.random.randint(jax.random.key(2), (2, 10), 0, cfg.vocab)
    full, _ = forward(params, cfg, {"tokens": toks})
    cache = init_cache(cfg, 2, 16, dtype=jnp.float32)
    for t in range(10):
        lg, cache = decode_step(params, cfg, cache, {"token": toks[:, t:t + 1]})
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, t]), rtol=0, atol=2e-5)


# --------------------------------------------------------------------------- #
# experts
# --------------------------------------------------------------------------- #
def _topk_dispatch_before(gates, top_k, capacity):
    """``moe._topk_dispatch`` as it was before ``norm_topk`` and ``held``, frozen."""
    b, g, e = gates.shape
    topv, topi = jax.lax.top_k(gates, top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(jnp.moveaxis(topi, -1, 0), e, dtype=jnp.float32)
    flat = jnp.moveaxis(onehot, 0, 1).reshape(b, top_k * g, e)
    pos = jnp.moveaxis(jnp.cumsum(flat, axis=1).reshape(b, top_k, g, e), 1, 0) - 1.0
    keep = (pos < capacity) * onehot
    pos_ne = (pos * onehot).sum(0)
    keep_ne = keep.sum(0)
    gate_ne = jnp.einsum("bgk,kbge->bge", topv, onehot)
    dispatch = keep_ne[..., None] * jax.nn.one_hot(pos_ne.astype(jnp.int32), capacity, dtype=jnp.float32)
    return dispatch, dispatch * gate_ne[..., None]


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "granite-moe-3b-a800m"])
def test_renormalising_layers_are_unchanged(arch, monkeypatch):
    cfg = get_smoke_config(arch).moe
    assert cfg.norm_topk and cfg.held is None
    p = moe_init(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model), jnp.float32)
    now = moe_forward(x, p, cfg)
    monkeypatch.setattr(moe_mod, "_topk_dispatch",
                        lambda g, k, c, **kw: _topk_dispatch_before(g, k, c))
    before = moe_forward(x, p, cfg)
    for a, b in zip(now, before):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unnormalised_gates_are_the_chosen_probabilities_times_the_scale():
    gates = jax.nn.softmax(jax.random.normal(jax.random.key(5), (2, 6, 16)), -1)
    _, combine = moe_mod._topk_dispatch(gates, 6, 8, norm_topk=False)
    _, renorm = moe_mod._topk_dispatch(gates, 6, 8)
    w = np.asarray(combine.sum(-1))
    top = np.sort(np.asarray(gates), -1)[..., -6:]
    np.testing.assert_allclose(np.sort(w, -1)[..., -6:], top, rtol=1e-6)
    assert (w > 0).sum(-1).max() == 6 and w.sum(-1).max() < 1
    np.testing.assert_allclose(np.asarray(renorm.sum((-1, -2))), 1.0, rtol=1e-6)
    # one token a row: the layer's routed output is its renormalised one
    # times the chosen experts' probability mass
    cfg = MoEConfig(d_model=16, n_experts=16, top_k=6, d_expert=8, norm_topk=False)
    p = moe_init(jax.random.key(6), cfg)
    x = jax.random.normal(jax.random.key(7), (4, 1, 16))
    renormed = moe_forward(x, p, dataclasses.replace(cfg, norm_topk=True))[0]
    probs = jax.nn.softmax(x @ p["router"], -1)
    mass = np.sort(np.asarray(probs), -1)[..., -6:].sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(moe_forward(x, p, cfg)[0]), mass * np.asarray(renormed),
                               rtol=1e-5, atol=1e-7)


def test_a_share_holds_its_experts_and_routes_over_all():
    whole = MoEConfig(d_model=16, n_experts=16, top_k=6, d_expert=8)
    share = dataclasses.replace(whole, held=(4, 8))
    pw, ps = moe_init(jax.random.key(8), whole), moe_init(jax.random.key(8), share)
    assert ps["router"].shape == (16, 16) and ps["gate"].shape == (4, 16, 8)
    for k in ("gate", "up", "down"):
        np.testing.assert_array_equal(np.asarray(ps[k]), np.asarray(pw[k][4:8]))
    with pytest.raises(ValueError, match="held"):
        MoEConfig(d_model=16, n_experts=16, top_k=6, d_expert=8, held=(8, 20))


@pytest.mark.parametrize("held", [None, (0, 2), (2, 4), (4, 6), (6, 8)])
def test_decode_counts_the_routed_pairs_of_active_rows(held):
    """Each row routes top_k pairs in each MoE layer (``moe_load`` in the new
    cache); a share counts the pairs to its own experts, and the shares'
    counts add up to the whole layer's, row by row."""
    base = _f32(get_smoke_config(ARCH))
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe, held=held))
    params = init_params(jax.random.key(9), cfg)
    cache = init_cache(cfg, 6, 8, dtype=jnp.float32)
    tok = jax.random.randint(jax.random.key(10), (6, 1), 0, cfg.vocab)
    n_moe = cfg.n_layers - cfg.first_k_dense
    _, new = decode_step(params, cfg, cache, {"token": tok})
    load = np.asarray(new["moe_load"])
    assert load.dtype == np.int32 and load.shape == (n_moe, 6, cfg.moe.n_held)
    assert set(np.unique(load)) <= {0, 1}
    if held is None:
        assert (load.sum(-1) == cfg.moe.top_k).all()
    else:
        shares = []
        for lo in range(0, 8, 2):
            c = dataclasses.replace(base, moe=dataclasses.replace(base.moe, held=(lo, lo + 2)))
            shares.append(np.asarray(decode_step(init_params(jax.random.key(9), c), c,
                                                 init_cache(c, 6, 8, dtype=jnp.float32),
                                                 {"token": tok})[1]["moe_load"]))
        assert (np.concatenate(shares, -1).sum(-1) == cfg.moe.top_k).all()
        assert any((load == sh).all() for sh in shares)
    assert "moe_load" not in init_cache(_f32(get_smoke_config("qwen1.5-0.5b")), 6, 8)


def test_fused_reference_dispatch_runs_bf16_experts_in_a_layer_scan():
    """Off the chip the fused dispatch's batched expert einsum takes bf16
    operands inside the layer scan (XLA:CPU has no batched bf16 x bf16 ->
    f32 dot, so the form computes in f32), protected equal to off."""
    from repro.core.engine import HyCAConfig, empty_fault_state, identity_plan
    from repro.core.ftcontext import build_ftcontext
    from repro.core.redundancy import DPPUConfig

    cfg = get_smoke_config(ARCH)
    hy = HyCAConfig(rows=8, cols=8, dppu=DPPUConfig(size=4, group_size=4), mode="unprotected")
    ftc = build_ftcontext(empty_fault_state(64), hy, dispatch="fused", plan=identity_plan(8, 8))
    assert ftc.fused_backend == "ref"
    params = init_params(jax.random.key(11), cfg)
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, {"token": t}, ftc=ftc))
    lg, _ = step(params, init_cache(cfg, 4, 8), jnp.zeros((4, 1), jnp.int32))
    want, _ = decode_step(params, cfg, init_cache(cfg, 4, 8), {"token": jnp.zeros((4, 1), jnp.int32)})
    assert lg.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(lg, np.float32), np.asarray(want, np.float32),
                               rtol=0, atol=0.05 * float(jnp.abs(want).max()))


# --------------------------------------------------------------------------- #
# the server step
# --------------------------------------------------------------------------- #
class _Span:
    """A stand-in profiler span that records its metadata."""
    made: list = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, dict(attrs)
        _Span.made.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def is_enabled(self):
        return True

    def set_metadata(self, **attrs):
        self.attrs.update(attrs)


@pytest.mark.parametrize("arch,counters", [(ARCH, False), (ARCH, True), ("qwen1.5-0.5b", False)])
def test_server_step_records_the_routing_load(arch, counters, monkeypatch):
    from repro.serving import FaultTolerantServer, ServerConfig
    from repro.serving import server as server_mod

    monkeypatch.setattr(server_mod, "span", _Span)
    _Span.made = []
    srv = FaultTolerantServer(ServerConfig(arch=arch, n_slots=4, smax=32, dispatch="fused",
                                           counters=counters))
    srv.submit(np.arange(6) + 3, 4)
    srv.submit(np.arange(3) + 5, 3)
    for _ in range(4):
        srv.step()
    roots = [s for s in _Span.made if s.name == "server.step"]
    assert len(roots) == 4 and all(r.attrs["active"] == 2 for r in roots)
    # step 0: the 6-token prompt rides as the chunk, the other slot feeds one
    # prompt token; step 1: the other slot's last 2 as the chunk, beside one
    # generated token; then one token a slot
    assert [r.attrs["prefill_tokens"] for r in roots] == [6, 2, 0, 0]
    fed = [7, 3, 2, 2]
    if arch == ARCH:
        cfg = srv.lm
        pairs = cfg.moe.top_k * (cfg.n_layers - cfg.first_k_dense)
        assert [r.attrs["moe_pairs"] for r in roots] == [n * pairs for n in fed]
        assert all(1 <= r.attrs["moe_max_load"] <= n for r, n in zip(roots, fed))
        last = np.asarray(srv.cache["moe_load"])          # every slot's row; two are busy
        assert last.shape == (cfg.n_layers - cfg.first_k_dense, 4, cfg.moe.n_held)
        assert last.sum() >= roots[-1].attrs["moe_pairs"]
    else:
        assert not any("moe_pairs" in r.attrs for r in roots) and "moe_load" not in srv.cache
