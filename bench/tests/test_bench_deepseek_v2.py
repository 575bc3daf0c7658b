"""The deepseek_v2 family: its mapping, its float32 reference, its counts and
its readers, against the served program at CPU sizes.  ``correct`` on the
chip rests on the reference; the per-layer metrics of the deepseek-v2-lite
cell rest on the counts."""
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import families, harness, layer_metrics, trace_reduce  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.reference import deepseek_v2 as ref  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

CELL = "deepseek-v2-lite.batch.protected"
SEED = 2**31 + 29
V5E = PEAKS["TPU v5 lite"]
MS = 1e-3


def _published() -> dict:
    return json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite.json").read_text())


def small_config(held: int = 4, width: int = 8) -> dict:
    """The cell's configuration file at a size the CPU runs in seconds:
    every width cut, the router ``width`` experts wide with ``held`` of them
    here, YaRN as published."""
    config = _published()
    config.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                  num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3,
                  vocab_size=512, num_experts_per_tok=2, n_routed_experts=held,
                  n_slots=4, smax=64)
    config["reduced"] = dict(config["reduced"], n_routed_experts=width)
    return config


def small_cell(held: int = 4, width: int = 8) -> harness.Cell:
    """The cell at CPU size: the mix's shape, protection and limit as
    committed, on an 8x8 array with faults the DPPU repairs."""
    cell = harness.load_cell(CELL)
    cell.config = small_config(held, width)
    t = cell.traffic
    t.update(block=16, warm_steps=10, follow_s=10.0, check_requests=4)
    t["prompt"] = dict(t["prompt"], median=8, min=2, max=24)
    t["output"] = dict(t["output"], median=8, min=4, max=24)
    t["protection"] = dict(t["protection"], rows=8, cols=8, dppu_size=4, faults_at_boot=3)
    return cell


# --------------------------------------------------------------------------- #
# the mapping
# --------------------------------------------------------------------------- #
def test_published_file_maps_to_the_registry_entry_cut_to_the_share():
    from repro.configs import get_config

    config = _published()
    cell = harness.load_cell(CELL)
    assert families.name(config) == "deepseek_v2"
    assert cell.family.__file__ == str(ROOT / "bench" / "families" / "deepseek_v2.py")
    lm = cell.family.lm_config(config)
    want = get_config("deepseek-v2-lite")
    assert lm.n_layers == 7 and lm.first_k_dense == 1 and lm.dense_d_ff == 10944
    assert lm.mla == want.mla and lm.mla.q_lora is None and lm.mla.rope_scaling.factor == 40
    assert lm.moe == dataclasses.replace(want.moe, held=(0, 16))
    assert (lm.moe.n_experts, lm.moe.n_held, lm.moe.top_k, lm.moe.norm_topk) == (64, 16, 6, False)
    assert dataclasses.replace(lm, n_layers=27, moe=want.moe) == want


@pytest.mark.parametrize("key,value", [
    ("topk_method", "group_limited_greedy"), ("n_group", 8), ("topk_group", 3),
    ("scoring_func", "sigmoid"), ("routed_scaling_factor", 16.0), ("q_lora_rank", 1536),
    ("hidden_act", "gelu"),
    ("attention_bias", True), ("moe_layer_freq", 2), ("tie_word_embeddings", True),
    ("rms_norm_eps", 1e-5), ("num_key_value_heads", 8),
    ("rope_scaling", {"type": "linear", "factor": 4}), ("ep_size", 4)])
def test_mapping_refuses_what_the_program_cannot_honour(key, value):
    config = dict(_published(), **{key: value})
    with pytest.raises(ValueError, match=repr(key)):
        harness.lm_config(config)


def test_mapping_passes_assumed_and_null_keys():
    config = _published()
    extra = dict(config, q_lora_rank=None, aux_loss_alpha=0.001,
                 assumed=dict(config["assumed"], aux_loss_alpha="training only"))
    assert harness.lm_config(extra) == harness.lm_config(config)


# --------------------------------------------------------------------------- #
# the reference
# --------------------------------------------------------------------------- #
def test_yarn_frequencies_and_scales_match_the_reference():
    """DeepSeek-V2-Lite's rope at factor 40: the program's frequencies, cos/sin
    factor and softmax scale are the reference's."""
    import jax.numpy as jnp

    from repro.models.layers import yarn_freqs, yarn_mscale

    lm = harness.lm_config(_published())
    spec = ref.Spec.from_config(_published())
    want = ref.inv_freq(spec)
    got = np.asarray(yarn_freqs(64, 10000.0, lm.mla.rope_scaling))
    np.testing.assert_allclose(got, want, rtol=2e-7)
    assert got[0] == np.float32(1.0) and got[-1] == pytest.approx(want[-1], rel=1e-6)
    assert want[-1] == pytest.approx(10000.0 ** (-62 / 64) / 40)        # past the ramp: interpolated
    assert want[9] == pytest.approx(10000.0 ** (-18 / 64))              # before it: extrapolated
    assert lm.mla.softmax_scale == pytest.approx(ref.softmax_scale(spec), rel=1e-12)
    assert lm.mla.softmax_scale == pytest.approx((0.0707 * np.log(40) + 1) ** 2 / np.sqrt(192))
    assert ref.rope_mscale(spec) == 1.0 == yarn_mscale(40, 0.707) / yarn_mscale(40, 0.707)
    assert jnp.asarray(got).dtype == jnp.float32


def test_reference_weights_follow_the_seed_recipe():
    """Layer by layer, the reference makes the weights the program is
    initialised with, from the seed alone, the held experts included (to
    float32 rounding: the reference draws them under ``jit``)."""
    import jax

    from repro.models.lm import init_params

    config = small_config()
    spec = ref.Spec.from_config(config)
    prog = init_params(jax.random.key(SEED), harness.lm_config(config))
    key = jax.random.key(SEED)

    def same(a, b):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)

    same(ref.embedding(spec, key), prog["embed"][: spec.vocab])
    same(ref.lm_head(spec, key), prog["lm_head"][: spec.vocab])
    for i in range(spec.n_layers):
        w = ref.layer_weights(spec, key, i)
        stack, j = (("dense_blocks", i) if i < spec.first_dense else ("blocks", i - spec.first_dense))
        blk = jax.tree.map(lambda a: a[j], prog[stack])
        for name in ("wq", "wkv_a", "wkv_b", "wo", "kv_norm"):
            same(w[name], blk["attn"][name])
        if i < spec.first_dense:
            for name in ("up", "down", "gate"):
                same(w[name], blk["ffn"][name])
            continue
        moe = blk["moe"]
        assert moe["gate"].shape[0] == 4 and moe["router"].shape[1] == 8
        for a, b in (("router", "router"), ("e_gate", "gate"), ("e_up", "up"), ("e_down", "down")):
            same(w[a], moe[b])
        for name in ("up", "down", "gate"):
            same(w["shared"][name], moe["shared"][name])


def test_held_shares_sum_to_the_uncut_layer():
    """Four shares [0,4) .. [12,16) of a 16-expert layer, the shared experts
    counted once, add up to the whole layer, in the program and in the
    reference; and the program's whole layer is the reference's.  Decode
    shapes, one token a row: no expert capacity binds."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import ffn
    from repro.models.moe import moe_forward, moe_init

    lm = harness.lm_config(small_config(16, 16))
    whole = lm.moe
    assert whole.held is None
    x = jax.random.normal(jax.random.key(1), (24, 1, 64), jnp.float32)
    key = jax.random.key(2)
    out, _ = moe_forward(x, moe_init(key, whole), whole)
    parts = []
    for lo in range(0, 16, 4):
        cfg = dataclasses.replace(whole, held=(lo, lo + 4))
        p = moe_init(key, cfg)
        assert p["gate"].shape[0] == 4 and p["router"].shape[1] == 16
        parts.append(moe_forward(x, p, cfg)[0])
    shared = ffn(x, moe_init(key, whole)["shared"])
    np.testing.assert_allclose(sum(parts) - 3 * shared, out, rtol=0, atol=2e-6)

    spec = ref.Spec.from_config(small_config(16, 16))
    p = moe_init(key, whole)
    w = {"router": p["router"], "e_gate": p["gate"], "e_up": p["up"], "e_down": p["down"],
         "shared": p["shared"]}
    ref_parts = []
    for lo in range(0, 16, 4):
        s = dataclasses.replace(spec, held=(lo, lo + 4))
        ws = dict(w, e_gate=w["e_gate"][lo:lo + 4], e_up=w["e_up"][lo:lo + 4],
                  e_down=w["e_down"][lo:lo + 4])
        ref_parts.append(jax.vmap(lambda h: ref.moe_ffn(s, h, ws))(x))
    ref_whole = jax.vmap(lambda h: ref.moe_ffn(spec, h, w))(x)
    np.testing.assert_allclose(sum(ref_parts) - 3 * shared, ref_whole, rtol=0, atol=2e-6)
    np.testing.assert_allclose(out, ref_whole, rtol=0, atol=2e-6)


def test_float32_decode_follows_the_reference_token_by_token():
    """The program's float32 decode through the latent cache (absorbed MLA)
    against the reference's full forward: the same function up to float32
    rounding (1e-5 of logits of order 1)."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import decode_step, forward, init_cache, init_params

    config = small_config()
    lm = dataclasses.replace(harness.lm_config(config), dtype=jnp.float32)
    params = init_params(jax.random.key(SEED), lm)
    toks = np.random.default_rng(3).integers(0, lm.vocab, (2, 12)).astype(np.int32)
    cache = init_cache(lm, 2, 16, dtype=jnp.float32)
    steps = []
    for t in range(toks.shape[1]):
        logits, cache = decode_step(params, lm, cache, {"token": jnp.asarray(toks[:, t:t + 1])})
        steps.append(np.asarray(logits[:, 0, : lm.vocab]))
    pos = np.broadcast_to(np.arange(toks.shape[1]), toks.shape)
    want = np.asarray(ref.logits_at(ref.Spec.from_config(config), SEED, toks, pos))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.stack(steps, 1), want, rtol=0, atol=1e-5)
    # the non-absorbed form, over whole sequences; the reference has no
    # expert capacity, so the forward's is set where it does not bind
    lm = dataclasses.replace(lm, moe=dataclasses.replace(lm.moe, capacity_factor=16.0))
    full, _ = forward(params, lm, {"tokens": jnp.asarray(toks)})
    np.testing.assert_allclose(np.asarray(full[..., : lm.vocab]), want, rtol=0, atol=1e-5)


@pytest.mark.parametrize("backend", ["interpret", "ref"])
def test_served_prefill_then_decode_agrees_with_the_reference(backend, monkeypatch):
    """A prompt fed one token per step through ``ModelBundle.step_fn`` (the
    served path: bfloat16, ``dispatch="fused"``, faults the DPPU repairs),
    then greedy decode through the latent cache; every step's logits agree
    with the reference's forward over the whole sequence to bfloat16
    rounding, 2 % of the largest logit (bfloat16 keeps 8 bits, about 0.4 %
    a rounding, over three layers of residual sums and a routing that may
    flip a near-tie).  ``interpret`` runs both Pallas kernels
    (``ft_matmul``, ``ft_matmul_batched``) in interpret mode; ``ref`` is the
    fused dispatch's single-pass jnp form, the default off the chip."""
    import jax.numpy as jnp

    from repro.core import ftcontext

    monkeypatch.setattr(ftcontext, "fused_backend", lambda: backend)
    config = small_config()
    prot = {"mode": "protected", "dispatch": "fused", "rows": 8, "cols": 8, "dppu_size": 4,
            "faults_at_boot": 3, "scan_block": 1, "fault_rate": 0.0}
    server = harness.build_server(harness.Cell("t", config, {"protection": prot}, {}), SEED)
    b = server.bundle
    assert b.ftc.fused_backend == backend
    rng = np.random.default_rng(0)
    n_slots, plen, gen = config["n_slots"], 10, 6
    prompts = rng.integers(0, config["vocab_size"], (n_slots, plen)).astype(np.int32)
    cache = b.fresh_cache()
    fstate = server._current_fstate()
    seq = [prompts[:, i] for i in range(plen)]
    got = []
    for t in range(plen + gen - 1):
        logits, cache = b.step_fn(b.params, cache, jnp.asarray(seq[t])[:, None], fstate,
                                  b.identity_plan)
        lg = np.asarray(logits[:, 0, : config["vocab_size"]], np.float32)
        got.append(lg)
        if t >= plen - 1:
            seq.append(lg.argmax(-1).astype(np.int32))
        # each row's token routes top_k = 2 pairs a layer, some to the held half
        load = np.asarray(cache["moe_load"])
        assert load.shape == (2, n_slots, 4) and load.sum(-1).max() <= 2
    got = np.stack(got, 1)
    tokens = np.stack(seq, 1)[:, : plen + gen - 1]
    pos = np.broadcast_to(np.arange(plen + gen - 1), tokens.shape)
    want = np.asarray(ref.logits_at(ref.Spec.from_config(config), SEED, tokens, pos))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 0.02 * scale
    assert np.abs(got - want).max() > 0          # bfloat16 compute, not a copy of the reference


def _run(cell, monkeypatch, breakage=None) -> dict:
    if breakage is not None:
        build = harness.build_server

        def broken(cell, seed):
            server = build(cell, seed)
            server.bundle.step_fn = breakage(server.bundle.step_fn)
            return server

        monkeypatch.setattr(harness, "build_server", broken)
    return harness.run_cell(cell, 2**31 + 5, 1.5, False, t_start=time.perf_counter(),
                            device={"platform": "cpu", "kind": "cpu", "count": 1})


def _alter_token(step_fn):
    def f(*args):
        logits, cache = step_fn(*args)
        return logits.at[..., 7].add(1e3), cache
    return f


def _stale_state(step_fn):
    import jax
    import jax.numpy as jnp

    def f(params, cache, *rest):
        logits, _ = step_fn(params, jax.tree.map(jnp.copy, cache), *rest)   # step_fn donates
        return logits, cache
    return f


@pytest.mark.parametrize("breakage", [None, _alter_token, _stale_state],
                         ids=["sound", "token", "state"])
def test_cell_is_served_and_checked_against_its_reference(breakage, monkeypatch):
    """A whole run of the cell at CPU size through the protected server with
    the fused dispatch: correct against the family's reference under the
    cell's own limit, and not correct with a token altered where it is
    produced, or with a step that hands back its latent cache unwritten."""
    out = _run(small_cell(), monkeypatch, breakage)
    json.dumps(out)
    assert out["served_tokens_checked"] > 0
    assert out["correct"] is (breakage is None), out["checks"]


# --------------------------------------------------------------------------- #
# the counts and the readers
# --------------------------------------------------------------------------- #
def test_counts_are_the_programs_call_ledger():
    """``decode_calls`` lists every ``ft_matmul`` call of the program's decode
    step, and ``expert_calls`` the held experts' ``ft_matmul_batched`` work:
    one call per held expert and matmul, over the routed rows."""
    import jax
    import jax.numpy as jnp

    from repro.configs.hyca_dla import dla_config
    from repro.core.engine import empty_fault_state
    from repro.core.ftcontext import build_ftcontext
    from repro.models.lm import decode_step, init_cache, init_params
    from repro.obs.counters import trace_site_calls

    config = small_config()
    fam = families.load(config)
    lm = fam.lm_config(config)
    hyca = dla_config()
    ftc = build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused")
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), lm))
    cache = jax.eval_shape(lambda: init_cache(lm, 4, 16))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    ledger = trace_site_calls(lambda c, p, ch, t: decode_step(p, lm, ch, {"token": t}, ftc=c),
                              ftc, params, cache, tok)
    got = {}
    for c in fam.decode_calls(config, 4):
        got[(c.site, c.m, c.k, c.n)] = got.get((c.site, c.m, c.k, c.n), 0) + c.count
    kernel = [c for c in ledger if c.site != "moe.expert"]
    assert sum(got.values()) == sum(c.count for c in kernel) == 3 * 3 + 3 + 2 * 4 + 1
    by_shape = {}
    for (site, m, k, n), count in got.items():
        by_shape[(site, m, n)] = by_shape.get((site, m, n), 0) + count
    assert by_shape == {(c.site, c.m, c.n): sum(d.count for d in kernel if (d.site, d.m, d.n)
                                                == (c.site, c.m, c.n)) for c in kernel}
    calls = fam.expert_calls(config, 4)
    assert [(c.k, c.n, c.count) for c in calls] == [(64, 32, 2 * 4 * 2), (32, 64, 4 * 2)]
    # one call per held expert and matmul, as the program's ledger counts
    # them; its kernel gets each expert's whole (slots x capacity) slab
    experts = [c for c in ledger if c.site == "moe.expert"]
    assert {(c.n, c.count) for c in experts} == {(c.n, c.count) for c in calls}
    assert {c.m for c in experts} == {4}
    assert calls[0].m == 4 * 2 / 8                          # rows per held expert: active k / width
    # FLOPs of the expected pairs; bytes of every held weight once and the pairs' rows
    pairs = 4 * 2 * 4 / 8
    assert sum(c.flops for c in calls) == 2 * pairs * 64 * 32 * 3 * 2
    assert sum(c.bytes for c in calls) == 2 * (3 * 4 * 64 * 32 + pairs * (64 + 32) * 3) * 2
    full = fam.expert_calls(_published(), 128)
    weights = 2 * 3 * 16 * 2048 * 1408
    assert sum(c.bytes for c in full) / 6 == pytest.approx(weights + 2 * 192 * (2048 + 1408) * 3)
    # the held weights dominate: 6 layers x 277 MB at 819 GB/s, about 2 ms a step
    assert sum(c.least_s(V5E) for c in full) == pytest.approx(6 * weights / V5E.hbm_bw, rel=0.02)


KERNEL_OP = ('%ft_matmul_batched.{} = f32[16,128,1408] custom-call(s32[1024] %m, '
             'bf16[16,128,2048] %x, bf16[16,2048,1408] %w), custom_call_target="tpu_custom_call"')
STEP_LOAD = [(128, 128 * 500), (120, 120 * 480)]


def _window(kernels_per_step: int) -> trace_reduce.Window:
    """Two 60 ms steps, each a 50 ms decode module holding
    ``kernels_per_step`` expert kernels of 0.4 ms."""
    ops, modules, spans = [], [], []
    for i in range(2):
        t0 = 60 * MS * i
        spans.append(Event("bench.step", t0, 59 * MS))
        modules.append(Event(f"jit__step({i})", t0 + 5 * MS, 50 * MS))
        for j in range(kernels_per_step):
            ops.append(Event(KERNEL_OP.format(i * kernels_per_step + j),
                             t0 + 5 * MS + 0.5 * MS * j, 0.4 * MS))
    (w,) = trace_reduce.windows(Trace(ops={"/device:TPU:0": ops},
                                      modules={"/device:TPU:0": modules}, spans=spans))
    return w


def test_expert_roofline_reader():
    """The least time of the traced steps' expected pairs over the kernels'
    time; silent when a step ran another number of expert kernels, and for
    a family without experts."""
    config = _published()
    fam = families.load(config)
    ctx = layer_metrics.Context(_window(18), config, V5E, STEP_LOAD, family=fam)
    least = sum(c.least_s(V5E) for a, _ in STEP_LOAD for c in fam.expert_calls(config, a))
    got = layer_metrics.read("ft_matmul_batched_roofline.v2lite", ctx)
    assert got == pytest.approx(100 * least / (2 * 18 * 0.4 * MS))
    assert 0 < got <= 100
    assert layer_metrics.read("ft_matmul_batched_roofline.v2lite", layer_metrics.Context(
        _window(17), config, V5E, STEP_LOAD, family=fam)) is None
    dense = json.loads((ROOT / "bench" / "configs" / "starcoder2-3b.json").read_text())
    assert layer_metrics.read("ft_matmul_batched_roofline.v2lite", layer_metrics.Context(
        _window(18), dense, V5E, STEP_LOAD)) is None


@pytest.mark.parametrize("name", ["mla_latent_device_ms.v2lite", "moe_route_device_ms.v2lite",
                                  "ft_wrap_device_ms.v2lite"])
def test_scope_readers_are_silent_without_a_program_trace(name):
    config = _published()
    ctx = layer_metrics.Context(_window(18), config, V5E, STEP_LOAD, family=families.load(config))
    assert layer_metrics.read(name, ctx) is None


def _per_layer() -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"]: m for m in bench["per_layer"]}


BATCH_METRICS = sorted(n[: -len(".batch")] for n in _per_layer() if n.endswith(".batch"))


@pytest.mark.parametrize("name", BATCH_METRICS)
def test_the_cell_reports_every_metric_of_the_batch_cell(name):
    """Each per-layer metric of the starcoder2-3b batch cell has a twin in
    this cell: an entry in the same layer that lists this cell, and a
    reader that reads what the batch cell's reads (the same function,
    where both find the same trace)."""
    entries = _per_layer()
    twin, batch = entries[name + ".v2lite"], entries[name + ".batch"]
    assert twin["workloads"] == [CELL] and twin["moves"] == "out_tok_s"
    assert ({k: v for k, v in twin.items() if k not in ("name", "workloads")}
            == {k: v for k, v in batch.items() if k not in ("name", "workloads")})
    config = _published()
    ctx = layer_metrics.Context(_window(18), config, V5E, STEP_LOAD, family=families.load(config))
    got = layer_metrics.read(name + ".v2lite", ctx)
    if name != "ft_wrap_device_ms":        # the twin also leaves out the expert kernels
        assert got == layer_metrics.read(name + ".batch", ctx)
