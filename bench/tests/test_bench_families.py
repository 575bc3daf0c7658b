"""Model families: the dense family gives what the benchmark gave before
families existed, its mapping refuses what the program cannot honour, and
a second family is served, checked and counted from new files alone."""
import dataclasses
import json
import pathlib
import shutil
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import check, families, harness, layer_metrics, trace_reduce, work  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.tests.small import small_config  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
SEED = 2**31 + 23
MS = 1e-3
V5E = PEAKS["TPU v5 lite"]


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


CONFIGS = {"starcoder2-3b": _config("starcoder2-3b"),
           "small-qwen1.5": small_config("qwen1.5-0.5b"),
           "small-starcoder2": small_config("starcoder2-3b")}


# --------------------------------------------------------------------------- #
# the dense family is the code it replaced
# --------------------------------------------------------------------------- #
def _lm_config_before(config: dict):
    """``bench.harness.lm_config`` as it was before model families, frozen."""
    from repro.configs import get_config

    act = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}[config["hidden_act"]]
    return dataclasses.replace(
        get_config(config["arch"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=None, rope_theta=float(config["rope_theta"]),
        norm="ln" if "norm_epsilon" in config else "rms",
        gated_ffn=act == "silu", act=act,
        tie_embeddings=bool(config["tie_word_embeddings"]),
    )


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_lm_config_is_the_mapping_before_families(name):
    config = CONFIGS[name]
    assert families.name(config) == "dense"
    want = _lm_config_before(config)
    for got in (harness.lm_config(config), families.load(config).lm_config(config),
                harness.load_cell("starcoder2-3b.batch.protected").family.lm_config(config)):
        for f in dataclasses.fields(want):
            assert getattr(got, f.name) == getattr(want, f.name), f.name


def _sample(config: dict, n: int = 3) -> list:
    """Finished requests of fixed prompts and served tokens."""
    rng = np.random.default_rng(11)
    return [types.SimpleNamespace(prompt=rng.integers(0, config["vocab_size"], 9 + 4 * b).astype(np.int32),
                                  tokens=rng.integers(0, config["vocab_size"], 6 + b).astype(np.int32))
            for b in range(n)]


def _served_gaps_before(config: dict, seed: int, sample, smax: int) -> dict:
    """``bench.check.served_gaps`` with ``control`` as it was before model
    families, frozen: the dense reference called by name."""
    import jax.numpy as jnp

    from bench.reference.dense import DenseSpec, logits_at

    spec = DenseSpec.from_config(config)
    tokens, pos, served, mask = check._arrays(sample, smax)
    logits = logits_at(spec, seed, tokens, pos)
    best = logits.max(-1)

    def gap(pick):
        at = jnp.take_along_axis(logits, jnp.asarray(pick)[..., None], axis=-1)[..., 0]
        return float(np.asarray(best - at)[mask].max())

    pick = np.asarray(logits_at(spec, seed, tokens, pos, mode="fp8").argmax(-1))
    return {"max_gap": gap(served), "served_tokens": int(mask.sum()), "control_gap": gap(pick)}


@pytest.mark.parametrize("name", ["small-qwen1.5", "small-starcoder2"])
def test_dense_served_gaps_bit_equal_to_before(name):
    config = CONFIGS[name]
    sample = _sample(config)
    want = _served_gaps_before(config, SEED, sample, config["smax"])
    assert check.served_gaps(config, SEED, sample, config["smax"], control=True) == want
    assert want["max_gap"] > 0


KERNEL_OP = ('%ft_matmul.{} = bf16[64,3072] custom-call(s32[1024] %m, bf16[64,3072] %x, '
             'bf16[3072,3072] %w), custom_call_target="tpu_custom_call"')


def _window(kernels_per_step: int) -> trace_reduce.Window:
    """Two 80 ms steps, each a 70 ms decode module holding
    ``kernels_per_step`` protected-matmul kernels of about 0.5 ms."""
    ops, modules, spans = [], [], []
    for i in range(2):
        t0 = 80 * MS * i
        spans.append(Event("bench.step", t0, 79 * MS))
        modules.append(Event(f"jit__step({i})", t0 + 5 * MS, 70 * MS))
        for j in range(kernels_per_step):
            ops.append(Event(KERNEL_OP.format(i * kernels_per_step + j),
                             t0 + 5 * MS + 0.7 * MS * j, (0.5 + 0.001 * j) * MS))
    (w,) = trace_reduce.windows(Trace(ops={"/device:TPU:0": ops},
                                      modules={"/device:TPU:0": modules}, spans=spans))
    return w


STEP_LOAD = [(64, 64 * 700), (60, 60 * 650)]


def test_dense_counts_and_readers_unchanged():
    """The counts and the two readers that use them give, on a synthetic
    window, the numbers they gave before model families (recorded then)."""
    config = CONFIGS["starcoder2-3b"]
    fam = families.load(config)
    calls = fam.decode_calls(config, 64)
    assert [(c.site, c.m, c.k, c.n, c.count) for c in calls] == [
        ("attn.qkv", 64, 3072, 3072, 15), ("attn.qkv", 64, 3072, 256, 30),
        ("attn.out", 64, 3072, 3072, 15), ("ffn", 64, 3072, 12288, 15),
        ("ffn", 64, 12288, 3072, 15), ("head", 64, 3072, 49152, 1)]
    assert calls == work.decode_calls(config, 64)
    assert fam.step_model_flops(config, 64, 64 * 700) == 211798720512.0
    assert work.matmul_params_per_token(config) == 1590165504
    assert work.attn_flops_per_position(config) == 184320
    ctx = layer_metrics.Context(_window(91), config, V5E, STEP_LOAD)
    assert ctx.family is fam
    assert layer_metrics.read("decode_mfu.batch", ctx) == 1.3083263448328704
    assert layer_metrics.read("ft_matmul_roofline.batch", ctx) == 8.081013129425267


# --------------------------------------------------------------------------- #
# the dense mapping refuses what it cannot honour
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("key,value", [
    ("n_routed_experts", 64), ("num_experts_per_tok", 6), ("moe_intermediate_size", 1408),
    ("kv_lora_rank", 512), ("qk_rope_head_dim", 64),
    ("rope_scaling", {"type": "yarn", "factor": 40}), ("head_dim", 128)])
def test_dense_mapping_refuses_keys_it_would_drop(key, value):
    config = dict(CONFIGS["starcoder2-3b"], **{key: value})
    with pytest.raises(ValueError, match=repr(key)):
        harness.lm_config(config)


@pytest.mark.parametrize("name,key,value", [
    ("starcoder2-3b", "norm_epsilon", 1e-6), ("small-qwen1.5", "rms_norm_eps", 1e-5),
    ("starcoder2-3b", "rms_norm_eps", 1e-6), ("starcoder2-3b", "norm_type", "rms_norm"),
    ("small-qwen1.5", "tie_word_embeddings", False)])
def test_dense_mapping_refuses_a_norm_or_head_it_cannot_honour(name, key, value):
    config = dict(CONFIGS[name], **{key: value})
    with pytest.raises(ValueError, match=repr(key)):
        harness.lm_config(config)


def test_dense_mapping_refuses_an_arch_of_another_family():
    config = dict(CONFIGS["small-qwen1.5"], arch="deepseek-moe-16b")
    with pytest.raises(ValueError, match="moe"):
        harness.lm_config(config)


def test_dense_mapping_passes_assumed_null_and_inert_keys():
    config = CONFIGS["starcoder2-3b"]
    assert {"use_bias", "sliding_window"} <= set(config["assumed"])
    extra = dict(config, rope_scaling=None, q_lora_rank=None, family="dense",
                 assumed=dict(config["assumed"], attention_dropout="not modelled"),
                 attention_dropout=0.1)
    assert harness.lm_config(extra) == harness.lm_config(config)


# --------------------------------------------------------------------------- #
# a second family from new files alone
# --------------------------------------------------------------------------- #
FIXTURE_CELL = "deepseek-moe-16b-smoke.batch.protected"


def _fixture_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout holding the repository's benchmark files plus the fixture
    family's files (module, reference, configuration, limits) and entries."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copytree(FIXTURES, root / "bench", dirs_exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "deepseek-moe-16b-smoke", "source": "test",
                             "file": "bench/configs/deepseek-moe-16b-smoke.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": FIXTURE_CELL, "config": "deepseek-moe-16b-smoke",
                               "traffic": "batch", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append(FIXTURE_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _untouched(root: pathlib.Path) -> None:
    """Every file of the repository's benchmark is in the checkout as it is."""
    for f in (ROOT / "bench").rglob("*.py"):
        if not {"__pycache__", "tests"} & set(f.parts):
            assert (root / f.relative_to(ROOT)).read_bytes() == f.read_bytes(), f


def test_a_second_family_is_looked_up_and_counted_from_new_files(tmp_path):
    from repro.configs import get_smoke_config

    root = _fixture_root(tmp_path)
    _untouched(root)
    cell = harness.load_cell(FIXTURE_CELL, root=root)
    assert cell.family.__file__ == str(root / "bench" / "families" / "moe_smoke.py")
    lm = harness.lm_config(cell.config, cell.family)
    assert lm == dataclasses.replace(get_smoke_config("deepseek-moe-16b"), name=lm.name)
    with pytest.raises(KeyError, match="moe_smoke"):
        harness.lm_config(cell.config)           # the repository has no such family

    calls = cell.family.decode_calls(cell.config, 4)
    ctx = layer_metrics.Context(_window(sum(c.count for c in calls)), cell.config, V5E,
                                STEP_LOAD, family=cell.family)
    flops = sum(cell.family.step_model_flops(cell.config, a, c) for a, c in STEP_LOAD)
    assert layer_metrics.read("decode_mfu.batch", ctx) == pytest.approx(
        100 * flops / ctx.window.seconds / V5E.bf16_flops)
    least = 2 * sum(c.least_s(V5E) for c in calls)
    kernel_s = sum(k.dur for k in ctx.window.ops_within(
        ctx.window.modules_matching(layer_metrics.STEP_MODULE), layer_metrics.KERNEL))
    assert layer_metrics.read("ft_matmul_roofline.batch", ctx) == pytest.approx(
        100 * least / kernel_s)
    # one kernel short of the family's calls: silent
    assert layer_metrics.read("ft_matmul_roofline.batch", layer_metrics.Context(
        _window(sum(c.count for c in calls) - 1), cell.config, V5E, STEP_LOAD,
        family=cell.family)) is None


def test_a_second_family_counts_the_programs_kernel_calls(tmp_path):
    """The fixture's counts list every ``ft_matmul`` call of the program's
    decode step (its experts run ``ft_matmul_batched``), and its reference
    follows the program's float32 decode token by token."""
    import jax
    import jax.numpy as jnp

    from repro.configs.hyca_dla import dla_config
    from repro.core.engine import empty_fault_state
    from repro.core.ftcontext import build_ftcontext
    from repro.models.lm import decode_step, init_cache, init_params
    from repro.obs.counters import trace_site_calls

    cell = harness.load_cell(FIXTURE_CELL, root=_fixture_root(tmp_path))
    lm = cell.family.lm_config(cell.config)
    hyca = dla_config()
    ftc = build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused")
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), lm))
    cache = jax.eval_shape(lambda: init_cache(lm, 4, 16))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    ledger = trace_site_calls(lambda c, p, ch, t: decode_step(p, lm, ch, {"token": t}, ftc=c),
                              ftc, params, cache, tok)
    got = {}
    for c in cell.family.decode_calls(cell.config, 4):
        got[(c.site, c.m, c.n)] = got.get((c.site, c.m, c.n), 0) + c.count
    assert got == {(c.site, c.m, c.n): c.count for c in ledger if c.site != "moe.expert"}

    lm32 = dataclasses.replace(lm, dtype=jnp.float32)
    params = init_params(jax.random.key(SEED), lm32)
    toks = np.random.default_rng(3).integers(0, lm.vocab, (2, 10)).astype(np.int32)
    cache = init_cache(lm32, 2, 16, dtype=jnp.float32)
    steps = []
    for t in range(toks.shape[1]):
        logits, cache = decode_step(params, lm32, cache, {"token": jnp.asarray(toks[:, t:t + 1])})
        steps.append(np.asarray(logits[:, 0, : lm.vocab]))
    pos = np.broadcast_to(np.arange(toks.shape[1]), toks.shape)
    want = cell.family.logits_at(cell.family.reference_spec(cell.config), SEED, toks, pos)
    np.testing.assert_allclose(np.stack(steps, 1), np.asarray(want), rtol=0, atol=1e-5)


def _small_traffic(cell: harness.Cell) -> harness.Cell:
    """The mix's shape at CPU size.  The protected path is the engine's two
    passes: XLA:CPU cannot run the fused batched kernel's bfloat16 dot in
    interpret mode ("Unsupported element type for DotThunk::Execute")."""
    t = cell.traffic
    t.update(block=16, warm_steps=10, follow_s=10.0, check_requests=4)
    t["prompt"] = dict(t["prompt"], median=8, min=2, max=24)
    t["output"] = dict(t["output"], median=8, min=4, max=24)
    t["protection"] = dict(t["protection"], rows=8, cols=8, dppu_size=4, faults_at_boot=3,
                           dispatch="twopass")
    return cell


def _alter_token(step_fn):
    def f(*args):
        logits, cache = step_fn(*args)
        return logits.at[..., 7].add(1e3), cache
    return f


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "token"])
def test_a_second_family_is_served_and_checked_against_its_reference(tmp_path, monkeypatch,
                                                                     broken):
    """A whole run of the fixture cell through the protected server: correct
    against the fixture's own reference, and not correct with a token
    altered where it is produced."""
    cell = _small_traffic(harness.load_cell(FIXTURE_CELL, root=_fixture_root(tmp_path)))
    seen = []
    served_gaps = check.served_gaps

    def spy(config, seed, sample, smax, **kw):
        seen.append(kw["family"])
        return served_gaps(config, seed, sample, smax, **kw)

    monkeypatch.setattr(check, "served_gaps", spy)
    if broken:
        build = harness.build_server

        def broken_server(cell, seed):
            server = build(cell, seed)
            server.bundle.step_fn = _alter_token(server.bundle.step_fn)
            return server

        monkeypatch.setattr(harness, "build_server", broken_server)
    out = harness.run_cell(cell, 2**31 + 9, 1.5, False, t_start=time.perf_counter(),
                           device={"platform": "cpu", "kind": "cpu", "count": 1})
    assert seen == [cell.family]
    assert out["served_tokens_checked"] > 0
    assert out["correct"] is (not broken), out["checks"]
