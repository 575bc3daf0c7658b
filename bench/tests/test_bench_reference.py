"""The float32 reference against the served program at small sizes: the
comparison that decides ``correct`` on the chip rests on these."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.reference.dense import DenseSpec, layer_weights, logits_at  # noqa: E402
from bench.tests.small import small_config  # noqa: E402

SEED = 2**31 + 17          # larger than a signed 32-bit seed


ARCHS = ["qwen1.5-0.5b", "starcoder2-3b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_reference_weights_follow_the_seed_recipe(arch):
    """Layer by layer, the reference makes the weights the program is
    initialised with, from the seed alone."""
    import jax

    from bench.harness import lm_config
    from repro.models.lm import init_params

    config = small_config(arch)
    spec = DenseSpec.from_config(config)
    prog = init_params(jax.random.key(SEED), lm_config(config))
    for i in range(spec.n_layers):
        ref = layer_weights(spec, jax.random.key(SEED), i)
        blk = jax.tree.map(lambda a: a[i], prog["blocks"])
        for name in ("wq", "wk", "wv", "wo"):
            np.testing.assert_allclose(ref[name], blk["attn"][name], rtol=1e-6, atol=1e-8)
        for name in ("up", "down") + (("gate",) if spec.gated else ()):
            np.testing.assert_allclose(ref[name], blk["ffn"][name], rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_through_the_bundle_agrees_with_reference(arch):
    """Feed a prompt one token per step through ``ModelBundle.step_fn`` (the
    served path, protected, with faults the DPPU repairs) and decode
    greedily; every step's logits agree with the reference's forward over
    the whole sequence to bfloat16 rounding."""
    import jax.numpy as jnp

    from bench.harness import Cell, build_server

    config = small_config(arch)
    prot = {"mode": "protected", "dispatch": "fused", "rows": 8, "cols": 8, "dppu_size": 4,
            "faults_at_boot": 3, "scan_block": 1, "fault_rate": 0.0}
    server = build_server(Cell("t", config, {"protection": prot}, {}), SEED)
    b = server.bundle
    rng = np.random.default_rng(0)
    n_slots, plen, gen = config["n_slots"], 12, 8
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
    got = np.stack(got, 1)                                     # (slots, steps, vocab)
    tokens = np.stack(seq, 1)[:, : plen + gen - 1]
    pos = np.broadcast_to(np.arange(plen + gen - 1), (n_slots, plen + gen - 1))
    want = np.asarray(logits_at(DenseSpec.from_config(config), SEED, tokens, pos))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 0.02 * scale
    assert np.abs(got - want).max() > 0       # bfloat16 compute, not a copy of the reference


def test_padding_after_a_sequence_does_not_reach_back():
    config = small_config("qwen1.5-0.5b")
    spec = DenseSpec.from_config(config)
    rng = np.random.default_rng(1)
    a = rng.integers(0, 500, (1, 20)).astype(np.int32)
    b = a.copy()
    b[0, 12:] = 0
    pos = np.arange(12)[None]
    np.testing.assert_array_equal(np.asarray(logits_at(spec, SEED, a, pos)),
                                  np.asarray(logits_at(spec, SEED, b, pos)))


def test_fp8_control_departs_from_float32():
    config = small_config("starcoder2-3b")
    spec = DenseSpec.from_config(config)
    toks = np.random.default_rng(2).integers(0, 500, (2, 16)).astype(np.int32)
    pos = np.broadcast_to(np.arange(16), (2, 16))
    f32 = np.asarray(logits_at(spec, SEED, toks, pos))
    f8 = np.asarray(logits_at(spec, SEED, toks, pos, mode="fp8"))
    rel = np.abs(f8 - f32).max() / np.abs(f32).max()
    assert 1e-3 < rel < 0.5
