"""The benchmark's operation and byte counts against what the program's
decode step really calls (abstract tracing only: no weights are made)."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import work  # noqa: E402

CONFIGS = ["starcoder2-3b"]


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _ledger(config: dict, n_slots: int):
    """The program's static call ledger of one decode step at full size."""
    import jax
    import jax.numpy as jnp

    from bench.harness import lm_config
    from repro.configs.hyca_dla import dla_config
    from repro.core.engine import empty_fault_state
    from repro.core.ftcontext import build_ftcontext
    from repro.models.lm import decode_step, init_cache, init_params
    from repro.obs.counters import trace_site_calls

    lm = lm_config(config)
    hyca = dla_config()
    ftc = build_ftcontext(empty_fault_state(hyca.rows * hyca.cols), hyca, dispatch="fused")
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), lm))
    cache = jax.eval_shape(lambda: init_cache(lm, n_slots, 16))
    tok = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)
    return trace_site_calls(
        lambda c, p, ch, t: decode_step(p, lm, ch, {"token": t}, ftc=c), ftc, params, cache, tok)


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_calls_match_the_program_ledger(name):
    config = _config(name)
    ledger = _ledger(config, 64)
    assert all(c.protected and c.dispatch == "fused" for c in ledger)
    got = sorted((c.site, c.m, c.n, c.count) for c in ledger)
    want = sorted((c.site, c.m, c.n, c.count) for c in work.decode_calls(config, 64))
    assert got == want


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_and_bf16_bytes_per_call(name):
    config = _config(name)
    d, ff = config["hidden_size"], config["intermediate_size"]
    calls = {(c.site, c.k, c.n): c for c in work.decode_calls(config, 64)}
    down = calls[("ffn", ff, d)]
    assert down.flops == 2 * 64 * ff * d * config["num_hidden_layers"]
    assert down.bytes == 2 * (64 * ff + ff * d + 64 * d) * config["num_hidden_layers"]
    head = [c for c in calls.values() if c.site == "head"][0]
    assert head.n == -(-config["vocab_size"] // 256) * 256 and head.count == 1
    # every call of a 64-slot decode step is bound by its weight bytes
    from bench.peaks import PEAKS

    pk = PEAKS["TPU v5 lite"]
    assert all(c.least_s(pk) == c.bytes / pk.hbm_bw for c in calls.values())


@pytest.mark.parametrize("name", CONFIGS)
def test_model_flops_match_the_roofline_decode_formula(name):
    from bench.harness import lm_config
    from repro.configs.shapes import ShapeCell
    from repro.launch.roofline import model_flops

    config = _config(name)
    seq, slots = 512, 64
    want = model_flops(lm_config(config), ShapeCell("d", "decode", seq, slots))
    got = work.step_model_flops(config, slots, slots * seq)
    # the roofline counts every parameter (norms, biases, the padded
    # vocabulary rows) as a matmul weight; those are under 0.1 percent
    assert got == pytest.approx(want, rel=1e-3)
    assert got < want
