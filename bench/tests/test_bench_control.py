"""``correct`` comes out false when the timed path is broken underneath.

Each test drives the rest of a run (``run_cell``, without the harness's
look for a chip) at a size the CPU holds, through the protected server with
faults the DPPU repairs, and compares with the cell's own limit:

* sound: the program as it is, which the limit passes;
* a token altered where it is produced: the decode step's logits get a
  bias toward one token id, so greedy sampling emits it;
* a step that returns its state unchanged: the decode step computes on a
  copy of the KV cache and hands the old cache back, so no position is
  ever written.

(Serving has no batch mean and no exchange between chips: the other faults
of a training cell do not arise.)  The float8 control of the limit is read
on the chip at the cells' own sizes (``bench/control.py``, PERF.md).
"""
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import harness  # noqa: E402

CELLS = ["starcoder2-3b.batch.protected"]


def small_cell(name: str) -> harness.Cell:
    """The cell with its widths, depth, vocabulary and slots cut to CPU size;
    mix shape, protection and limits as committed."""
    cell = harness.load_cell(name)
    cell.config.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                       vocab_size=4096, n_slots=4, smax=64)
    cell.config["num_attention_heads"] = 4
    cell.config["num_key_value_heads"] = 4 if cell.config["hidden_act"] == "silu" else 2
    t = cell.traffic
    t.update(block=16, warm_steps=10, follow_s=10.0, check_requests=4)
    if t["loop"] == "open":
        t["rate_rps"] = 40.0
    t["prompt"] = dict(t["prompt"], median=8, min=2, max=24)
    t["output"] = dict(t["output"], median=8, min=4, max=24)
    t["protection"] = dict(t["protection"], rows=8, cols=8, dppu_size=4, faults_at_boot=3)
    return cell


def _run(cell, monkeypatch, breakage=None) -> dict:
    if breakage is not None:
        build = harness.build_server

        def broken(cell, seed):
            server = build(cell, seed)
            server.bundle.step_fn = breakage(server.bundle.step_fn)
            return server

        monkeypatch.setattr(harness, "build_server", broken)
    out = harness.run_cell(cell, 2**31 + 5, 1.5, False, t_start=time.perf_counter(),
                           device={"platform": "cpu", "kind": "cpu", "count": 1})
    json.dumps(out)                                   # the result line serialises
    assert set(out["checks"]) == {"max_gap"} and list(out)[-1] == "checks"
    return out


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


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    out = _run(small_cell(name), monkeypatch)
    assert out["correct"] is True, out["checks"]
    assert out["served_tokens_checked"] > 0


@pytest.mark.parametrize("breakage", [_alter_token, _stale_state], ids=["token", "state"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, breakage, monkeypatch):
    out = _run(small_cell(name), monkeypatch, breakage)
    assert out["correct"] is False, out["checks"]
