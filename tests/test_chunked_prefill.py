"""The prompt chunk that rides in every decode step (``ServerConfig.prefill_chunk``):

  * greedy tokens served with a small chunk equal the token-at-a-time feed
    (``decode_step`` without a chunk, one prompt token a step), for a dense
    GQA model and for latent attention with a held share of MoE experts;
  * with faults within DPPU capacity, protected serving equals ``off``;
  * a row that advances nothing leaves its cache leaves bit-identical;
  * the recurrent and encoder-decoder families keep the token feed;
  * ``prefill_tokens`` counts the chunk tokens fed.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models.lm import CHUNK_FAMILIES, decode_step, init_cache, init_params
from repro.serving import FaultTolerantServer, ModelBundle, ServerConfig

C = 4
# shorter than, equal to, one over, and several times the chunk
PROMPT_LENS = (C - 1, C, C + 1, 3 * C + 1)
MAX_NEW = 5


def _lm(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype=jnp.float32)
    if cfg.moe is not None:   # a share of the experts, as one chip of an EP layer holds
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(0, cfg.moe.n_experts // 2)))
    return cfg


def _prompts(vocab, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def _token_feed(params, lm, prompt, max_new, smax):
    """Greedy tokens of one request fed one prompt token a step."""
    step = jax.jit(lambda p, c, t: decode_step(p, lm, c, {"token": t}))
    cache = init_cache(lm, 1, smax)
    out = []
    for t in prompt:
        logits, cache = step(params, cache, jnp.full((1, 1), t, jnp.int32))
    for _ in range(max_new):
        out.append(int(jnp.argmax(logits[0, -1])))
        logits, cache = step(params, cache, jnp.full((1, 1), out[-1], jnp.int32))
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite"])
def test_chunked_greedy_tokens_equal_the_token_feed(arch):
    lm = _lm(arch)
    if arch == "deepseek-v2-lite":
        m = lm.mla
        assert (m.q_lora, lm.moe.norm_topk) == (None, False) and m.rope_scaling is not None
        assert lm.moe.held is not None
    cfg = ServerConfig(arch=arch, n_slots=2, smax=32, mode="off", rows=4, cols=4,
                       dppu_size=2, prefill_chunk=C)
    srv = FaultTolerantServer(cfg, bundle=ModelBundle(cfg, lm=lm))
    assert srv.bundle.prefill_chunk == C
    prompts = _prompts(lm.vocab)
    for p in prompts:
        srv.submit(p, MAX_NEW)
    srv.run(max_steps=80)
    got = srv.completions_by_rid()
    assert len(got) == len(prompts)
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(got[rid], _token_feed(srv.params, lm, p, MAX_NEW, cfg.smax))


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite"])
def test_protected_equals_off_with_faults_within_capacity(arch):
    base = ServerConfig(arch=arch, n_slots=2, smax=32, rows=4, cols=4, dppu_size=2,
                        dispatch="fused", bist=False, prefill_chunk=C)
    bundle = ModelBundle(base)
    out = {}
    for mode in ("off", "protected"):
        srv = FaultTolerantServer(dataclasses.replace(base, mode=mode), bundle=bundle)
        srv.injector.inject_at(1, 2, bit=30, val=1)
        srv.injector.inject_at(3, 1, bit=25, val=1)
        assert srv.injector.n_faults <= base.hyca().capacity
        srv.manager.bist()
        for p in _prompts(srv.lm.vocab, seed=5):
            srv.submit(p, MAX_NEW)
        srv.run(max_steps=80)
        out[mode] = srv.completions_by_rid()
    assert out["off"].keys() == out["protected"].keys() and len(out["off"]) == len(PROMPT_LENS)
    for rid, toks in out["off"].items():
        np.testing.assert_array_equal(toks, out["protected"][rid])


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite"])
def test_rows_that_advance_nothing_leave_the_cache_bit_identical(arch):
    """Slot 0 takes a chunk of 3 of its C rows at position 5, slot 1 does not
    advance, slot 2 does: slot 1's leaves, slot 0's past position 7 and
    slot 2's but its one position are unchanged, bit for bit."""
    lm = _lm(arch)
    params = init_params(jax.random.key(0), lm)
    cache = init_cache(lm, 3, 16)
    keys = iter(jax.random.split(jax.random.key(1), 16))
    cache["attn"] = {k: (jnp.full_like(v, 5) if k == "idx" else
                         jax.random.normal(next(keys), v.shape, v.dtype))
                     for k, v in cache["attn"].items()}
    batch = {"token": jnp.asarray([[0], [7], [9]], jnp.int32),
             "advance": jnp.asarray([False, False, True]),
             "chunk": {"tokens": jnp.asarray([3, 4, 5, 0], jnp.int32),
                       "slot": jnp.int32(0), "len": jnp.int32(3)}}
    _, new = decode_step(params, lm, cache, batch)
    old, new = cache["attn"], new["attn"]
    np.testing.assert_array_equal(np.asarray(new["idx"][:, :]), [[8, 5, 6]] * new["idx"].shape[0])
    for k in old:
        if k == "idx":
            continue
        a, b = np.asarray(old[k], np.float32), np.asarray(new[k], np.float32)
        np.testing.assert_array_equal(b[:, 1], a[:, 1])
        np.testing.assert_array_equal(b[:, 0, 8:], a[:, 0, 8:])
        np.testing.assert_array_equal(b[:, 0, :5], a[:, 0, :5])
        assert not np.array_equal(b[:, 0, 5:8], a[:, 0, 5:8])
        keep = np.arange(16) != 5
        np.testing.assert_array_equal(b[:, 2, keep], a[:, 2, keep])


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_the_family_decides_whether_a_step_takes_a_chunk(arch):
    lm = get_smoke_config(arch)
    b = ModelBundle(ServerConfig(arch=arch, smax=16))
    assert b.prefill_chunk == (16 if lm.family in CHUNK_FAMILIES else 0)
    if lm.family in CHUNK_FAMILIES:
        return
    with pytest.raises(ValueError, match="no prompt chunk"):
        decode_step(b.params, lm, b.fresh_cache(),
                    {"token": jnp.zeros((4, 1), jnp.int32), "advance": jnp.ones(4, bool),
                     "chunk": {"tokens": jnp.zeros(2, jnp.int32), "slot": jnp.int32(0),
                               "len": jnp.int32(1)}})


@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-1.2b", "whisper-tiny"])
def test_recurrent_and_encdec_families_keep_the_token_feed(arch):
    srv = FaultTolerantServer(ServerConfig(arch=arch, n_slots=2, smax=16, mode="off"))
    assert get_smoke_config(arch).family in ("ssm", "hybrid", "encdec")
    srv.submit(np.arange(1, 6), 2)
    s = srv.run(max_steps=20)
    (done,) = srv.metrics.completions
    # one prompt token a step: the fifth step gives the first token
    assert done.reason == "done" and done.first_token_step == 4
    assert s["prefill_tokens_per_step"] == 0 and srv.scheduler.chunk is None


def test_prefill_tokens_count_the_chunk_tokens_fed():
    """One slot: every prompt streams through the chunk, C tokens a step."""
    cfg = ServerConfig(arch="qwen1.5-0.5b", n_slots=1, smax=32, mode="off", rows=4, cols=4,
                       dppu_size=2, prefill_chunk=C)
    srv = FaultTolerantServer(cfg)
    prompts = _prompts(srv.lm.vocab)
    for p in prompts:
        srv.submit(p, MAX_NEW)
    s = srv.run(max_steps=80)
    fed = [r.prefill_tokens for r in srv.metrics.steps if r.prefill_tokens]
    want = []
    for n in PROMPT_LENS:
        want += [C] * (n // C) + ([n % C] if n % C else [])
    assert fed == want and sum(fed) == sum(PROMPT_LENS)
    assert s["prefill_tokens_per_step"] == sum(fed) / s["steps"]
    # each first token came out of the step that fed the prompt's last chunk
    for c in srv.metrics.completions:
        assert c.first_token_step - c.admitted_step == -(-c.prompt_len // C) - 1


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite"])
def test_a_step_without_a_chunk_runs_the_token_only_program(arch):
    """A step with no prompt to chunk feeds the bare token array, so it
    runs no padded chunk rows; both programs compile in the first step and
    none after it."""
    cfg = ServerConfig(arch=arch, n_slots=2, smax=32, mode="protected", rows=4, cols=4,
                       dppu_size=2, prefill_chunk=C, counters=True)
    srv = FaultTolerantServer(cfg)
    step_fn, chunked = srv.bundle.step_fn, []

    def spy(params, cache, feed, *rest):
        chunked.append(isinstance(feed, dict))
        return step_fn(params, cache, feed, *rest)

    srv.bundle.step_fn = spy
    srv.submit(_prompts(srv.lm.vocab)[-1], MAX_NEW)         # 3C + 1 tokens
    srv.step()
    compiles = []

    def count(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        srv.run(max_steps=20)
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    (done,) = srv.metrics.completions
    assert done.reason == "done" and len(done.tokens) == MAX_NEW
    assert chunked == [True] * 4 + [False] * (len(chunked) - 4) and len(chunked) > 4
    assert compiles == []
