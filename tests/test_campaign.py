"""FaultCampaign acceptance tests — the vmapped Monte-Carlo engine.

  * batched-vs-reference parity: the vmapped evaluator is bit-identical to
    the legacy per-config NumPy ``evaluate_scheme`` loop at fixed seeds,
    across all four schemes and both fault models (satellite: campaign ==
    legacy, the ``boot_scan(batched=False)`` idiom);
  * DR union-find reformulation == ``redundancy.dr_repair`` on adversarially
    random maps/spares, including rectangular sub-array splits;
  * no-retrace acceptance: sweeping PER points and swapping batched
    FaultStates through one compiled program triggers zero recompilations
    (the test_ftcontext/test_scan pattern);
  * seed plumbing: per-point seeds are stable (NOT the salted builtin hash)
    and fault maps are shared across schemes by construction;
  * device samplers: marginal rate within binomial CI, clustered maps stay
    in-bounds at extreme sigma and keep the Binomial count distribution;
  * batched FaultStates: per-config parity with fault_state_from_map, and
    the kernels' device fault grids == the host AGU;
  * chaos hook: campaign-sampled maps land in running servers / fleets and
    the ScanEngine (not the injector) is what confirms them;
  * golden-stats suite (CI campaign-stats job, @campaign_stats): seeded
    curves pinned within the campaign's own confidence intervals — monotone
    FFP degradation, HyCA >= DR >= CR/RR ordering, the capacity cliff, and
    protected-accuracy recovery up to DPPU capacity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import campaign as cp
from repro.core import redundancy as red
from repro.core import reliability as rel
from repro.core.engine import HyCAConfig, fault_state_from_map, hyca_matmul
from repro.core.fault_models import random_fault_maps
from repro.core.redundancy import DPPUConfig
from repro.kernels.ops import fault_grids, fault_grids_device


# --------------------------------------------------------------------------- #
# batched-vs-reference parity (bit-identical)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("fault_model", ["random", "clustered"])
@pytest.mark.parametrize("seed", [0, 7])
def test_campaign_bit_identical_to_legacy_loop(fault_model, seed):
    """The vmapped campaign reproduces the legacy per-config NumPy loop's
    FFP and remaining power EXACTLY (same seed, same streams) — all four
    schemes, both fault models."""
    n = 150
    spec = cp.CampaignSpec(rows=16, cols=16, fault_model=fault_model,
                           n_configs=n, dppu=DPPUConfig(size=16), seed=seed)
    point = cp.sample_point(spec, 0.03)
    for r in cp.evaluate_point(spec, point):
        legacy = rel.evaluate_scheme(
            r.scheme, 0.03, rows=16, cols=16, fault_model=fault_model,
            n_configs=n, dppu=DPPUConfig(size=16), seed=seed,
        )
        assert r.fully_functional_prob == legacy.fully_functional_prob, r.scheme
        assert r.remaining_power == legacy.remaining_power, r.scheme


@pytest.mark.parametrize("rows,cols", [(8, 8), (16, 8), (8, 16), (12, 8)])
def test_vmapped_equals_per_config_reference(rows, cols, rng):
    """Per-config (ff, surviving_columns) parity on dense random batches —
    including non-square arrays (rectangular DR sub-splits)."""
    n = 200
    pers = rng.uniform(0.0, 0.25, size=n)
    maps = rng.random((n, rows, cols)) < pers[:, None, None]
    for scheme in red.SCHEMES:
        if scheme == "HyCA":
            aux_np = rng.integers(0, cols + 2, size=n).astype(np.int32)
            ref = [red.hyca_repair(maps[i], int(aux_np[i])) for i in range(n)]
        else:
            n_sp = red.n_spares(scheme, rows, cols)
            aux_np = rng.random((n, n_sp)) < 0.25
            ref = [red.repair(scheme, maps[i], spare_faulty=aux_np[i]) for i in range(n)]
        ff, surv = cp.evaluate_batched(jnp.asarray(maps), jnp.asarray(aux_np), scheme=scheme)
        np.testing.assert_array_equal(np.asarray(ff), [r[0] for r in ref], err_msg=scheme)
        np.testing.assert_array_equal(np.asarray(surv), [r[1] for r in ref], err_msg=scheme)


def test_dr_dead_spares_and_diagonal_faults(rng):
    """DR corner cases: faults on the diagonal (single-spare neighbourhood),
    dead spares on both endpoints, and heavy spare mortality."""
    rows = cols = 8
    n = 300
    maps = rng.random((n, rows, cols)) < 0.15
    for i in range(0, n, 3):
        maps[i, i % rows, i % cols] = True  # force diagonal faults
    spares = rng.random((n, 8)) < 0.5      # very unhealthy spares
    ref = [red.dr_repair(maps[i], spares[i]) for i in range(n)]
    ff, surv = cp.evaluate_batched(jnp.asarray(maps), jnp.asarray(spares), scheme="DR")
    np.testing.assert_array_equal(np.asarray(ff), [r[0] for r in ref])
    np.testing.assert_array_equal(np.asarray(surv), [r[1] for r in ref])


# --------------------------------------------------------------------------- #
# no-retrace acceptance
# --------------------------------------------------------------------------- #
def test_campaign_step_zero_recompilations_across_per_points(rng):
    """Sweeping PER points (fresh maps + fresh DPPU capacities every point)
    through the campaign evaluator is ONE compiled program per scheme."""
    traces = {s: [] for s in red.SCHEMES}
    fns = {}
    for scheme in red.SCHEMES:

        def make(scheme):
            @jax.jit
            def f(maps, aux):
                traces[scheme].append(1)
                return cp.evaluate_batched(maps, aux, scheme=scheme)
            return f

        fns[scheme] = make(scheme)
    for per in (0.01, 0.03, 0.06):
        maps = jnp.asarray(rng.random((64, 8, 8)) < per)
        for scheme in red.SCHEMES:
            if scheme == "HyCA":
                aux = jnp.asarray(rng.integers(0, 9, size=64), jnp.int32)
            else:
                n_sp = red.n_spares(scheme, 8, 8)
                aux = jnp.asarray(rng.random((64, n_sp)) < per)
            fns[scheme](maps, aux)
    assert all(len(traces[s]) == 1 for s in red.SCHEMES), traces


def test_batched_fault_state_swap_zero_recompilations(rng):
    """Swapping batched FaultStates (different PER points) through a vmapped
    protected matmul never retraces — fault tables are data."""
    x = jnp.asarray(rng.integers(-8, 8, (4, 16)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (16, 8)), jnp.int8)
    cfg = HyCAConfig(rows=8, cols=8, mode="protected")
    traces = []

    @jax.jit
    def fwd(states):
        traces.append(1)
        return jax.vmap(lambda s: hyca_matmul(x, w, s, cfg=cfg))(states)

    for per in (0.01, 0.05, 0.2):
        maps = random_fault_maps(rng, 16, 8, 8, per)
        fwd(cp.batched_fault_states(maps, seed=int(per * 1e3)))
    assert len(traces) == 1


# --------------------------------------------------------------------------- #
# seed plumbing (the reliability.sweep hash regression)
# --------------------------------------------------------------------------- #
def test_point_seed_is_stable_golden():
    """Pin the derivation: it must not regress to the salted builtin hash
    (which made cross-scheme map sharing depend on PYTHONHASHSEED)."""
    assert [cp.point_seed(0, i) for i in range(4)] == [7919, 15838, 23757, 31676]
    assert cp.point_seed(5, 0) == 5 + 7919


def test_fault_maps_shared_across_schemes_by_construction():
    """One CampaignPoint carries ONE maps array consumed by every scheme; the
    per-scheme auxiliary draws replay the legacy streams, so the maps each
    scheme WOULD have sampled are identical to the shared batch."""
    spec = cp.CampaignSpec(rows=8, cols=8, n_configs=50, seed=11)
    point = cp.sample_point(spec, 0.05)
    for scheme in spec.schemes:
        rng = np.random.default_rng(11)
        maps = random_fault_maps(rng, 50, 8, 8, 0.05)
        np.testing.assert_array_equal(point.maps, maps, err_msg=scheme)
    # spare draws differ per scheme (shapes differ) but are deterministic
    assert set(point.spare_faulty) == {"RR", "CR", "DR"}
    assert point.hyca_caps is not None


def test_sweep_is_reproducible_and_shares_maps():
    """reliability.sweep twice in-process -> identical results (the old
    hash-based seeds were only stable within one PYTHONHASHSEED); and the
    per-point seed is scheme-independent, so RR and CR at the same PER see
    the same fault maps."""
    a = rel.sweep(("RR", "CR"), [0.02, 0.04], rows=8, cols=8, n_configs=40)
    b = rel.sweep(("RR", "CR"), [0.02, 0.04], rows=8, cols=8, n_configs=40)
    assert a == b
    # scheme-independent seeds: replaying the map stream at the derived seed
    # yields the same maps for both schemes at each PER point
    for i, per in enumerate((0.02, 0.04)):
        s = cp.point_seed(0, i)
        m1 = random_fault_maps(np.random.default_rng(s), 40, 8, 8, per)
        m2 = random_fault_maps(np.random.default_rng(s), 40, 8, 8, per)
        np.testing.assert_array_equal(m1, m2)


# --------------------------------------------------------------------------- #
# device samplers
# --------------------------------------------------------------------------- #
def test_device_random_maps_rate_within_binomial_ci():
    per = 0.02
    n, rows, cols = 400, 16, 16
    maps = np.asarray(cp.device_random_maps(jax.random.key(0), n, rows, cols, per))
    assert maps.shape == (n, rows, cols)
    halfwidth = cp.binomial_halfwidth(per, n * rows * cols, z=4.0)  # 4-sigma
    assert abs(maps.mean() - per) < halfwidth


@pytest.mark.parametrize("sigma", [0.5, 1.5, 500.0])
def test_device_clustered_maps_bounds_and_count(sigma):
    """Clustered maps stay in-bounds at ANY sigma (offsets are clipped) and
    keep the exact Binomial count distribution — HyCA's distribution
    insensitivity depends on it."""
    per = 0.03
    n, rows, cols = 200, 16, 16
    maps = np.asarray(cp.device_clustered_maps(
        jax.random.key(1), n, rows, cols, per, cluster_sigma=sigma
    ))
    assert maps.shape == (n, rows, cols) and maps.dtype == bool
    halfwidth = cp.binomial_halfwidth(per, n * rows * cols, z=4.0)
    assert abs(maps.mean() - per) < halfwidth


def test_device_clustered_maps_are_spatially_clustered():
    def mean_pair_dist(maps):
        ds = []
        for m in maps:
            r, c = np.nonzero(m)
            if r.size < 2:
                continue
            d = np.sqrt((r[:, None] - r[None, :]) ** 2 + (c[:, None] - c[None, :]) ** 2)
            ds.append(d[np.triu_indices(r.size, 1)].mean())
        return float(np.mean(ds))

    key = jax.random.key(2)
    cmaps = np.asarray(cp.device_clustered_maps(key, 150, 32, 32, 0.02))
    rmaps = np.asarray(cp.device_random_maps(key, 150, 32, 32, 0.02))
    assert mean_pair_dist(cmaps) < mean_pair_dist(rmaps) - 2.0


def test_device_dppu_capacity_matches_numpy_statistics():
    cfg = DPPUConfig(size=32)
    dev = np.asarray(cp.device_dppu_capacity(jax.random.key(3), cfg, 0.02, 3000))
    ref = red.dppu_capacity(np.random.default_rng(3), cfg, 0.02, 3000)
    assert dev.shape == ref.shape
    assert set(np.unique(dev)) <= set(range(0, cfg.size + 1, cfg.group_size))
    assert abs(dev.mean() - ref.mean()) < 0.5


def test_device_sampler_campaign_end_to_end():
    spec = cp.CampaignSpec(rows=16, cols=16, n_configs=300, sampler="device",
                           dppu=DPPUConfig(size=16), seed=4)
    run = cp.run_campaign(spec, [0.01, 0.04])
    t = run.table()
    assert t["HyCA"][0.01] > 0.9            # well under capacity
    assert t["HyCA"][0.04] >= t["RR"][0.04]  # ordering survives the sampler


# --------------------------------------------------------------------------- #
# batched FaultStates + kernels' batched repair path
# --------------------------------------------------------------------------- #
def test_batched_fault_states_match_fault_state_from_map(rng):
    maps = random_fault_maps(rng, 12, 8, 8, 0.08)
    states = cp.batched_fault_states(maps)
    assert states.fpt.shape == (12, 64, 2)
    for i in range(12):
        ref = fault_state_from_map(maps[i], max_faults=64)
        np.testing.assert_array_equal(
            np.asarray(cp.take_config(states, i).fpt), np.asarray(ref.fpt)
        )


def test_fault_grids_device_matches_host_agu(rng):
    maps = random_fault_maps(rng, 1, 8, 8, 0.1)[0]
    state = fault_state_from_map(maps, max_faults=64, rng=rng)
    host = fault_grids(state, 8, 8, capacity=4)
    dev = jax.jit(lambda s: fault_grids_device(s, 8, 8, capacity=4))(state)
    for h, d in zip(host, dev):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(d))


# --------------------------------------------------------------------------- #
# chaos hook
# --------------------------------------------------------------------------- #
def test_chaos_spec_targets_and_maps():
    spec = cp.ChaosSpec(per=0.05, at_step=3, replicas=(0, 2, 9))
    assert spec.targets(4) == (0, 2)
    assert cp.ChaosSpec().targets(3) == (0, 1, 2)
    maps = cp.chaos_maps(spec, 4, 8, 8)
    assert maps.shape == (4, 8, 8)
    assert 0 < maps.sum() < 4 * 64  # sampled, not degenerate


def test_apply_chaos_merges_into_injector():
    from repro.serving.fault_manager import FaultInjector

    inj = FaultInjector(8, 8, seed=0)
    inj.inject_at(1, 1)
    m = np.zeros((8, 8), bool)
    m[1, 1] = m[2, 3] = m[4, 5] = True
    new = cp.apply_chaos(inj, m)
    assert new == 2 and inj.n_faults == 3  # (1,1) already present


@pytest.mark.slow
def test_fleet_chaos_injection_detected_by_scan():
    """Campaign-sampled chaos maps land in live replicas mid-run; the scan
    pipeline (not the injector) must confirm them afterwards."""
    from repro.serving import FleetConfig, ServerConfig, run_fleet

    chaos = cp.ChaosSpec(per=0.06, at_step=4, seed=3)
    cfg = FleetConfig(
        n_replicas=2, n_spares=0, steps=40, request_rate=0.3, chaos=chaos,
        server=ServerConfig(n_slots=2, smax=24, mode="protected", scan_block=4,
                            rows=8, cols=8, dppu_size=8),
    )
    out = run_fleet(cfg)
    assert out["chaos_injected"] > 0
    assert out["chaos_at_step"] == 4
    confirmed = sum(r["confirmed"] for r in out["replica_summaries"])
    true_faults = sum(r["true_faults"] for r in out["replica_summaries"])
    assert true_faults >= out["chaos_injected"]
    assert confirmed == true_faults  # 36 steps of scan_block=4 sweeps suffice


@pytest.mark.slow
def test_server_on_step_hook_runs_chaos():
    from repro.serving import FaultTolerantServer, ServerConfig

    srv = FaultTolerantServer(ServerConfig(n_slots=1, smax=16, mode="protected"))
    cmap = cp.chaos_maps(cp.ChaosSpec(per=0.1, seed=1), 1, 8, 8)[0]
    seen = {}

    def hook(s):
        if s.step_idx == 2 and "n" not in seen:
            seen["n"] = cp.apply_chaos(s.injector, cmap)

    # the prompt rides in step 0's chunk: four new tokens keep the request
    # in flight through step 3, past the hook's step 2
    srv.run([{"step": 0, "prompt": [1, 2], "max_new_tokens": 4}],
            max_steps=6, on_step=hook)
    assert seen["n"] == int(cmap.sum())
    assert srv.injector.n_faults == int(cmap.sum())


# --------------------------------------------------------------------------- #
# golden-stats acceptance suite (the campaign-stats CI job)
# --------------------------------------------------------------------------- #
GOLDEN_SEED = 0
GOLDEN_N = 1500
GOLDEN_PERS = (0.01, 0.025, 0.04)


@pytest.fixture(scope="module")
def golden_run():
    spec = cp.CampaignSpec(rows=32, cols=32, fault_model="random",
                           n_configs=GOLDEN_N, dppu=DPPUConfig(size=32),
                           seed=GOLDEN_SEED)
    return cp.run_campaign(spec, GOLDEN_PERS)


@pytest.mark.campaign_stats
@pytest.mark.slow
def test_golden_monotone_ffp_degradation(golden_run):
    for scheme in red.SCHEMES:
        for i in range(len(GOLDEN_PERS) - 1):
            a = golden_run.get(scheme, GOLDEN_PERS[i])
            b = golden_run.get(scheme, GOLDEN_PERS[i + 1])
            assert (
                a.fully_functional_prob
                >= b.fully_functional_prob - a.ffp_ci95 - b.ffp_ci95
            ), scheme


@pytest.mark.campaign_stats
@pytest.mark.slow
def test_golden_scheme_ordering(golden_run):
    """HyCA >= DR >= CR/RR at every operating point, within campaign CI."""
    for per in GOLDEN_PERS:
        hyca = golden_run.get("HyCA", per)
        dr = golden_run.get("DR", per)
        for lo in ("CR", "RR"):
            low = golden_run.get(lo, per)
            assert dr.fully_functional_prob >= low.fully_functional_prob \
                - dr.ffp_ci95 - low.ffp_ci95, (per, lo)
        assert hyca.fully_functional_prob >= dr.fully_functional_prob \
            - hyca.ffp_ci95 - dr.ffp_ci95, per
        assert hyca.remaining_power >= dr.remaining_power \
            - hyca.remaining_power_ci95 - dr.remaining_power_ci95, per


@pytest.mark.campaign_stats
@pytest.mark.slow
def test_golden_hyca_capacity_cliff(golden_run):
    """FFP ~1 below the 32/1024 capacity cliff, ~0 above it — the curve the
    campaign must keep reproducing (tolerance = the campaign's own CI)."""
    below = golden_run.get("HyCA", 0.01)
    near = golden_run.get("HyCA", 0.025)
    above = golden_run.get("HyCA", 0.04)
    assert below.fully_functional_prob >= 0.99 - below.ffp_ci95
    assert near.fully_functional_prob >= 0.85 - near.ffp_ci95
    assert above.fully_functional_prob <= 0.10 + above.ffp_ci95
    # remaining power barely degrades even past the cliff (column discard
    # only starts at the first unrepairable fault)
    assert above.remaining_power >= 0.5 - above.remaining_power_ci95


@pytest.mark.campaign_stats
@pytest.mark.slow
def test_golden_protected_accuracy_recovery(rng):
    """Protected forward passes are bit-exact with the clean run for EVERY
    campaign config with #faults <= DPPU capacity, and corrupt for most
    configs when unprotected — the Fig. 2 recovery claim as a batched
    statistical test."""
    rows = cols = 16
    cfg_p = HyCAConfig(rows=rows, cols=cols, dppu=DPPUConfig(size=16, group_size=8),
                       mode="protected")
    cfg_u = dataclasses.replace(cfg_p, mode="unprotected")
    x = jnp.asarray(rng.integers(-8, 8, (8, 32)), jnp.int8)
    w = jnp.asarray(rng.integers(-8, 8, (32, cols)), jnp.int8)
    maps = random_fault_maps(rng, 128, rows, cols, 0.02)
    counts = maps.reshape(128, -1).sum(1)
    states = cp.batched_fault_states(maps, seed=9)
    fwd_p = jax.jit(jax.vmap(lambda s: hyca_matmul(x, w, s, cfg=cfg_p)))
    fwd_u = jax.jit(jax.vmap(lambda s: hyca_matmul(x, w, s, cfg=cfg_u)))
    clean = np.asarray(jnp.matmul(x, w, preferred_element_type=jnp.int32))
    out_p = np.asarray(fwd_p(states))
    out_u = np.asarray(fwd_u(states))
    cap = cfg_p.capacity
    recovered = [np.array_equal(out_p[i], clean) for i in range(128) if counts[i] <= cap]
    assert recovered and all(recovered)
    corrupted = [not np.array_equal(out_u[i], clean) for i in range(128) if counts[i] > 0]
    assert np.mean(corrupted) > 0.5  # stuck-at faults usually visible
