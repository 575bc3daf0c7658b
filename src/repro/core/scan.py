"""ScanEngine — the unified, batched, jit-compiled DPPU scan pipeline.

The paper's Section IV-D runtime detection, previously implemented as three
disconnected host-side shards (``core.detection`` Monte-Carlo, the
``runtime.online_verify`` per-PE verifier, and ``serving.fault_manager``'s
one-PE-per-Python-call probe loop), unified behind one engine:

  * **scan state is a device-resident pytree** (:class:`ScanState`: cursor,
    per-PE hit counters; suspect/confirmed masks are derived views) — the
    mode-as-data design FTContext introduced, extended to detection: swapping
    fault maps, probe operands, or hit counters never retraces;
  * **one probe step checks a whole row-block of the virtual PE grid** —
    ``block_rows`` grid rows × all ``cols`` columns per call, the paper's
    *p* DPPU groups probing *p* PEs in parallel (p = block_rows·cols).  The
    AR == BAR + PR comparison runs as a vmapped int32-exact check
    (:func:`repro.kernels.dppu_recompute.probe_check_ref`) or the Pallas
    probe kernel on TPU (:func:`~repro.kernels.dppu_recompute.probe_check`,
    same lane structure as the DPPU recompute kernel);
  * **the boot scan is one ``jax.lax.scan`` over sweeps** (each sweep itself
    a ``lax.scan`` over row-blocks) instead of ``rows·cols`` Python
    iterations — one jitted call for the whole power-on scan;
  * **detections merge into the FPT on-device** via the batched
    :meth:`~repro.core.engine.FaultState.merge` (dedup + leftmost-first
    sort, static shapes), so detection → FPT → DPPU repair stays inside one
    compiled program with zero recompilations.

The analytical cycle model lives in :mod:`repro.core.detection`
(``detection_cycles(rows, cols, dppu_groups=p)`` = ⌈Row·Col/p⌉ + Col);
:meth:`ScanConfig.scan_cycles` reports the same number the engine achieves,
so the Table I / Fig. 15 benchmarks and the runtime agree by construction.

Complementary probe pairing: every PE is checked against a probe matmul AND
its negated-weights complement.  A stuck-at-1 on a *high* accumulator bit is
a no-op on every small negative two's-complement value; negating the weights
flips the accumulator's sign, so one of the pair always exposes it — the
classic BIST pattern pairing the legacy scan applied one PE at a time.
Low-bit stuck-ats can still evade a probe whose accumulator already carries
that bit (bit 0 on an odd value survives negation too); those marginal
faults are what the fresh-operands-per-sweep re-scan and the
``confirm_hits`` hysteresis exist for — detection latency, not a miss,
exactly the paper's re-scan story.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.detection import detection_cycles
from repro.core.engine import FaultState


# --------------------------------------------------------------------------- #
# configuration (static) and state (device-resident pytree)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ScanConfig:
    """Static scan-pipeline geometry.

    ``block_rows`` grid rows are probed per step (all columns at once), i.e.
    ``dppu_groups = block_rows * cols`` PEs in parallel — the paper's
    p-parallel DPPU grouping.  ``confirm_hits`` probe flags promote a PE from
    suspect to confirmed (re-scan of marginal faults).  The boot-scan sweep
    count is the caller's (the probe-schedule length fed to
    :meth:`ScanEngine.boot_scan`), not engine config.
    """

    rows: int = 32
    cols: int = 32
    window: int = 8         # S — MACs recomputed per check (partial result)
    block_rows: int = 1     # grid rows probed per step
    confirm_hits: int = 2

    def __post_init__(self):
        if self.rows <= 0 or self.cols <= 0:
            raise ValueError(f"array must be non-empty, got {self.rows}x{self.cols}")
        if not 1 <= self.block_rows <= self.rows:
            raise ValueError(
                f"block_rows must be in [1, rows={self.rows}], got {self.block_rows}"
            )
        if self.rows % self.block_rows:
            raise ValueError(
                f"block_rows must divide rows (no PE may be probed twice per "
                f"sweep), got rows={self.rows}, block_rows={self.block_rows}"
            )
        if self.confirm_hits < 1:
            raise ValueError(f"confirm_hits must be >= 1, got {self.confirm_hits}")

    @property
    def dppu_groups(self) -> int:
        """p — PEs probed in parallel per scan step."""
        return self.block_rows * self.cols

    @property
    def steps_per_sweep(self) -> int:
        return self.rows // self.block_rows

    def scan_cycles(self) -> int:
        """Full-sweep latency in the analytical model — the engine's probe
        steps plus the Col-cycle comparison drain.  Agrees with
        ``detection_cycles(rows, cols, dppu_groups=p)`` by construction."""
        return detection_cycles(self.rows, self.cols, dppu_groups=self.dppu_groups)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ScanState:
    """Device-resident scan cursor + per-PE hit counters.

    ``cursor``: next row-block index within the current sweep; ``sweep``:
    completed-sweep counter (keys the probe-operand schedule); ``hits``:
    (rows, cols) int32 — probe flags accumulated per PE.  Suspect/confirmed
    are derived: ``1 <= hits < confirm_hits`` / ``hits >= confirm_hits``.
    """

    cursor: jax.Array
    sweep: jax.Array
    hits: jax.Array

    def tree_flatten(self):
        return (self.cursor, self.sweep, self.hits), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


# --------------------------------------------------------------------------- #
# probe schedule (the one recipe every scan path shares)
# --------------------------------------------------------------------------- #
def probe_operands(
    rows: int, cols: int, sweep: int, window: int = 8
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic small-int probe operands for one sweep.

    THE probe recipe — the hardware injector, the scan adapters, and the
    benchmarks all draw from here so the detectability guarantee stays in
    one place: values in [-4, 8) bound |accumulator| ≤ window·32 ≪ 2^30,
    so a bit-30/31 stuck-at is always exposed by one of the complementary
    ±probes.  Operands are fresh per sweep (seeded by the sweep index), so
    marginal low-bit faults that one sweep's accumulators mask are re-scanned
    with different values the next sweep (the paper's re-scan story).
    """
    rng = np.random.default_rng((sweep + 1) * 7919)
    px = rng.integers(-4, 8, size=(rows, window)).astype(np.int32)
    pw = rng.integers(-4, 8, size=(window, cols)).astype(np.int32)
    return px, pw


# --------------------------------------------------------------------------- #
# device-side hardware model (mirror of FaultInjector.corrupted_probe)
# --------------------------------------------------------------------------- #
def corrupt_probe(out: jax.Array, fault_map: jax.Array, stuck_bit: jax.Array,
                  stuck_val: jax.Array) -> jax.Array:
    """What the faulty array returns for an int32 probe matmul: out[i, j] is
    PE(i, j)'s accumulator with its stuck bit forced.  Device-side mirror of
    :meth:`~repro.serving.fault_manager.FaultInjector.corrupted_probe`
    (bit-identical int32 semantics), so whole sweeps run jitted."""
    out = out.astype(jnp.int32)
    mask = jnp.left_shift(jnp.int32(1), stuck_bit)
    bad = jnp.where(stuck_val > 0, out | mask, out & ~mask)
    return jnp.where(fault_map, bad, out)


# --------------------------------------------------------------------------- #
# float-tolerant output check (the OnlineVerifier adapter path)
# --------------------------------------------------------------------------- #
def output_block_check(
    x: jax.Array,
    w: jax.Array,
    out: jax.Array,
    *,
    row0: int,
    row1: int,
    n_cols: int,
    window: int,
    rtol: float,
) -> np.ndarray:
    """AR == BAR + PR over an *output* row-block (rows [row0, row1), columns
    [0, n_cols)): the DPPU lanes recompute the window-long partial result PR
    and the tail BAR and compare against the array's accumulator AR.
    Integer dtypes recompute in the int32 accumulator and compare exactly
    (the paper's datapath — an f32 recompute would lose exactness past
    2^24); float dtypes use ``rtol`` (recomputation reassociates the sum —
    DESIGN.md §2).  Returns a (row1-row0, n_cols) bool mismatch mask
    (host)."""
    kwin = min(window, x.shape[1])
    exact = jnp.issubdtype(out.dtype, jnp.integer)
    acc = jnp.int32 if exact else jnp.float32
    xs = x[row0:row1].astype(acc)
    ws = w[:, :n_cols].astype(acc)
    pr = jnp.matmul(xs[:, :kwin], ws[:kwin], preferred_element_type=acc)
    bar = jnp.matmul(xs[:, kwin:], ws[kwin:], preferred_element_type=acc)
    ar = out[row0:row1, :n_cols].astype(acc)
    expect = pr + bar
    if exact:
        bad = ar != expect
    else:
        # negated <=, not >: a corrupted accumulator can be NaN (stuck bit in
        # the exponent), and NaN must flag as a mismatch
        bad = ~(jnp.abs(ar - expect) <= rtol * (1.0 + jnp.abs(expect)))
    return np.asarray(bad)


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ScanEngine:
    """Batched DPPU scan pipeline over one rows×cols virtual PE array.

    Hashable/static (frozen, config-only), so jitted entry points take the
    engine as a static argument: :func:`scan_probe_step` (one row-block),
    :func:`scan_sweep` (one whole-array sweep + FPT merge) and
    :func:`boot_scan` (``lax.scan`` over sweeps) — all retrace-free across
    fault-map, probe, and state value changes.

    ``backend``: ``"jnp"`` (vmapped reference check — CPU/GPU),
    ``"pallas"`` (compiled TPU probe kernel) or ``"interpret"`` (the kernel
    body interpreted — test path).  Pick with :func:`build_scan_engine`.
    """

    cfg: ScanConfig
    backend: str = "jnp"

    # -- probe comparison ------------------------------------------------- #
    def _mismatch(self, px: jax.Array, pw: jax.Array, ar: jax.Array) -> jax.Array:
        from repro.kernels.dppu_recompute import probe_check, probe_check_ref

        if self.backend == "jnp":
            return probe_check_ref(px, pw, ar, window=self.cfg.window)
        return probe_check(px, pw, ar, interpret=self.backend == "interpret").astype(bool)

    # -- state ------------------------------------------------------------ #
    def init_state(self) -> ScanState:
        c = self.cfg
        return ScanState(
            cursor=jnp.int32(0), sweep=jnp.int32(0),
            hits=jnp.zeros((c.rows, c.cols), jnp.int32),
        )

    def confirmed(self, state: ScanState) -> jax.Array:
        return state.hits >= self.cfg.confirm_hits

    def suspect(self, state: ScanState) -> jax.Array:
        return (state.hits >= 1) & ~self.confirmed(state)

    # -- one probe step: a whole row-block of the grid --------------------- #
    def probe_block(
        self,
        state: ScanState,
        px: jax.Array,       # (rows, K) probe activations
        pw: jax.Array,       # (K, cols) probe weights
        ar: jax.Array,       # (rows, cols) array readback for  px @ pw
        ar_neg: jax.Array,   # (rows, cols) array readback for  px @ -pw
    ) -> tuple[ScanState, jax.Array, jax.Array]:
        """Probe grid rows [cursor·block, cursor·block + block) — all
        columns — against the complementary probe pair.  Returns
        (next state, (block_rows, cols) raw mismatch flags, block start row).
        Already-confirmed PEs keep failing their probes (the flags report
        hardware truth) but stop accumulating hits (the runtime already
        knows).  Fully traceable — no host round-trips."""
        c = self.cfg
        row0 = state.cursor * c.block_rows
        px_b = jax.lax.dynamic_slice(px, (row0, 0), (c.block_rows, px.shape[1]))
        ar_b = jax.lax.dynamic_slice(ar, (row0, 0), (c.block_rows, c.cols))
        arn_b = jax.lax.dynamic_slice(ar_neg, (row0, 0), (c.block_rows, c.cols))
        return self.probe_presliced(state, px_b, pw, ar_b, arn_b)

    def probe_presliced(
        self,
        state: ScanState,
        px_b: jax.Array,     # (block_rows, K) — the cursor block's rows only
        pw: jax.Array,
        ar_b: jax.Array,     # (block_rows, cols)
        arn_b: jax.Array,    # (block_rows, cols)
    ) -> tuple[ScanState, jax.Array, jax.Array]:
        """Probe step on an already-sliced row-block (the serving hot path:
        the host knows the cursor, so it only materializes — and the
        hardware only corrupts — the block actually being probed)."""
        c = self.cfg
        row0 = state.cursor * c.block_rows
        flags = self._mismatch(px_b, pw, ar_b) | self._mismatch(px_b, -pw, arn_b)
        hits_b = jax.lax.dynamic_slice(state.hits, (row0, 0), (c.block_rows, c.cols))
        countable = flags & (hits_b < c.confirm_hits)
        hits = jax.lax.dynamic_update_slice(
            state.hits, hits_b + countable.astype(jnp.int32), (row0, 0)
        )
        last = state.cursor == c.steps_per_sweep - 1
        nxt = ScanState(
            cursor=jnp.where(last, 0, state.cursor + 1).astype(jnp.int32),
            sweep=state.sweep + last.astype(jnp.int32),
            hits=hits,
        )
        return nxt, flags, row0

    # -- one whole-array sweep + on-device FPT merge ----------------------- #
    def sweep(
        self,
        state: ScanState,
        fstate: FaultState,
        fault_map: jax.Array,
        stuck_bit: jax.Array,
        stuck_val: jax.Array,
        px: jax.Array,
        pw: jax.Array,
    ) -> tuple[ScanState, FaultState]:
        """One full sweep: the hardware responds to the probe pair once, then
        ``lax.scan`` walks every row-block and the sweep's confirmed set
        merges into the FPT on-device (batched, deduped)."""
        clean = jnp.matmul(
            px.astype(jnp.int32), pw.astype(jnp.int32),
            preferred_element_type=jnp.int32,
        )
        clean_neg = jnp.matmul(
            px.astype(jnp.int32), (-pw).astype(jnp.int32),
            preferred_element_type=jnp.int32,
        )
        ar = corrupt_probe(clean, fault_map, stuck_bit, stuck_val)
        ar_neg = corrupt_probe(clean_neg, fault_map, stuck_bit, stuck_val)

        def body(st, _):
            st, _, _ = self.probe_block(st, px, pw, ar, ar_neg)
            return st, None

        state, _ = jax.lax.scan(body, state, None, length=self.cfg.steps_per_sweep)
        return state, fstate.merge(self.confirmed(state))

    # -- power-on scan: lax.scan over sweeps -------------------------------- #
    def boot_scan(
        self,
        state: ScanState,
        fstate: FaultState,
        fault_map: jax.Array,
        stuck_bit: jax.Array,
        stuck_val: jax.Array,
        px_stack: jax.Array,   # (n_sweeps, rows, K)
        pw_stack: jax.Array,   # (n_sweeps, K, cols)
    ) -> tuple[ScanState, FaultState]:
        """The whole power-on scan as ONE traced program: ``lax.scan`` over
        the sweep axis of the pre-sampled probe schedule, each sweep itself a
        ``lax.scan`` over row-blocks — where the legacy path paid
        ``sweeps · rows · cols`` Python iterations and host round-trips."""

        def body(carry, xw):
            st, fs = carry
            st, fs = self.sweep(st, fs, fault_map, stuck_bit, stuck_val, *xw)
            return (st, fs), None

        (state, fstate), _ = jax.lax.scan(body, (state, fstate), (px_stack, pw_stack))
        return state, fstate


# --------------------------------------------------------------------------- #
# jitted entry points (engine static — value swaps never retrace)
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("engine",))
def scan_probe_step(engine: ScanEngine, state: ScanState, px, pw, ar, ar_neg):
    return engine.probe_block(state, px, pw, ar, ar_neg)


@functools.partial(jax.jit, static_argnames=("engine",))
def scan_probe_block(engine: ScanEngine, state: ScanState, px_b, pw, ar_b, arn_b):
    return engine.probe_presliced(state, px_b, pw, ar_b, arn_b)


@functools.partial(jax.jit, static_argnames=("engine",))
def scan_sweep(engine: ScanEngine, state, fstate, fault_map, stuck_bit, stuck_val, px, pw):
    return engine.sweep(state, fstate, fault_map, stuck_bit, stuck_val, px, pw)


@functools.partial(jax.jit, static_argnames=("engine",))
def boot_scan(engine: ScanEngine, state, fstate, fault_map, stuck_bit, stuck_val, px_stack, pw_stack):
    return engine.boot_scan(state, fstate, fault_map, stuck_bit, stuck_val, px_stack, pw_stack)


def build_scan_engine(
    rows: int,
    cols: int,
    *,
    window: int = 8,
    block_rows: int = 1,
    confirm_hits: int = 2,
    backend: str | None = None,
) -> ScanEngine:
    """Build a :class:`ScanEngine`, choosing the probe backend **once** (the
    FTContext pattern): the compiled Pallas probe kernel on TPU, the vmapped
    jnp reference elsewhere."""
    cfg = ScanConfig(
        rows=rows, cols=cols, window=window, block_rows=block_rows,
        confirm_hits=confirm_hits,
    )
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("jnp", "pallas", "interpret"):
        raise ValueError(f"unknown scan backend {backend!r}")
    return ScanEngine(cfg=cfg, backend=backend)
