"""FTContext — the unified fault-aware execution layer.

Replaces the ad-hoc ``dot: Callable`` / ``protect_mask`` injection that used
to be threaded through every model-family signature.  One pytree object
carries the whole fault-tolerance story:

  * the device-resident :class:`~repro.core.engine.FaultState` (a traced
    leaf, so fault tables update without recompiles);
  * the :class:`~repro.core.engine.HyCAConfig` (virtual array geometry, DPPU
    capacity, off/protected/unprotected mode) — static;
  * a :class:`ProtectPolicy` naming which call *sites* (attention
    projections, FFN, MoE experts, SSM projections, LM head, …) run on the
    protected array and which fraction of main-stack layers is protected —
    static, so unprotected sites/layers lower to a plain ``jnp.matmul`` and
    pay **zero** overhead (the old ``jnp.where(flag, dot(a,b), matmul(a,b))``
    gate evaluated both branches);
  * the dispatch decision (plain / two-pass DPPU / fused Pallas kernel) plus
    the fused backend (compiled TPU kernel, interpret mode, or the pure-jnp
    oracle), chosen **once** at context build — never per call;
  * the active repro.repair :class:`~repro.core.engine.RepairPlan` (a traced
    leaf beside the fault table).

The context computes and nothing else: observation stays outside it.  The
repro.obs counters are folded beside the compiled step from a call ledger
that ``repro.obs.trace_site_calls`` discovers once, through the transient
``_obs_record`` hook — never a pytree field.

Models receive an optional ``ftc`` and route every weight matmul through
``ftc.matmul(x, w, site="attn.qkv")`` (or ``ftc.einsum`` for batched expert
matmuls).  ``ftc=None`` is the production fast path: plain matmuls, no fault
machinery anywhere in the lowered HLO.

Bit-exactness invariant (property-tested across every registry config):
with ``mode="protected"`` and #faults ≤ DPPU capacity, every dispatch mode
produces outputs bit-exact with ``mode="off"``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.engine import (
    META_PRUNE_SHIFT,
    FaultState,
    HyCAConfig,
    RepairPlan,
    abft_checksums,
    apply_fault_epilogue,
    fault_meta_grid,
    hyca_matmul,
    validate_fault_state,
    validate_repair_plan,
)

# Protection sites — the call-site vocabulary of the model stack.  A site
# names a *class* of weight matmuls, not a tensor: the policy decides per
# site, the layer fraction decides per main-stack layer.
SITES = (
    "attn.qkv",   # Q/K/V (and MLA LoRA down/up) projections
    "attn.out",   # attention output projection
    "ffn",        # dense FFN up/gate/down (incl. MoE shared experts, RWKV channel mix)
    "moe.router", # MoE router logits
    "moe.expert", # batched per-expert matmuls
    "ssm.in",     # SSM/RWKV input-side projections (in_proj, r/k/v/g, decay LoRA)
    "ssm.out",    # SSM/RWKV output projections
    "head",       # LM head (dense logits + chunked-loss head)
    "mm.proj",    # multimodal projector
)

DISPATCHES = ("plain", "twopass", "fused")
FUSED_BACKENDS = ("pallas", "interpret", "ref")

# Batched-weight einsum patterns FTContext.einsum understands (the MoE
# expert matmuls, activation-major and weight-transposed).
EINSUM_SPECS = ("becd,edf->becf", "becf,efd->becd")


@dataclasses.dataclass(frozen=True)
class ProtectPolicy:
    """Static per-site / per-layer protection policy.

    ``sites``: which call sites run on the protected array (``None`` = all of
    :data:`SITES`).  ``layer_fraction``: leading fraction of each main-stack
    layer scan that runs protected; the remaining layers are lowered with
    plain matmuls (zero fault-machinery overhead, not a traced select).
    ``abft``: carry ABFT checksum lanes beside protected matmuls —
    :meth:`FTContext.abft_matmul` returns ``(out, chk_row, chk_col)`` with
    ``out`` bit-exact with :meth:`FTContext.matmul` (the checksums ride
    beside the data path, never inside it); off (the default) makes
    ``abft_matmul`` return ``None`` checksums at zero extra cost.
    """

    sites: frozenset[str] | None = None
    layer_fraction: float = 1.0
    abft: bool = False

    def __post_init__(self):
        if self.sites is not None:
            unknown = set(self.sites) - set(SITES)
            if unknown:
                raise ValueError(f"unknown protection sites {sorted(unknown)}; known: {SITES}")
        if not 0.0 <= self.layer_fraction <= 1.0:
            raise ValueError(f"layer_fraction must be in [0, 1], got {self.layer_fraction}")

    def covers(self, site: str) -> bool:
        if site not in SITES:
            raise ValueError(f"unknown site {site!r}; known: {SITES}")
        return self.sites is None or site in self.sites

    def n_protected_layers(self, n_layers: int) -> int:
        return min(n_layers, int(math.ceil(self.layer_fraction * n_layers)))


def _as_2d(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FTContext:
    """Fault-aware execution context.  A pytree: ``state`` and ``plan`` are
    the (traced) leaves, everything else is static aux data — jit a function
    over an ``FTContext`` argument and only fault-table and plan *values*
    change per call.

    Build with :func:`build_ftcontext` (which picks the fused backend for the
    current JAX backend and validates the fault table against the array
    geometry) rather than direct construction.
    """

    state: FaultState | None
    hyca: HyCAConfig
    policy: ProtectPolicy = dataclasses.field(default_factory=ProtectPolicy)
    dispatch: str = "twopass"
    fused_backend: str = "ref"
    # (bm, bn, bk) kernel block, or "auto" to resolve per call shape through
    # the autotune cache (kernels.autotune).  Hashable either way — aux data.
    fused_block: tuple[int, int, int] | str = "auto"
    # repro.repair: one RepairPlan for all sites, or {site: RepairPlan}.
    # A traced leaf like `state` — plan swaps never recompile (the dict's
    # keys, like every other treedef change, recompile once when the plan
    # *structure* first appears).
    plan: object = None
    # transient trace-time hook used by repro.obs.trace_site_calls to
    # discover the call ledger; never part of the pytree (a callable is not
    # hashable aux data and must not leak into jit keys)
    _obs_record: object = dataclasses.field(default=None, repr=False, compare=False)

    # ------------------------------------------------------------------ #
    # pytree protocol
    # ------------------------------------------------------------------ #
    def tree_flatten(self):
        aux = (self.hyca, self.policy, self.dispatch, self.fused_backend,
               self.fused_block)
        return (self.state, self.plan), aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], *aux, plan=leaves[1])

    # ------------------------------------------------------------------ #
    # static predicates
    # ------------------------------------------------------------------ #
    @property
    def mode(self) -> str:
        return self.hyca.mode

    @property
    def active(self) -> bool:
        """Does any matmul route through the fault-aware path at all?"""
        return self.state is not None and self.hyca.mode != "off"

    def protects(self, site: str) -> bool:
        return self.active and self.policy.covers(site)

    def n_protected_layers(self, n_layers: int) -> int:
        if not self.active:
            return 0
        return self.policy.n_protected_layers(n_layers)

    def with_state(self, state: FaultState | None) -> "FTContext":
        """Same static context, new fault table (per-step serving update)."""
        return dataclasses.replace(self, state=state)

    def with_plan(self, plan) -> "FTContext":
        """Same static context, new repair plan (repro.repair remediation).
        Keeping the plan *structure* stable (always a plan, identity when no
        remediation is active) makes plan swaps leaf-only: zero recompiles."""
        return dataclasses.replace(self, plan=plan)

    def _plan_for(self, site: str) -> RepairPlan | None:
        if self.plan is None or isinstance(self.plan, RepairPlan):
            return self.plan
        return self.plan.get(site)

    # ------------------------------------------------------------------ #
    # op dispatch
    # ------------------------------------------------------------------ #
    def matmul(self, x: jax.Array, w: jax.Array, *, site: str) -> jax.Array:
        """``x @ w`` with ``x: (..., K)`` and ``w: (K, N)``; routed through
        the protected virtual array when the policy covers ``site``.

        The clean accumulate stays in the caller's layout (no pre-reshape),
        so it lowers to the identical XLA dot as the unprotected path —
        required for the bit-exact protected==off invariant.

        Every operation of the call (casts, pads, fault grid, kernel, slice)
        runs under ``jax.named_scope(site)``, so the device trace names it
        after its site; a scope changes metadata only.
        """
        with jax.named_scope(site):
            if self._obs_record is not None:
                protected = self.protects(site) and self.dispatch != "plain"
                self._obs_record(
                    site=site, m=math.prod(x.shape[:-1]), n=int(w.shape[-1]),
                    count=1, dispatch=self.dispatch if protected else "plain",
                    protected=protected,
                    operand_dtype=jnp.promote_types(x.dtype, w.dtype).name,
                )
            if not self.protects(site):
                return jnp.matmul(x, w)
            plan = self._plan_for(site)
            if self.dispatch == "plain":
                out = jnp.matmul(x, w)
            elif self.dispatch == "twopass":
                out = hyca_matmul(x, w, self.state, cfg=self.hyca, plan=plan)
            elif self.dispatch == "fused":
                out = self._fused(x, w, plan, site=site)
            else:
                raise ValueError(f"unknown dispatch {self.dispatch!r}; known: {DISPATCHES}")
            return out.astype(x.dtype)

    def abft_matmul(
        self, x: jax.Array, w: jax.Array, *, site: str, wc: jax.Array | None = None
    ) -> tuple[jax.Array, jax.Array | None, jax.Array | None]:
        """:meth:`matmul` plus ABFT checksum lanes carried through the array
        (``policy.abft`` — the third detector, docs/faults.md).

        Returns ``(out, chk_row, chk_col)``.  ``out`` is ALWAYS bit-exact
        with ``matmul(x, w, site=site)`` on the same dispatch: the checksums
        are computed beside the data matmul
        (:func:`~repro.core.engine.abft_checksums`), never appended into it,
        so turning the knob on cannot perturb the protected==off invariant.
        Both checksums are ``None`` when the policy does not cover the site
        or ``policy.abft`` is off; ``chk_col`` additionally needs ``wc`` (the
        encode-time weight checksum, :func:`~repro.core.engine.abft_encode`)
        — without it only MAC/accumulator faults are detectable, with it
        weight-memory flips are too.  Checksum corruption is element-granular
        (the two-pass/ref-fused semantics); under the Pallas backend's
        tile-granular drain the checksum lane is a conservative detector,
        not a bit-mirror of the kernel's corruption placement.

        Syndromes and thresholds live in ``repro.transient.abft`` — this
        method only carries the lanes."""
        out = self.matmul(x, w, site=site)
        if not (self.protects(site) and self.policy.abft):
            return out, None, None
        # plain dispatch leaves the data path uncorrupted — the checksum
        # lanes must match (clean), or a healthy array would raise syndromes
        state = None if self.dispatch == "plain" else self.state
        chk_row, chk_col = abft_checksums(
            x, w, state, cfg=self.hyca, plan=self._plan_for(site),
            wc=wc,
        )
        return out, chk_row, chk_col

    def einsum(self, spec: str, x: jax.Array, w: jax.Array, *, site: str) -> jax.Array:
        """Batched-weight einsum through the protected array.

        Supports the MoE expert-matmul patterns (:data:`EINSUM_SPECS`): each
        expert's matmul is one virtual-array execution.  Under
        ``dispatch="fused"`` the expert axis becomes the outermost kernel
        grid dimension (``ft_matmul_batched``) — one launch for all experts —
        or, on the ref backend, one clean einsum plus a broadcast fault
        epilogue.  ``dispatch="twopass"`` vmaps the two-pass engine over
        experts.

        The spec is validated *first* (unsupported specs raise the same
        clear error on every dispatch path, before any shape indexing).
        Like :meth:`matmul`, the call runs under ``jax.named_scope(site)``.
        """
        if spec not in EINSUM_SPECS:
            raise ValueError(
                f"FTContext.einsum supports the expert-matmul patterns "
                f"{EINSUM_SPECS} only, got {spec!r}"
            )
        with jax.named_scope(site):
            if self._obs_record is not None:
                protected = self.protects(site) and self.dispatch != "plain"
                self._obs_record(
                    site=site, m=x.shape[0] * x.shape[2], n=int(w.shape[-1]),
                    count=x.shape[1], dispatch=self.dispatch if protected else "plain",
                    protected=protected,
                    operand_dtype=jnp.promote_types(x.dtype, w.dtype).name,
                )
            if not self.protects(site) or self.dispatch == "plain":
                return jnp.einsum(spec, x, w)
            plan = self._plan_for(site)
            if self.dispatch == "fused":
                return self._fused_einsum(spec, x, w, plan, site=site).astype(x.dtype)
            return self._einsum_twopass(spec, x, w, plan).astype(x.dtype)

    def _einsum_twopass(self, spec: str, x, w, plan: RepairPlan | None):
        b, e, c, d = x.shape
        xe = x.transpose(1, 0, 2, 3).reshape(e, b * c, d)
        state, cfg = self.state, self.hyca
        out = jax.vmap(lambda xi, wi: hyca_matmul(xi, wi, state, cfg=cfg, plan=plan))(xe, w)
        n = w.shape[-1]
        return out.reshape(e, b, c, n).transpose(1, 0, 2, 3)

    # ------------------------------------------------------------------ #
    # fused dispatch
    # ------------------------------------------------------------------ #
    def _block_for(self, m: int, n: int, k: int, dtype) -> tuple[int, int, int]:
        """The kernel block for one call: the autotune lookup keyed on the
        operand dtype, or the explicit block checked against that dtype's
        sublane tile (context build could only check the f32 one)."""
        from repro.kernels.autotune import resolve_block, validate_fused_block

        if self.fused_block == "auto":
            return resolve_block(m, n, k, dtype=dtype, backend=self.fused_backend)
        return validate_fused_block(self.fused_block, backend=self.fused_backend, dtype=dtype)

    def _prune_mask(self, plan: RepairPlan | None, meta: jax.Array,
                    bm: int, bn: int, mp: int, np_: int) -> jax.Array | None:
        """Element-granular prune AND-mask for the kernel drain (the engine
        zeroes pruned PEs per output ELEMENT, and the dispatch layer keeps
        that placement at any block size), from the prune bit of the packed
        meta grid.  A single periodic (bm, bn) tile when the block is
        PE-aligned — broadcast to every grid cell, no per-tile HBM traffic —
        else the full padded (mp, np_) mask."""
        if plan is None:
            return None
        cfg = self.hyca
        pruned = (meta >> META_PRUNE_SHIFT) & 1
        keep = jnp.where(pruned > 0, jnp.int32(0), jnp.int32(-1))
        if bm % cfg.rows == 0 and bn % cfg.cols == 0:
            return jnp.tile(keep, (bm // cfg.rows, bn // cfg.cols))
        return jnp.tile(keep, (-(-mp // cfg.rows), -(-np_ // cfg.cols)))[:mp, :np_]

    def _record_fallback(self, site: str, reason: str) -> None:
        from repro.obs.fallbacks import record_site_fallback  # deferred: obs←core

        record_site_fallback(site, reason)

    def _fused(self, x: jax.Array, w: jax.Array, plan: RepairPlan | None = None,
               *, site: str = "?") -> jax.Array:
        cfg = self.hyca
        if self.fused_backend == "ref":
            # Single-pass jnp formulation (non-TPU): the clean accumulate is
            # the IDENTICAL matmul the unprotected path lowers (structural
            # protected==off bit-exactness), and the whole fault story —
            # stuck-at mux for effective faults, DPPU repair (= skipping the
            # mux), plan remap and prune — collapses into one packed-meta
            # gather + select chain over the output view
            # (engine.fault_meta_grid / apply_fault_epilogue).  No
            # corrupt-everything pass, no repair overwrite pass, no
            # post-kernel prune pass: that is the fused win off-TPU.
            pref = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.float32
            out = jnp.matmul(x, w, preferred_element_type=pref)
            meta = fault_meta_grid(self.state, cfg, plan)
            shape = out.shape
            out2 = out.reshape(-1, shape[-1])
            return apply_fault_epilogue(out2, meta, cfg.rows, cfg.cols).reshape(shape)
        # Pallas kernel (compiled on TPU, interpret elsewhere): single fused
        # pass — repaired tiles skip the fault mux at drain, the RepairPlan's
        # col_map is a pre-kernel gather of the tiny (rows, cols) grids and
        # its element-granular prune mask zeroes inside the drain, so
        # plan-active decode costs zero extra output-sized HBM passes.  The
        # stuck-at mux is at (bm, bn) tile→PE granularity; inputs enter in
        # their common dtype (bf16 serving stays bf16: the kernel multiplies
        # into an f32 accumulator, and a bf16 product is exact in f32), are
        # zero-padded to block multiples, and the result is sliced back.
        if jnp.issubdtype(x.dtype, jnp.integer) or jnp.issubdtype(w.dtype, jnp.integer):
            # the kernel accumulates f32; int datapaths keep the engine's
            # exact int32 stuck-at semantics via the two-pass path
            self._record_fallback(site, "int-dtype-kernel")
            return hyca_matmul(x, w, self.state, cfg=cfg, plan=plan)
        from repro.kernels.ft_matmul import ft_matmul  # deferred: pallas import

        x2, lead = _as_2d(x)
        m, k = x2.shape
        n = w.shape[-1]
        dtype = jnp.promote_types(x.dtype, w.dtype)
        bm, bn, bk = self._block_for(m, n, k, dtype)
        mp, kp, np_ = -(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn
        xp = jnp.pad(x2.astype(dtype), ((0, mp - m), (0, kp - k)))
        wp = jnp.pad(w.astype(dtype), ((0, kp - k), (0, np_ - n)))
        meta = fault_meta_grid(self.state, cfg, plan)
        out = ft_matmul(
            xp, wp, meta, self._prune_mask(plan, meta, bm, bn, mp, np_),
            bm=bm, bn=bn, bk=bk, interpret=self.fused_backend == "interpret",
        )
        return out[:m, :n].reshape(*lead, n)

    def _fused_einsum(self, spec: str, x, w, plan: RepairPlan | None, *, site: str):
        cfg = self.hyca
        b, e, c, d = x.shape
        n = w.shape[-1]
        if self.fused_backend == "ref":
            # One clean einsum (bitwise the plain path's accumulate — each
            # expert's dot is unchanged) + ONE broadcast fault epilogue: the
            # per-expert output view is (b·c, n) with row index bi·c + ci, so
            # a (b, 1, c, 1) row-residue grid lets a single packed-meta
            # gather cover every expert.  Replaces the vmapped two-pass
            # engine (corrupt + overwrite + prune per expert).
            pref = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.float32
            if pref == jnp.float32:
                # XLA:CPU has no batched bf16 x bf16 -> f32 dot ("DotThunk");
                # a bf16 product is exact in f32, so f32 operands lose nothing
                x, w = x.astype(jnp.float32), w.astype(jnp.float32)
            out = jnp.einsum(spec, x, w, preferred_element_type=pref)
            meta = fault_meta_grid(self.state, cfg, plan)
            row_res = (
                (jnp.arange(b)[:, None] * c + jnp.arange(c)[None, :]) % cfg.rows
            )[:, None, :, None]
            return apply_fault_epilogue(out, meta, cfg.rows, cfg.cols, row_residue=row_res)
        if jnp.issubdtype(x.dtype, jnp.integer) or jnp.issubdtype(w.dtype, jnp.integer):
            self._record_fallback(site, "int-dtype-kernel")
            return self._einsum_twopass(spec, x, w, plan)
        # expert axis → outermost kernel grid dimension: ONE launch for all
        # experts instead of a vmapped two-pass pipeline per expert
        from repro.kernels.ft_matmul import ft_matmul_batched  # deferred: pallas import

        xe = x.transpose(1, 0, 2, 3).reshape(e, b * c, d)
        m, kdim = b * c, d
        dtype = jnp.promote_types(x.dtype, w.dtype)
        bm, bn, bk = self._block_for(m, n, kdim, dtype)
        mp, kp, np_ = -(-m // bm) * bm, -(-kdim // bk) * bk, -(-n // bn) * bn
        xp = jnp.pad(xe.astype(dtype), ((0, 0), (0, mp - m), (0, kp - kdim)))
        wp = jnp.pad(w.astype(dtype), ((0, 0), (0, kp - kdim), (0, np_ - n)))
        meta = fault_meta_grid(self.state, cfg, plan)
        out = ft_matmul_batched(
            xp, wp, meta, self._prune_mask(plan, meta, bm, bn, mp, np_),
            bm=bm, bn=bn, bk=bk, interpret=self.fused_backend == "interpret",
        )
        return out[:, :m, :n].reshape(e, b, c, n).transpose(1, 0, 2, 3)


def fused_backend() -> str:
    """The fused dispatch's backend on this process's default device: the
    compiled Pallas kernel on a TPU, the single-pass jnp formulation
    elsewhere.  Never a fallback: a kernel that cannot lower on the chip
    raises there."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def build_ftcontext(
    state: FaultState | None,
    hyca: HyCAConfig,
    *,
    policy: ProtectPolicy | None = None,
    dispatch: str = "twopass",
    fused_block: tuple[int, int, int] | str = "auto",
    plan=None,
    autotune_shapes=None,
) -> FTContext:
    """Build an :class:`FTContext`, choosing the fused backend **once**.

    On a TPU backend the fused dispatch lowers the compiled Pallas kernel;
    everywhere else it lowers the single-pass jnp formulation (element-
    granular, bit-identical to the two-pass engine semantics — and, unlike
    the engine, ONE output pass).  Pass ``dispatch="fused"`` + a non-TPU
    backend and you get full fault semantics plus most of the fused win.

    ``fused_block="auto"`` (the default) resolves kernel blocks per call
    shape through the persisted autotune cache
    (``experiments/autotune/ft_matmul.json``, loaded here once per process;
    see docs/kernels.md); an explicit ``(bm, bn, bk)`` is validated against
    the backend's tile constraints now — a clear build-time error instead of
    a Pallas lowering failure at first trace.  ``autotune_shapes`` optionally
    runs the measured search for a list of ``(m, n, k)`` shapes at build.

    Host-side :func:`~repro.core.engine.validate_fault_state` runs here: FPT
    entries outside the (rows, cols) array geometry raise immediately instead
    of silently wrapping around at matmul time.
    """
    if dispatch not in DISPATCHES:
        raise ValueError(f"unknown dispatch {dispatch!r}; known: {DISPATCHES}")
    if state is not None:
        validate_fault_state(state, hyca.rows, hyca.cols)
    if plan is not None:
        for p in (plan.values() if isinstance(plan, dict) else (plan,)):
            validate_repair_plan(p, hyca.rows, hyca.cols)
    backend = fused_backend()
    from repro.kernels import autotune  # deferred: keeps core import-light

    if fused_block == "auto":
        autotune.load_cache()  # warm the persisted cache once per process
        if autotune_shapes:
            kernel_backend = "pallas" if backend == "pallas" else "interpret"
            for m, n, k in autotune_shapes:
                autotune.autotune_block(int(m), int(n), int(k),
                                        backend=kernel_backend,
                                        rows=hyca.rows, cols=hyca.cols)
    else:
        fused_block = autotune.validate_fused_block(fused_block, backend=backend)
    return FTContext(
        state=state,
        hyca=hyca,
        policy=policy or ProtectPolicy(),
        dispatch=dispatch,
        fused_backend=backend,
        fused_block=fused_block,
        plan=plan,
    )


def site_matmul(ftc: FTContext | None, site: str) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """The model-side helper: a plain ``jnp.matmul`` when no context is
    threaded (production fast path), else the context's dispatcher bound to
    one call site."""
    if ftc is None:
        return jnp.matmul
    return lambda x, w: ftc.matmul(x, w, site=site)
