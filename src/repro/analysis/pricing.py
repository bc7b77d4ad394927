"""Pricing cross-check: compiled-HLO cost vs the analytical WorkloadModel.

The audit jit-lowers and *compiles* (never executes) the serving engine's
hot paths — one prompt-chunk prefill, one fused decode step, one batched
speculative-verify step, under both attention impls and representative
tp/pp plans — then reconciles what XLA actually emitted against what the
analytical twin priced for the identical geometry:

* **matmul FLOPs** (the load-bearing check): trip-folded ``dot`` FLOPs of
  :func:`repro.core.hlo.analyze` vs the analytical ``gemm`` + ``bmm``
  operator classes.  Both sides count 2·m·k·n exactly, so this check is
  tight (default 15 %) and is what the mutation gate leans on — perturb
  one pricing constant and the reconciliation breaks loudly.
* **memory bytes** (sanity net): aggregate HLO boundary bytes vs the
  analytical memory totals inside a wide ratio window.  XLA's post-fusion
  boundary traffic legitimately over-counts the analytical hot-loop model
  at audit scale (weight reads replayed per scan iteration at tiny
  d_model, layout copies), so this check only catches order-of-magnitude
  breakage.
* **collective wire bytes**: per-chip ring-convention wire bytes of the
  compiled module vs the ``wire_bytes`` operator records of the sharded
  plan.
* **unpriced work**: every HLO op family carrying a non-trivial share of
  the module's FLOPs or bytes must map to at least one analytical
  operator class present in the matching record stream — a kernel XLA
  emits that the model never prices is exactly the drift this audit
  exists to catch.

Engine/model geometry alignment: the engine compiles static shapes that
attend the slot's full virtual sequence ``L_virt = max_blocks_per_seq ×
block_size`` regardless of the cursor, so every analytical comparator is
evaluated at ``past_len`` chosen to make its ``kv_len`` equal ``L_virt``.
The engine's prefill reads logits at one position while the analytical
convention (paper Table 4) prices the LM head over all positions; the
comparator subtracts the analytically-known difference rather than
widening the tolerance.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

from repro.core import dtypes, hlo
from repro.core.stats import StatsDB
from repro.core.workload import ShardingPlan, WorkloadModel

from repro.configs.base import ArchConfig, Variant

from .findings import Finding, Severity


@dataclasses.dataclass(frozen=True)
class PricingTarget:
    """One engine entry point to lower, compile and reconcile.

    ``lora_rank > 0`` compiles the target with a grouped-LoRA adapter
    pool of that rank (every slot live on an adapter) and adds the
    matching ``WorkloadModel.lora_step`` records to the comparator."""
    kind: str                   # "prefill" | "decode" | "verify"
    attn_impl: str              # "gather" | "paged"
    tp: int = 1
    pp: int = 1
    lora_rank: int = 0

    @property
    def name(self) -> str:
        plan = f"/tp{self.tp}pp{self.pp}" if self.tp * self.pp > 1 else ""
        lora = f"/lora{self.lora_rank}" if self.lora_rank else ""
        return f"{self.kind}/{self.attn_impl}{plan}{lora}"


#: single-chip coverage of every entry point × both attention impls; the
#: audit CLI appends a sharded decode target when the host exposes enough
#: devices (see :func:`repro.analysis.audit.default_targets`)
DEFAULT_TARGETS: Tuple[PricingTarget, ...] = tuple(
    PricingTarget(kind, impl)
    for kind in ("prefill", "decode", "verify")
    for impl in ("gather", "paged"))


@dataclasses.dataclass(frozen=True)
class Tolerances:
    """Knobs of the reconciliation checks (audit CLI flags)."""
    matmul_rtol: float = 0.15          # dot vs gemm+bmm relative tolerance
    bytes_window: Tuple[float, float] = (0.05, 20.0)  # HLO/analytical ratio
    wire_rtol: float = 0.5             # collective wire relative tolerance
    unpriced_share: float = 0.02       # flops/bytes share that needs pricing


@dataclasses.dataclass(frozen=True)
class AuditGeometry:
    """Tiny static shapes shared by every target (seconds-per-compile)."""
    max_slots: int = 2
    block_size: int = 16
    max_blocks_per_seq: int = 2
    n_blocks: int = 8
    chunk_size: int = 32               # == L_virt: prefill fills the span
    spec_k: int = 1                    # verify runs k+1 = 2 queries/slot

    @property
    def l_virt(self) -> int:
        return self.max_blocks_per_seq * self.block_size


@dataclasses.dataclass
class CompiledTarget:
    """One lowered+compiled target with both cost views attached."""
    target: PricingTarget
    hlo_text: str
    module_cost: hlo.ModuleCost
    cost_analysis: dict
    db: StatsDB                        # analytical records, same geometry
    wm: WorkloadModel
    phase: str                         # StatsDB phase of the comparator
    compile_s: float
    batch: int                         # sequences in the compiled dispatch
    q_len: int                         # new tokens per sequence


# ---------------------------------------------------------------------------
# lowering (imports jax lazily so `repro audit --help` stays light)
# ---------------------------------------------------------------------------

def lower_target(cfg: ArchConfig, target: PricingTarget,
                 geom: AuditGeometry = AuditGeometry(),
                 variant: Optional[Variant] = None) -> CompiledTarget:
    """Lower + compile one engine entry point on abstract inputs and build
    its analytical comparator.  Execution-free: parameters and KV state
    are ``ShapeDtypeStruct`` trees, nothing touches device memory."""
    import jax
    import jax.numpy as jnp
    from repro.engine.decode_loop import (make_engine_fns, make_verify_fn)
    from repro.engine.kv_cache import BlockPagedKVCache
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import abstract_params
    from repro.runtime import ShardingPolicy

    n_dev = target.tp * target.pp
    mesh = make_host_mesh(model=target.tp, pipe=target.pp)
    policy = ShardingPolicy()
    cache = BlockPagedKVCache(
        cfg, geom.max_slots, n_blocks=geom.n_blocks,
        block_size=geom.block_size,
        max_blocks_per_seq=geom.max_blocks_per_seq, kv_dtype="bf16",
        lora_slots=(geom.max_slots if target.lora_rank else 0),
        lora_max_rank=target.lora_rank)
    params = abstract_params(cfg)
    state = cache.abstract_state()

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    def boo(*s):
        return jax.ShapeDtypeStruct(s, jnp.bool_)

    t0 = time.perf_counter()
    if target.kind == "prefill":
        prefill_fn, _, _ = make_engine_fns(
            cfg, mesh, policy, cache, chunk_size=geom.chunk_size,
            decode_block=1, temperature=0.0, eos_id=None,
            attn_impl=target.attn_impl)
        compiled = prefill_fn.lower(
            params, state, i32(1, geom.chunk_size), i32(), i32(),
            i32()).compile()
        batch, q_len, phase = 1, geom.chunk_size, "prefill"
    elif target.kind == "decode":
        _, decode_fn, _ = make_engine_fns(
            cfg, mesh, policy, cache, chunk_size=geom.chunk_size,
            decode_block=1, temperature=0.0, eos_id=None,
            attn_impl=target.attn_impl)
        compiled = decode_fn.lower(
            params, state, boo(geom.max_slots), i32(geom.max_slots),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        batch, q_len, phase = geom.max_slots, 1, "decode"
    elif target.kind == "verify":
        verify_fn = make_verify_fn(cfg, mesh, policy, cache,
                                   attn_impl=target.attn_impl)
        q = geom.spec_k + 1
        compiled = verify_fn.lower(
            params, state, i32(geom.max_slots, q), boo(geom.max_slots),
            i32(geom.max_slots)).compile()
        batch, q_len, phase = geom.max_slots, q, "decode"
    else:
        raise ValueError(f"unknown pricing target kind {target.kind!r}")
    compile_s = time.perf_counter() - t0

    text = compiled.as_text()
    mc = hlo.analyze(text, n_devices=n_dev)
    ca = compiled.cost_analysis()

    # analytical comparator at the SAME geometry: the compiled module
    # always attends the full virtual span, so past_len tops kv_len up to
    # L_virt exactly
    wm = WorkloadModel(cfg, variant or Variant(), attn_impl=target.attn_impl,
                       plan=ShardingPlan(tp=target.tp, pp=target.pp))
    past = geom.l_virt - q_len
    if target.kind == "prefill":
        db = wm.prefill(batch, q_len, past_len=past)
    elif target.kind == "decode":
        db = wm.decode_step(batch, past)
    else:
        db = wm.verify_step(batch, past, geom.spec_k)
    if target.lora_rank:
        # every compiled slot is live on a rank-R adapter (the XLA
        # reference computes the whole static batch, so the comparator
        # prices the full mix)
        wm.lora_step([target.lora_rank] * batch, q_len=q_len,
                     max_rank=target.lora_rank, db=db, phase=phase)
    return CompiledTarget(target=target, hlo_text=text, module_cost=mc,
                          cost_analysis=ca, db=db, wm=wm, phase=phase,
                          compile_s=compile_s, batch=batch, q_len=q_len)


# ---------------------------------------------------------------------------
# reconciliation
# ---------------------------------------------------------------------------

#: which analytical op classes can account for each HLO op family; ``None``
#: marks structural/layout ops the analytical model deliberately never
#: prices as work of their own
_ELEMWISE_CLASSES = ("elemw", "nlf", "softmax", "quant", "scan",
                     "embedding")
_FAMILY_MAP: Dict[str, Optional[Tuple[str, ...]]] = {
    "dot": ("gemm", "bmm"),
    "convolution": ("conv",),
    "fusion": _ELEMWISE_CLASSES,
    "reduce": _ELEMWISE_CLASSES,
    "reduce-window": _ELEMWISE_CLASSES,
    "gather": ("gather", "embedding", "kv"),
    "dynamic-slice": ("gather", "embedding", "kv"),
    "dynamic-update-slice": ("kv",),
    "scatter": ("kv",),
    "all-reduce": ("collective",),
    "all-gather": ("collective",),
    "reduce-scatter": ("collective",),
    "all-to-all": ("collective",),
    "collective-permute": ("collective",),
    # layout engineering / bookkeeping: boundary traffic of these is part
    # of XLA's materialization strategy, not separately priced work
    "copy": None, "transpose": None, "reshape": None, "broadcast": None,
    "iota": None, "slice": None, "concatenate": None, "pad": None,
    "reverse": None, "sort": None, "rng": None, "rng-bit-generator": None,
}


def _family_classes(op: str) -> Optional[Tuple[str, ...]]:
    if op in _FAMILY_MAP:
        return _FAMILY_MAP[op]
    if op in hlo._ELEMENTWISE_FLOP_OPS:
        return _ELEMWISE_CLASSES
    return ("<unmapped>",)


def reconcile(ct: CompiledTarget, tol: Tolerances = Tolerances(),
              perturb: Optional[Dict[str, float]] = None) -> List[Finding]:
    """All pricing checks for one compiled target.

    ``perturb`` scales the analytical op-class totals before comparison —
    the mutation-test hook (``--perturb gemm=1.5`` must break the matmul
    reconciliation; a tolerance that survives it is too loose to gate)."""
    out: List[Finding] = []
    t = ct.target
    mc = ct.module_cost
    byc = {k: v.as_dict() for k, v in ct.db.by_op_class(ct.phase).items()}
    for cls, factor in (perturb or {}).items():
        if cls in byc:
            byc[cls] = {k: v * factor for k, v in byc[cls].items()}
    totals = ct.db.totals(ct.phase)

    if mc.unknown_trip_loops:
        out.append(Finding(
            "pricing", "pricing.unknown_trip_loop", Severity.WARNING,
            f"[{t.name}] {mc.unknown_trip_loops} compiled while loop(s) "
            f"lack known_trip_count — trip-folded costs are lower bounds",
            {"target": t.name, "loops": mc.unknown_trip_loops}))

    # ---- matmul FLOPs (tight; carries the mutation gate) ---------------
    ana_matmul = sum(byc.get(c, {}).get("ops", 0.0) for c in ("gemm", "bmm"))
    if t.kind == "prefill":
        # engine reads logits at ONE position; the analytical convention
        # prices the LM head over all chunk positions — subtract the known
        # difference instead of loosening the tolerance
        lm = sum(r.ops for r in ct.db.records
                 if r.op == "lm_head" and r.phase == ct.phase)
        lm *= (perturb or {}).get("gemm", 1.0)
        ntok = ct.batch * ct.q_len
        ana_matmul -= lm * (ntok - 1) / ntok
    hlo_matmul = mc.dot_flops
    # per-chip views: the analytical side is per-chip in tp (sharded
    # division) but NOT in pp — a GSPMD-partitioned module may hold
    # anywhere between one stage's matmuls (1/pp) and, when the partitioner
    # replicates stage compute, all of them.  pp == 1 collapses the window
    # to the plain tolerance band.
    lo = ana_matmul / t.pp * (1.0 - tol.matmul_rtol)
    hi = ana_matmul * (1.0 + tol.matmul_rtol)
    detail = {
        "target": t.name, "hlo_dot_flops": hlo_matmul,
        "analytical_matmul_ops": ana_matmul,
        "ratio": hlo_matmul / ana_matmul if ana_matmul else float("inf"),
        "rtol": tol.matmul_rtol, "perturb": dict(perturb or {}),
        "cost_analysis_flops": ct.cost_analysis.get("flops"),
    }
    if not (lo <= hlo_matmul <= hi):
        classes = sorted(set(perturb or {}) & {"gemm", "bmm"}) or \
            ["gemm", "bmm"]
        out.append(Finding(
            "pricing", "pricing.matmul_mismatch", Severity.ERROR,
            f"[{t.name}] compiled dot FLOPs {hlo_matmul:.4g} disagree "
            f"with the analytical {'+'.join(classes)} operator-class "
            f"total {ana_matmul:.4g} beyond ±{tol.matmul_rtol:.0%} "
            f"(ratio {detail['ratio']:.3f})", detail))
    else:
        out.append(Finding(
            "pricing", "pricing.matmul_ok", Severity.INFO,
            f"[{t.name}] dot FLOPs reconcile: HLO {hlo_matmul:.4g} vs "
            f"analytical {ana_matmul:.4g} "
            f"(ratio {detail['ratio']:.3f})", detail))

    # ---- aggregate bytes (wide sanity window) --------------------------
    ana_mem = totals.mem_total
    ratio = mc.bytes / ana_mem if ana_mem else float("inf")
    if not (tol.bytes_window[0] <= ratio <= tol.bytes_window[1]):
        out.append(Finding(
            "pricing", "pricing.bytes_out_of_window", Severity.ERROR,
            f"[{t.name}] compiled boundary bytes {mc.bytes:.4g} are "
            f"{ratio:.2g}× the analytical memory total {ana_mem:.4g} — "
            f"outside the sanity window {tol.bytes_window}",
            {"target": t.name, "hlo_bytes": mc.bytes,
             "analytical_mem": ana_mem, "ratio": ratio,
             "window": list(tol.bytes_window)}))

    # ---- collective wire bytes -----------------------------------------
    # Compare at SERVING dtype: the analytical model prices wire in
    # dtype_act, while the audit backend may widen on-wire dtypes
    # (XLA:CPU legalizes bf16 compute to f32) — so rebuild the HLO side
    # from ring-convention wire ELEMENTS × serving bytes/element.
    ana_wire = ct.wm.wire_bytes_by_op(ct.db, ct.phase)
    ana_total = sum(ana_wire.values())
    act_el = dtypes.get(ct.wm.variant.dtype_act).bytes_per_el
    hlo_total = (mc.wire_elements * act_el if mc.wire_elements
                 else mc.wire_bytes)
    wire_detail = {"target": t.name, "hlo_wire": mc.collective_wire,
                   "hlo_wire_elements": mc.collective_wire_elements,
                   "hlo_wire_at_serving_dtype": hlo_total,
                   "hlo_counts": mc.collective_counts,
                   "analytical_wire": ana_wire}
    if ana_total == 0.0 and hlo_total > 0.0:
        out.append(Finding(
            "pricing", "pricing.unpriced_collectives", Severity.ERROR,
            f"[{t.name}] compiled module moves {hlo_total:.4g} collective "
            f"wire bytes but the analytical plan records none",
            wire_detail))
    elif ana_total > 0.0:
        rel = abs(hlo_total - ana_total) / ana_total
        wire_detail["rel_err"] = rel
        if rel > tol.wire_rtol:
            # pure-tp plans map 1:1 onto the Megatron collectives the model
            # prices, so a mismatch is an error; pp>1 plans additionally
            # carry GSPMD's staged-scan resharding traffic, which the
            # analytical model deliberately does not price (ROADMAP
            # pipeline-modeling gap) — observe, don't gate
            sev = Severity.ERROR if t.pp == 1 else Severity.INFO
            out.append(Finding(
                "pricing", "pricing.wire_mismatch", sev,
                f"[{t.name}] collective wire bytes (at serving dtype) "
                f"disagree: HLO {hlo_total:.4g} vs analytical "
                f"{ana_total:.4g} (rel err {rel:.0%} > {tol.wire_rtol:.0%})"
                + ("" if t.pp == 1 else
                   " — expected for pp>1: GSPMD stage resharding is an "
                   "unpriced modeling gap"), wire_detail))
        else:
            out.append(Finding(
                "pricing", "pricing.wire_ok", Severity.INFO,
                f"[{t.name}] collective wire reconciles at serving dtype: "
                f"HLO {hlo_total:.4g} vs analytical {ana_total:.4g} "
                f"(rel err {rel:.0%})", wire_detail))

    # ---- unpriced HLO op families --------------------------------------
    tot_f = sum(mc.flops_by_op.values()) or 1.0
    tot_b = sum(mc.bytes_by_op.values()) or 1.0
    families = set(mc.flops_by_op) | set(mc.bytes_by_op)
    present = {c for c, d in byc.items()
               if any(v for v in d.values())}
    for fam in sorted(families):
        f_share = mc.flops_by_op.get(fam, 0.0) / tot_f
        b_share = mc.bytes_by_op.get(fam, 0.0) / tot_b
        if max(f_share, b_share) < tol.unpriced_share:
            continue
        classes = _family_classes(fam)
        if classes is None:
            continue                       # structural: exempt by design
        if not present.intersection(classes):
            out.append(Finding(
                "pricing", "pricing.unpriced_op_family", Severity.WARNING,
                f"[{t.name}] HLO op family {fam!r} carries "
                f"{f_share:.1%} of module FLOPs / {b_share:.1%} of bytes "
                f"but no analytical counterpart class "
                f"({', '.join(classes)}) appears in the record stream",
                {"target": t.name, "family": fam,
                 "flops_share": f_share, "bytes_share": b_share,
                 "expected_classes": list(classes),
                 "present_classes": sorted(present)}))
    return out


def run_pricing(cfg: ArchConfig, targets=DEFAULT_TARGETS,
                tol: Tolerances = Tolerances(),
                perturb: Optional[Dict[str, float]] = None,
                geom: AuditGeometry = AuditGeometry(),
                ) -> Tuple[List[Finding], List[CompiledTarget]]:
    """Lower, compile and reconcile every target; targets whose plan needs
    more devices than the host exposes are skipped with an info finding."""
    import jax
    findings: List[Finding] = []
    compiled: List[CompiledTarget] = []
    for target in targets:
        need = target.tp * target.pp
        if need > jax.device_count():
            findings.append(Finding(
                "pricing", "pricing.target_skipped", Severity.INFO,
                f"[{target.name}] needs {need} devices, host exposes "
                f"{jax.device_count()} — skipped",
                {"target": target.name, "devices_needed": need,
                 "devices": jax.device_count()}))
            continue
        ct = lower_target(cfg, target, geom)
        compiled.append(ct)
        findings.extend(reconcile(ct, tol, perturb))
    return findings, compiled
