"""Engine compile hygiene: donation aliasing and retrace detection.

Two failure modes silently wreck serving throughput without changing a
single output token:

* **Lost donation** — the engine donates its KV state into every jitted
  step (``donate_argnums=(1,)``); if XLA cannot alias a donated pool
  buffer to its output (dtype change, layout mismatch, an accidental
  read-after-write introduced by a refactor), it silently *copies* the
  whole KV pool every engine step.  The auditor statically asserts, on
  the already-compiled modules of the pricing pass, that the big KV-pool
  buffers appear in the module's ``input_output_alias`` table.
* **Retrace churn** — every distinct argument shape retraces and
  recompiles a jitted entry point.  The engine is shaped so a mixed-
  length serving run compiles each entry point ONCE (chunk padding,
  static decode batch); a shape leak (e.g. threading a Python int into
  an argument) multiplies compile time by the number of distinct
  lengths.  The auditor scripts a tiny mixed-length engine run and fails
  if ``prefill``/``decode`` accumulated more than one compiled entry
  (``verify`` is documented as retracing per draft width).
"""
from __future__ import annotations

from typing import List

from repro.core import hlo

from repro.configs.base import ArchConfig

from .findings import Finding, Severity
from .pricing import CompiledTarget


# ---------------------------------------------------------------------------
# donation auditor (static, reuses the pricing pass's compiled text)
# ---------------------------------------------------------------------------

def audit_donation(ct: CompiledTarget) -> List[Finding]:
    """The donated KV-pool buffers of one compiled engine step must be
    input-output aliased (updated in place, not copied).

    Works on the module header alone: collect the entry-parameter shapes
    that alias into outputs and require at least two of them (cache_k and
    cache_v) to be rank-5 pool buffers — ``(n_layers, n_blocks,
    n_kv_heads, block_size, head_dim)`` up to SPMD partitioning of the
    layer/head axes, which preserves the rank."""
    t = ct.target
    aliases = hlo.parse_input_output_aliases(ct.hlo_text)
    if not aliases:
        return [Finding(
            "hygiene", "hygiene.no_aliasing", Severity.ERROR,
            f"[{t.name}] compiled module declares NO input_output_alias "
            f"entries — the donated KV state is copied every engine step",
            {"target": t.name})]
    shapes = hlo.entry_parameter_shapes(ct.hlo_text)
    aliased_params = sorted({a.param_number for a in aliases})
    aliased_shapes = [shapes[p] for p in aliased_params if p < len(shapes)]
    pool_bufs = [s for s in aliased_shapes
                 if s.count(",") == 4]     # rank-5: the K and V pools
    detail = {"target": t.name, "alias_entries": len(aliases),
              "aliased_params": aliased_params,
              "aliased_shapes": aliased_shapes}
    if len(pool_bufs) < 2:
        return [Finding(
            "hygiene", "hygiene.kv_pool_not_donated", Severity.ERROR,
            f"[{t.name}] expected both rank-5 KV pool buffers (cache_k, "
            f"cache_v) among the module's aliased inputs, found "
            f"{len(pool_bufs)} — a non-aliased pool is silently copied "
            f"per step", detail)]
    return [Finding(
        "hygiene", "hygiene.donation_ok", Severity.INFO,
        f"[{t.name}] KV pool donated in place: {len(aliases)} alias "
        f"entries, {len(pool_bufs)} rank-5 pool buffers aliased", detail)]


# ---------------------------------------------------------------------------
# retrace detector (the audit's only execution-based pass)
# ---------------------------------------------------------------------------

def audit_retrace(cfg: ArchConfig, attn_impl: str = "gather"
                  ) -> List[Finding]:
    """Run a tiny mixed-length serving workload and assert each engine
    entry point compiled exactly once.

    Prompts of three different lengths (spanning chunk boundaries) and
    two generation budgets exercise every shape the scheduler feeds the
    jitted functions; any length-dependent retrace shows up as a cache
    size > 1.  This pass executes real (reduced-size) compute — gate it
    behind ``--skip-engine`` where wall clock matters."""
    from repro.engine.scheduler import Engine, EngineConfig, Request
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import init_params
    from repro.runtime import ShardingPolicy
    import jax

    mesh = make_host_mesh()
    ec = EngineConfig(max_slots=2, max_len=64, chunk_size=16,
                      decode_block=4, block_size=16, attn_impl=attn_impl)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), mesh,
                 ShardingPolicy(), ec)
    # mixed lengths: short, chunk-straddling, long; mixed budgets
    for rid, (plen, new) in enumerate([(5, 3), (17, 6), (33, 4)]):
        eng.submit(Request(rid=rid, prompt=list(range(1, plen + 1)),
                           max_new=new))
    steps = 0
    while not eng.done and steps < 200:
        eng.step()
        steps += 1
    out: List[Finding] = []
    if not eng.done:
        out.append(Finding(
            "hygiene", "hygiene.engine_stalled", Severity.ERROR,
            f"retrace-audit engine run did not drain in {steps} steps",
            {"steps": steps, "attn_impl": attn_impl}))
        return out
    for name, fn, budget in (("prefill", eng.prefill_fn, 1),
                             ("decode", eng.decode_fn, 1)):
        n = fn._cache_size()
        detail = {"entry_point": name, "compiled_entries": n,
                  "attn_impl": attn_impl, "budget": budget}
        if n > budget:
            out.append(Finding(
                "hygiene", "hygiene.retrace", Severity.ERROR,
                f"engine {name} compiled {n} distinct entries over a "
                f"mixed-length run (expected {budget}) — an argument "
                f"shape is leaking request lengths into the trace",
                detail))
        else:
            out.append(Finding(
                "hygiene", "hygiene.retrace_ok", Severity.INFO,
                f"engine {name} compiled once across mixed lengths",
                detail))
    return out
