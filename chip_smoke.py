#!/usr/bin/env python3
"""Bring-up check: the serving engine on a TPU at qwen2-7b's published widths.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # tensor-parallel path, four chips

One chip: qwen2-7b (d_model 3584, 28 query heads over 4 KV heads,
head_dim 128, d_ff 18944, vocab 152064, QKV bias) cut to ``DEPTH`` of
its 28 layers, with random weights from a seed, serves a handful of
chunked-prefill requests through ``Engine`` (scheduler, block pool,
fused decode) four times: gather and paged attention, each with bf16
and int8 KV.  It checks the engine's next-token logits at each prompt's
last position against the model's own uncached forward pass, and paged
against gather, and prints compile time, peak device memory, measured
TTFT/TPOT/TPS and the analytical twin's forecast for the same trace.

``--four-chips`` runs only the tensor-parallel path: the depth-cut model
at tp=4 against tp=1 on one chip, then qwen2-7b at full depth at tp=4,
with the weights and the KV pool checked to be split four ways.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, outside the repository, or when any check fails, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DEPTH = 20                        # of qwen2-7b's 28 layers, on one chip
SEED = 0
# none a multiple of the chunk, so each request's last chunk ends at a
# position no other request's chunk ends at
PROMPT_LENS = (383, 300, 257, 333, 211, 391)
NEW_TOKENS = 32
ENGINE = dict(max_slots=4, max_len=448, chunk_size=128, decode_block=8,
              block_size=16)
REF_LEN = 400                     # prompts are right-padded to this
# Logits are compared by relative L2 error, ||a - b|| / ||b||, against the
# model's uncached forward pass computed in f32 (bf16 weights, f32
# activations, f32 matmuls).  The tolerance is measured, not assumed: the
# same forward pass in bf16 lands FLOOR = max_i err(bf16_i, f32_i) away
# from it, from rounding alone.  The engine computes in bf16 too, rounding
# at other points (chunked prefill through the KV cache, attention in f32
# inside the paged kernel but in bf16 on the gather path), so it may sit
# up to REF_BOUND x FLOOR from the f32 answer; two engine paths, each
# within that of a common answer, within PAIR_BOUND x FLOOR of each other.
# A real fault moves the logits much further: dropping the diagonal of
# the causal mask in the prefill kernel moved them by 23% (128-wide,
# 20-layer model on the CPU) while FLOOR there is under 1%.
REF_BOUND, PAIR_BOUND = 2.0, 4.0
TWIN_EM = 0.8                     # memory efficiency the launcher uses
# Kernel against the gather oracle, max |kernel - oracle| / max |oracle|.
# Queries and K/V hold bf16 (or int8) values, so q·k is exact however the
# chip's matmul rounds its f32 operands; the softmax weights may be
# rounded to bf16 for the p·V product (2^-9 relative).  1% is above that
# and far below a wrong block, row, head or mask, which is of order 1.
KERNEL_TOL = 1e-2


def _rel_err(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _emit(tag: str, facts: dict) -> None:
    print(f"[{tag}] {json.dumps(facts, sort_keys=True, default=float)}",
          flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.secs, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.secs, "cache_hits": self.hits,
                "cache_misses": self.misses}


def _split(x) -> dict:
    """Global shape, one device's shard shape, and the devices holding x."""
    return {"shape": list(x.shape),
            "shard": list(x.addressable_shards[0].data.shape),
            "devices": len(x.sharding.device_set)}


def _prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in PROMPT_LENS]


def _reference_logits(cfg, params, prompts):
    """The uncached forward pass's next-token logits at each prompt's last
    position (right padding cannot reach earlier positions causally), in
    bf16 as the model runs and in f32; returns ``(bf16, f32, floor)``."""
    import jax
    import jax.numpy as jnp
    from repro.models import forward

    assert not cfg.tie_embeddings     # the f32 pass swaps the embedding
    fn = jax.jit(lambda p, t, i: forward(cfg, p, t)[0][0, i])
    ref16, ref32 = [], []
    for pr in prompts:
        toks = np.zeros((1, REF_LEN), np.int32)
        toks[0, :len(pr)] = pr
        last = jnp.int32(len(pr) - 1)
        ref16.append(np.asarray(fn(params, jnp.asarray(toks), last),
                                np.float32))
        # f32 rows of only this prompt's own tokens, ids remapped onto
        # them: every activation is f32 without an f32 copy of the table
        uniq, ids = np.unique(toks, return_inverse=True)
        rows = np.zeros(REF_LEN, np.int32)
        rows[:len(uniq)] = uniq
        p32 = dict(params, embed=params["embed"][rows].astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            ref32.append(np.asarray(
                fn(p32, jnp.asarray(ids.reshape(toks.shape), jnp.int32),
                   last), np.float32))
    floor = max(_rel_err(a, b) for a, b in zip(ref16, ref32))
    return ref16, ref32, floor


def _gate(failures, what, err, bound):
    if not err <= bound:
        failures.append(f"{what}: logits rel err {err:.4g} > {bound:.4g}")


def serve(cfg, params, mesh, prompts, attn_impl, kv_dtype, log, tp=1):
    """Serve ``prompts`` through the engine; returns what was measured."""
    import jax
    from repro.configs.base import Variant
    from repro.core import hardware
    from repro.core.workload import ShardingPlan
    from repro.engine import Engine, EngineConfig, ForecastTwin, Request
    from repro.runtime import ShardingPolicy

    ec = EngineConfig(**ENGINE, kv_dtype=kv_dtype, attn_impl=attn_impl,
                      seed=SEED)
    before = log.snapshot()
    t0 = time.perf_counter()
    eng = Engine(cfg, params, mesh, ShardingPolicy(), ec)
    eng.warmup()
    warm_s = time.perf_counter() - t0
    after = log.snapshot()

    # record the next-token logits of every prefill chunk; a request's
    # last chunk ends at its prompt length
    chunks = []
    prefill = eng.prefill_fn

    def recording_prefill(params, state, tokens, slot, start, valid):
        logits, state = prefill(params, state, tokens, slot, start, valid)
        chunks.append((start, valid, logits))
        return logits, state

    eng.prefill_fn = recording_prefill
    reqs = [Request(rid=i, prompt=p.tolist(), max_new=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    results = eng.run(reqs)
    by_end = {int(s) + int(v): lg for s, v, lg in chunks}
    logits = [np.asarray(by_end[len(p)], np.float32) for p in prompts]

    spec = hardware.for_device_kind(jax.devices()[0].device_kind)
    twin = ForecastTwin(cfg, spec, Variant(kv_dtype=kv_dtype, fused=True),
                        em=TWIN_EM, plan=ShardingPlan(tp=tp))
    fc = twin.replay(eng.trace)
    decoding = [r for r in results if len(r.tokens) > 1]
    out = {
        "attn_impl": attn_impl, "kv_dtype": kv_dtype, "tp": tp,
        "requests": len(results),
        "tokens": sum(len(r.tokens) for r in results),
        "warmup_s": warm_s,
        "compile_s": after["compile_s"] - before["compile_s"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "measured_ttft_s": float(np.mean([r.ttft for r in results])),
        "measured_tpot_s": float(np.mean([r.tpot for r in decoding])),
        "measured_tps": eng.aggregate_tps(),
        "forecast_device": spec.name,
        "forecast_ttft_s": fc.mean_ttft,
        "forecast_tpot_s": fc.mean_tpot,
        "forecast_tps": fc.tps,
        "split": {"cache_k": _split(eng.state["cache_k"]),
                  "wq": _split(eng.params["layers"]["attn"]["wq"]),
                  "mlp_up": _split(eng.params["layers"]["mlp"]["up"]),
                  "embed": _split(eng.params["embed"])},
    }
    toks = {r.rid: r.tokens for r in results}
    del eng
    gc.collect()
    return out, logits, toks


def kernel_check(cfg) -> list:
    """The three paged kernels against their gather oracles, natively
    compiled, at ``cfg``'s head geometry; returns the failed checks."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_attention as pa

    Hk, G, d = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    bs, N, nb, S = ENGINE["block_size"], 64, 16, 4
    chunk, Q = ENGINE["chunk_size"], 5
    rng = np.random.default_rng(SEED)
    tables = jnp.asarray(rng.permutation(N)[:S * nb].reshape(S, nb),
                         jnp.int32)
    pos = jnp.asarray([0, 37, 130, nb * bs - Q], jnp.int32)

    def bf16(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    failures = []
    for kv in ("bf16", "int8"):
        if kv == "int8":
            ck, cv = (jnp.asarray(rng.integers(-40, 40, (N, Hk, bs, d)),
                                  jnp.int8) for _ in range(2))
        else:
            ck, cv = bf16(N, Hk, bs, d), bf16(N, Hk, bs, d)
        cases = {
            "decode": (pa.paged_decode, pa.ref.paged_decode_ref,
                       (bf16(S, Hk, G, d), ck, cv, tables, pos), None),
            "prefill": (pa.paged_prefill, pa.ref.paged_prefill_ref,
                        (bf16(chunk, Hk, G, d), ck, cv, tables[2],
                         jnp.int32(96), jnp.int32(100)), 100),
            "verify": (pa.paged_verify, pa.ref.paged_verify_ref,
                       (bf16(S, Q, Hk, G, d), ck, cv, tables, pos), None),
        }
        errs = {}
        for name, (kernel, oracle, args, valid) in cases.items():
            out = np.asarray(kernel(*args), np.float32)
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(oracle(*args), np.float32)
            if valid is not None:         # padded chunk rows are garbage
                out, ref = out[:valid], ref[:valid]
            errs[name] = float(np.abs(out - ref).max() / np.abs(ref).max())
            if not errs[name] <= KERNEL_TOL:
                failures.append(f"{name} kernel/{kv} vs gather oracle: "
                                f"rel err {errs[name]:.4g} > {KERNEL_TOL}")
        _emit(f"kernels {kv}", {"Hk": Hk, "G": G, "head_dim": d,
                                "block_size": bs, "rel_err": errs})
    return failures


def _argmax_agreement(a, b) -> float:
    return sum(int(np.argmax(x)) == int(np.argmax(y))
               for x, y in zip(a, b)) / len(a)


def _token_agreement(a: dict, b: dict) -> float:
    same = total = 0
    for rid in a:
        n = min(len(a[rid]), len(b[rid]))
        same += sum(x == y for x, y in zip(a[rid][:n], b[rid][:n]))
        total += max(len(a[rid]), len(b[rid]))
    return same / total


def depth_cut(n_layers: int = DEPTH):
    from repro import configs
    return dataclasses.replace(configs.get("qwen2-7b"), n_layers=n_layers)


def _weights(cfg, mesh):
    import jax
    from repro.models import init_params
    from repro.runtime import ShardingPolicy
    from repro.runtime.sharding import param_shardings
    params = init_params(cfg, jax.random.PRNGKey(SEED),
                         param_shardings(cfg, mesh, ShardingPolicy()))
    return jax.block_until_ready(params)


def _param_bytes(cfg) -> int:
    import jax
    from repro.models import abstract_params
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(abstract_params(cfg)))


def one_chip(cfg, log) -> list:
    """Serve on device 0 with every attention path and KV dtype; returns
    the failed checks."""
    import jax
    from repro.launch.mesh import make_host_mesh

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    wbytes = _param_bytes(cfg)
    _emit("config", {"arch": cfg.name, "n_layers": cfg.n_layers,
                     "published_layers": 28, "d_model": cfg.d_model,
                     "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                     "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
                     "vocab": cfg.vocab_size, "weight_bytes": wbytes,
                     "bytes_limit": limit,
                     "hbm_share_of_16GiB": wbytes / 16 / 2**30,
                     "hbm_share_of_limit": (wbytes / limit) if limit
                     else None,
                     "engine": ENGINE, "prompt_lens": PROMPT_LENS,
                     "new_tokens": NEW_TOKENS})
    mesh = make_host_mesh()
    t0 = time.perf_counter()
    params = _weights(cfg, mesh)
    after_init = dev.memory_stats() or {}
    _emit("init", {"seconds": time.perf_counter() - t0,
                   "weight_bytes": wbytes,
                   "bytes_in_use": after_init.get("bytes_in_use"),
                   "peak_bytes_in_use": after_init.get("peak_bytes_in_use")})

    failures = kernel_check(cfg)
    prompts = _prompts(cfg.vocab_size)
    t0 = time.perf_counter()
    ref16, ref32, floor = _reference_logits(cfg, params, prompts)
    _emit("reference", {
        "seconds": time.perf_counter() - t0, "floor_bf16_vs_f32": floor,
        "ref_bound": REF_BOUND * floor, "pair_bound": PAIR_BOUND * floor,
        "greedy_f32": [int(np.argmax(r)) for r in ref32],
        "greedy_bf16_agrees": _argmax_agreement(ref16, ref32)})

    runs = {}
    for kv in ("bf16", "int8"):
        for impl in ("gather", "paged"):
            out, logits, toks = serve(cfg, params, mesh, prompts, impl, kv,
                                      log)
            runs[impl, kv] = (logits, toks)
            if kv == "bf16":
                # an int8 cache is a different (quantized) result: it is
                # held to the gather path below, not to the float model
                err = max(_rel_err(a, b) for a, b in zip(logits, ref32))
                out["vs_f32_reference_rel_err"] = err
                out["vs_bf16_reference_rel_err"] = max(
                    _rel_err(a, b) for a, b in zip(logits, ref16))
                out["first_token_agrees_with_f32_reference"] = (
                    _argmax_agreement(logits, ref32))
                _gate(failures, f"{impl}/{kv} engine vs f32 reference", err,
                      REF_BOUND * floor)
            if impl == "paged":
                g_logits, g_toks = runs["gather", kv]
                err = max(_rel_err(a, b) for a, b in zip(logits, g_logits))
                out["paged_vs_gather_rel_err"] = err
                out["paged_vs_gather_token_agreement"] = _token_agreement(
                    toks, g_toks)
                _gate(failures, f"paged/{kv} vs gather/{kv}", err,
                      PAIR_BOUND * floor)
            _emit(f"serve {impl}/{kv}", out)
    stats = dev.memory_stats() or {}
    _emit("memory", {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                     "bytes_limit": stats.get("bytes_limit")})
    return failures


def four_chips(cfg, full_cfg, log) -> list:
    """tp=4 against tp=1 at the depth cut, then full depth at tp=4."""
    import jax
    from repro.launch.mesh import make_host_mesh

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    prompts = _prompts(cfg.vocab_size)
    failures = []

    def sharded_four_ways(out):
        for name, s in out["split"].items():
            if s["devices"] != 4 or 4 * np.prod(s["shard"]) != np.prod(
                    s["shape"]):
                failures.append(f"tp=4 {name} is not split four ways: {s}")

    single = {}
    params = _weights(cfg, make_host_mesh())
    floor = _reference_logits(cfg, params, prompts)[2]
    _emit(f"tp1 reference L={cfg.n_layers}", {
        "floor_bf16_vs_f32": floor, "pair_bound": PAIR_BOUND * floor})
    for impl in ("gather", "paged"):
        out, logits, toks = serve(cfg, params, make_host_mesh(), prompts,
                                  impl, "bf16", log)
        single[impl] = (logits, toks)
        _emit(f"tp1 {impl}/bf16 L={cfg.n_layers}", out)
    del params
    gc.collect()

    mesh4 = make_host_mesh(model=4)
    params = _weights(cfg, mesh4)
    for impl in ("gather", "paged"):
        out, logits, toks = serve(cfg, params, mesh4, prompts, impl, "bf16",
                                  log, tp=4)
        err = max(_rel_err(a, b) for a, b in zip(logits, single[impl][0]))
        out["tp4_vs_tp1_rel_err"] = err
        out["tp4_vs_tp1_token_agreement"] = _token_agreement(
            toks, single[impl][1])
        _gate(failures, f"tp=4 {impl} vs tp=1", err, PAIR_BOUND * floor)
        sharded_four_ways(out)
        _emit(f"tp4 {impl}/bf16 L={cfg.n_layers}", out)
    del params
    gc.collect()

    params = _weights(full_cfg, mesh4)
    _, ref32, floor = _reference_logits(full_cfg, params, prompts)
    out, logits, toks = serve(full_cfg, params, mesh4, prompts, "paged",
                              "bf16", log, tp=4)
    err = max(_rel_err(a, b) for a, b in zip(logits, ref32))
    out.update(floor_bf16_vs_f32=floor, vs_f32_reference_rel_err=err,
               first_token_agrees_with_f32_reference=_argmax_agreement(
                   logits, ref32))
    _gate(failures, "tp=4 full depth vs f32 reference", err,
          REF_BOUND * floor)
    sharded_four_ways(out)
    out["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:4]]
    _emit(f"tp4 paged/bf16 L={full_cfg.n_layers}", out)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path on four chips")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 1
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log = CompileLog()
    devices = jax.devices()
    n = 4 if args.four_chips else 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": n}
    _emit("device", {**device, "visible": len(devices),
                     "jax": jax.__version__, "compile_cache": cache_dir})
    t0 = time.perf_counter()
    if args.four_chips:
        failures = four_chips(depth_cut(), configs.get("qwen2-7b"), log)
    else:
        failures = one_chip(depth_cut(), log)
    _emit("total", {"seconds": time.perf_counter() - t0, **log.snapshot()})
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
