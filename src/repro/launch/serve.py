"""Serving launcher CLI — continuous batching with the LIFE twin.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --requests 8 --max-slots 4 --prompt-len 64 --new-tokens 32 \
        --kv-dtype int8 --chunk 16

Runs the continuous-batching engine (slot-paged KV cache, chunked-prefill
admission, fused decode blocks) over a synthetic request stream, then
replays the scheduler's own trace through the analytical twin to print
forecast TTFT/TPOT/TPS for the TARGET hardware (TPU v5e) next to the
measured wall-clock — the paper's forecast-vs-measured loop for
multi-request traffic.  The model runs tensor-parallel over every device
present (a ``model=N`` mesh): one v5e chip holds qwen2-7b's weights, a
four-chip host splits them four ways.

``--legacy`` keeps the old single-shot lockstep ``Server`` path (also the
only path for engine-unsupported families: ssm / hybrid / encdec / MLA).

NOTE: for the common cases prefer the unified front door —
``python -m repro {forecast,measure,sweep,compare}`` (``repro.api``); this
launcher's single-request orientation forecast is itself served by
``repro.api``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import api, configs
from repro.configs.base import Variant
from repro.core import hardware
from repro.engine import (Engine, EngineConfig, ForecastTwin, Request,
                          engine_supported)
from repro.models import init_params
from repro.runtime import ShardingPolicy, Server, ServeConfig
from repro.runtime.sharding import param_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def run_legacy(args, cfg, mesh, policy, params) -> None:
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 16)
    sc = ServeConfig(batch=args.batch, max_len=max_len,
                     chunk_size=args.chunk or None, kv_dtype=args.kv_dtype,
                     temperature=args.temperature)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)
    with mesh:
        server = Server(cfg, params, mesh, policy, sc)
        t0 = time.time()
        tokens, stats = server.generate(prompt, args.new_tokens)
        jax.block_until_ready(tokens)
        wall = time.time() - t0
    print(json.dumps({
        "mode": "legacy", "arch": cfg.name,
        "generated": list(map(int, tokens[0][:8])),
        "shape": list(tokens.shape), "wall_s": round(wall, 2),
        "host_tps": round(args.new_tokens * args.batch / wall, 1),
        **stats}, indent=1))


def run_engine(args, cfg, full_cfg, mesh, policy, params) -> None:
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 16)
    ec = EngineConfig(max_slots=args.max_slots, max_len=max_len,
                      chunk_size=args.chunk or args.prompt_len,
                      decode_block=args.decode_block,
                      kv_dtype=args.kv_dtype, temperature=args.temperature)
    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(rng, (args.requests, args.prompt_len), 0,
                                 cfg.vocab_size, jnp.int32)
    reqs = [Request(rid=i, prompt=list(map(int, prompts[i])),
                    max_new=args.new_tokens) for i in range(args.requests)]
    with mesh:
        eng = Engine(cfg, params, mesh, policy, ec)
        eng.warmup()   # compile outside the measured metrics
        results = eng.run(reqs)

    # LIFE twin: replay the schedule the engine just executed, on the target
    variant = Variant(kv_dtype=args.kv_dtype, fused=True)
    twin = ForecastTwin(full_cfg, hardware.TPU_V5E, variant, em=0.8)
    fcst = twin.replay(eng.trace)
    print(f"[LIFE twin → tpu-v5e] {full_cfg.name}: "
          f"forecast TPS={fcst.tps:.1f}  mean TTFT={fcst.mean_ttft*1e3:.1f}ms"
          f"  mean TPOT={fcst.mean_tpot*1e3:.2f}ms  (em=0.8, same trace)")
    for r in results:
        f = fcst.requests.get(r.rid)
        print(f"  req {r.rid}: {len(r.tokens)} toks  "
              f"measured ttft={r.ttft*1e3:7.1f}ms tpot={r.tpot*1e3:6.2f}ms"
              f"  | forecast ttft={f.ttft*1e3:6.2f}ms "
              f"tpot={f.tpot*1e3:5.2f}ms")
    print(json.dumps({
        "mode": "engine", "arch": cfg.name, "requests": args.requests,
        "max_slots": args.max_slots, "host_tps": round(eng.aggregate_tps(), 1),
        "forecast_tps_tpu_v5e": round(fcst.tps, 1),
        "trace_events": len(eng.trace)}, indent=1))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=sorted(configs.ARCHS), required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--legacy", action="store_true",
                   help="single-shot lockstep Server path")
    p.add_argument("--batch", type=int, default=4, help="legacy batch size")
    p.add_argument("--requests", type=int, default=8,
                   help="engine request count")
    p.add_argument("--max-slots", type=int, default=4)
    p.add_argument("--decode-block", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--new-tokens", type=int, default=32)
    p.add_argument("--max-len", type=int, default=0)
    p.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"])
    p.add_argument("--chunk", type=int, default=0, help="chunked prefill size")
    p.add_argument("--temperature", type=float, default=0.0)
    args = p.parse_args()
    enable_compile_cache()

    full_cfg = configs.get(args.arch)
    cfg = configs.reduced(full_cfg) if args.reduced else full_cfg
    mesh = make_host_mesh(model=jax.device_count())
    policy = ShardingPolicy()

    # single-request LIFE forecast (paper Eqs. 1-6) for orientation
    variant = Variant(kv_dtype=args.kv_dtype, fused=True)
    scn = api.Scenario(model=args.arch, variant=variant,
                       prompt_len=args.prompt_len, gen_len=args.new_tokens,
                       chunk=args.chunk or None)
    r = api.forecast(scn, "tpu-v5e", em=0.8)
    print(f"[LIFE → tpu-v5e] {full_cfg.name}: single-request "
          f"TTFT={r.ttft_s*1e3:.1f}ms ({r.ttft_bound}-bound)  "
          f"TPOT={r.tpot_s*1e3:.2f}ms  TPS={r.tps:.1f} (1 chip, em=0.8)")

    params = init_params(cfg, jax.random.PRNGKey(0),
                         param_shardings(cfg, mesh, policy))
    if args.legacy or not engine_supported(cfg):
        if not args.legacy:
            print(f"({cfg.name}: family not engine-supported; "
                  f"using legacy lockstep path)")
        run_legacy(args, cfg, mesh, policy, params)
    else:
        run_engine(args, cfg, full_cfg, mesh, policy, params)


if __name__ == "__main__":
    main()
