"""Serving launcher: batched prefill + greedy decode, optional FZ KV parking
or the paged FZ KV pool with continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
        --prompt-len 128 --tokens 16 --kv-compress
    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
        --prompt-len 64 --tokens 16 --paged --pool-pages 8 --page-size 16
"""
from __future__ import annotations

import argparse


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--tokens", type=int, default=16)
    p.add_argument("--kv-compress", action="store_true")
    p.add_argument("--kv-eb", type=float, default=1e-4)
    p.add_argument("--paged", action="store_true",
                   help="serve through the paged KV pool (repro.serve.kvpool)")
    p.add_argument("--pool-pages", type=int, default=8)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--cold-after", type=int, default=2)
    from repro.obs import cli as obs_cli
    obs_cli.add_args(p)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import zoo
    from repro.serve import Engine, KVCompressionConfig, PoolConfig, Request
    from repro.serve.engine import cache_bytes, compressed_cache_bytes

    use_compile_cache()
    cfg = configs.get(args.arch, smoke=args.smoke)
    model = zoo.build(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    obs_cli.start(args)

    if args.paged:
        cap = args.page_size * -(-(args.prompt_len + args.tokens + 1)
                                 // args.page_size)
        pool_cfg = PoolConfig(num_pages=args.pool_pages,
                              page_size=args.page_size,
                              seq_capacity=cap, cold_after=args.cold_after,
                              eb=args.kv_eb)
        eng = Engine(model, params, pool=pool_cfg)
        reqs = [Request(req_id=i,
                        tokens=rng.integers(0, cfg.vocab, (args.prompt_len,),
                                            dtype=np.int32),
                        n_new=args.tokens, priority=i % 2)
                for i in range(args.batch)]
        outputs, stats, pool = eng.serve(reqs, max_batch=min(args.batch, 4))
        print(f"{cfg.arch_id}: {stats.completed} requests in "
              f"{stats.decode_steps} decode steps "
              f"({stats.preemptions} preempt / {stats.resumes} resume / "
              f"{stats.tiered_pages} tiered)")
        print(f"pool high-water {stats.high_water_used_bytes / 1e6:.2f} MB vs "
              f"{stats.high_water_demand_bytes / 1e6:.2f} MB raw demand")
        print("first sequence:", outputs[0])
        obs_cli.finish(args, metadata={"arch": cfg.arch_id, "mode": "serve-paged"})
        return

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len), dtype=np.int32))}
    if cfg.mrope_sections is not None:
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(args.prompt_len, dtype=jnp.int32), (args.batch, 3, args.prompt_len))
    if cfg.family == "audio":
        batch["audio_embeds"] = jnp.zeros(
            (args.batch, cfg.n_audio_ctx, cfg.d_model), jnp.bfloat16)

    eng = Engine(model, params, kv_compress=KVCompressionConfig(
        enabled=args.kv_compress, eb=args.kv_eb))
    toks, cache = eng.generate(batch, args.tokens,
                               park_between=args.kv_compress)
    print(f"{cfg.arch_id}: generated {toks.shape} tokens")
    print("first sequence:", np.asarray(toks[0]))
    if args.kv_compress:
        parked = eng.park(cache)
        print(f"KV parked: {cache_bytes(cache)/1e6:.1f} MB -> "
              f"{compressed_cache_bytes(parked)/1e6:.1f} MB")
    obs_cli.finish(args, metadata={"arch": cfg.arch_id, "mode": "serve"})


if __name__ == "__main__":
    main()
