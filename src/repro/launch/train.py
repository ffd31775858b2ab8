"""Training launcher: --arch <id> --shape <name> on any mesh.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 20 --ckpt-dir /tmp/run1

On a real fleet this process runs per host under the cluster scheduler
(jax.distributed.initialize picks up the coordinator from the environment);
on this box it drives the local mesh.

``--overlap-reduce`` turns on the overlapped bucketed compressed-gradient
reduce (dist/bucketed_reduce.py): it implies ``--compressed-grads``, routes
the step through per-bucket compress/all_gather/decompress hops issued in
backward production order, and exports the XLA latency-hiding-scheduler
flags below (TPU compute/communication overlap; harmless on CPU) so the
async all-gathers can actually hide inside the remaining backward compute.
Off, the legacy end-of-step barrier reduce runs unchanged.
"""
from __future__ import annotations

import argparse
import os

# Latency-hiding-scheduler flags exported by --overlap-reduce (must land in
# the environment before jax/libtpu initialize, hence before the imports in
# main()). Async collective fusion lets the per-bucket all-gather-start /
# -done pairs split around independent backward compute.
OVERLAP_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_enable_async_all_gather=true"
)


def enable_overlap_scheduler_flags() -> None:
    """Append the latency-hiding flags to LIBTPU_INIT_ARGS.

    Idempotent by flag NAME: a flag the operator already set — to either
    value, e.g. ``--xla_enable_async_all_gather=false`` to work around a
    scheduler bug — is left alone rather than overridden with a conflicting
    duplicate.
    """
    cur = os.environ.get("LIBTPU_INIT_ARGS", "")
    missing = [f for f in OVERLAP_XLA_FLAGS.split()
               if f.split("=", 1)[0] not in cur]
    if missing:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join([cur, *missing]).strip()


def build_trainer(arch: str, *, smoke: bool = False, layers: int | None = None,
                  shape: str | None = None, seq: int = 128, batch: int = 8,
                  steps: int = 50, microbatches: int = 1, pods: int = 1,
                  model_parallel: int = 1, grad_compress=None,
                  ckpt_dir: str | None = None, ckpt_codec: str = "raw"):
    """The in-process training path behind the CLI: model, mesh over the
    local devices, and a :class:`~repro.train.Trainer` ready to ``run``.

    ``layers`` cuts the architecture's depth and keeps its published widths;
    ``grad_compress`` is the gradient-exchange config (plain when ``None``).
    Returns ``(trainer, cfg)``.
    """
    import dataclasses

    from repro import configs
    from repro.configs.base import SHAPES, ShapeConfig
    from repro.data.tokens import TokenStream
    from repro.dist.compressed_allreduce import GradCompressionConfig
    from repro.launch.mesh import make_local_mesh
    from repro.models import zoo
    from repro.train import TrainConfig, Trainer

    cfg = configs.get(arch, smoke=smoke)
    if layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    model = zoo.build(cfg)
    tshape = SHAPES[shape] if shape else ShapeConfig("local", seq, batch, "train")
    mesh = make_local_mesh(model_parallel=model_parallel, pods=pods)
    tcfg = TrainConfig(
        microbatches=microbatches, total_steps=steps,
        warmup_steps=max(steps // 10, 1),
        grad_compress=grad_compress or GradCompressionConfig())
    stream = TokenStream(vocab_size=cfg.vocab, seq_len=tshape.seq_len,
                         global_batch=tshape.global_batch, seed=0)
    trainer = Trainer(model, tshape, mesh, tcfg, stream=stream,
                      ckpt_dir=ckpt_dir, ckpt_codec=ckpt_codec)
    return trainer, cfg


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default=None, help="assigned shape name (defaults to a local shape)")
    p.add_argument("--smoke", action="store_true", help="reduced config")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--pods", type=int, default=1)
    p.add_argument("--compressed-grads", action="store_true")
    p.add_argument("--overlap-reduce", action="store_true",
                   help="bucketed overlapped compressed reduce + latency-hiding "
                        "scheduler flags (implies --compressed-grads)")
    p.add_argument("--bucket-bytes", type=int, default=4 << 20,
                   help="wire-byte target per reduce bucket (--overlap-reduce)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-codec", choices=["raw", "fz"], default="raw")
    # telemetry flags duplicated from repro.obs.cli.add_args: importing
    # repro.obs pulls in jax, which must wait for the env setup below
    p.add_argument("--trace-out", default=None, metavar="PATH")
    p.add_argument("--metrics-out", default=None, metavar="PATH")
    p.add_argument("--profile-dir", default=None, metavar="DIR")
    args = p.parse_args()

    if args.overlap_reduce:
        enable_overlap_scheduler_flags()   # before jax initializes below

    from repro.dist.compressed_allreduce import GradCompressionConfig
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs import cli as obs_cli

    use_compile_cache()
    trainer, cfg = build_trainer(
        args.arch, smoke=args.smoke, shape=args.shape,
        seq=args.seq, batch=args.batch, steps=args.steps,
        microbatches=args.microbatches, pods=args.pods,
        model_parallel=args.model_parallel,
        grad_compress=GradCompressionConfig(
            enabled=args.compressed_grads or args.overlap_reduce,
            overlap=args.overlap_reduce, bucket_bytes=args.bucket_bytes),
        ckpt_dir=args.ckpt_dir, ckpt_codec=args.ckpt_codec)
    gc = trainer.tcfg.grad_compress
    reduce_mode = ("bucketed-overlap" if gc.overlap else
                   "barrier" if gc.enabled else "exact")
    print(f"{cfg.arch_id}: {trainer.model.param_count()/1e6:.1f}M params, "
          f"mesh={dict(trainer.mesh.shape)}, "
          f"reduce={reduce_mode}, resume_step={trainer.step}")
    obs_cli.start(args)
    hist = trainer.run(args.steps - trainer.step)
    for m in hist[:: max(len(hist) // 10, 1)]:
        print(f"step {m['step']:5d} loss {m['loss']:.4f} ({m['seconds']:.2f}s)")
    obs_cli.finish(args, metadata={"arch": cfg.arch_id, "mode": "train",
                                   "reduce": reduce_mode})


if __name__ == "__main__":
    main()
