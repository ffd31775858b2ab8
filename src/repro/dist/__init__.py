"""repro.dist — the distribution layer: FZ containers as a wire format.

The paper's §2.4 pitch is that error-bounded compression pays off wherever
scientific data is movement-bound, not compute-bound. This package deploys
that idea inside the training/serving stack, one module per use case:

  * ``sharding`` — logical-axis resolution. Models declare logical axes
    ("fsdp"/"tp"/"dp"/None); this module resolves them against any concrete
    mesh (laptop, (data, model) single-pod, (pod, data, model) multi-pod)
    with divisibility-fallback-to-replication, so the same model definition
    is elastic across topologies (ckpt/elastic.py builds on this).
  * ``compressed_allreduce`` — §2.4 "wire compression": the cross-pod
    gradient mean crosses the slow inter-pod link as capacity-sized FZ
    containers instead of raw f32, with error feedback carrying the lossy
    residual into the next step (train/step.py pod-compress path). This is
    the end-of-step barrier form, retained as the bit-parity oracle.
  * ``bucketed_reduce`` — the same reduce restructured for overlap: leaves
    packed into deterministic size-targeted buckets, one compress ->
    all_gather("pod") -> decompress-mean hop per bucket issued in backward
    production order, plus the ``grad_boundary`` custom_vjp taps that pin
    parameter-group cotangents as schedulable units (train/step.py overlap
    path, ``launch/train.py --overlap-reduce``).
  * ``flash_decode`` — sequence-sharded decode attention for serving: each
    KV shard produces flash-decoding partials that are renormalized across
    the sharding axis, so a parked-and-resharded cache (§2.4 "in-memory
    compression", serve/engine.py) never has to be regathered on one device.
    The jnp partials are the oracle; ``use_kernels`` swaps in the Pallas
    KV-tile kernel (``repro.kernels.flash_decode``) per shard.
"""
from . import (bucketed_reduce, compressed_allreduce,  # noqa: F401
               flash_decode, sharding)
