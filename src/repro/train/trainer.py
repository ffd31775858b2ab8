"""Trainer loop: data -> step -> metrics, with checkpoint/restart and a
straggler watchdog.

Fault-tolerance behaviours (all unit-tested):
  * restart: on construction the trainer restores the newest checkpoint
    (params, optimizer, error-feedback state, step, data cursor) and the loss
    sequence continues bitwise identically (tests/test_ckpt.py);
  * periodic + final checkpointing, atomic, keep-last-k;
  * straggler watchdog: per-step wall time tracked with an EWMA; steps
    slower than ``straggler_factor``x the EWMA are logged with a mitigation
    decision. On a real fleet the decision triggers the elastic path
    (ckpt/elastic.py) — on this single-host container it is a policy-level
    log, exercised by injecting artificial delays in tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ckpt import checkpoint as ckpt
from repro.data.tokens import TokenStream
from repro.models import zoo
from repro.obs import sentinels
from repro.optim import adamw_init
from .step import TrainConfig, build_train_step


@dataclasses.dataclass
class WatchdogEvent:
    step: int
    seconds: float
    ewma: float
    action: str


class StragglerWatchdog:
    def __init__(self, factor: float = 3.0, alpha: float = 0.2, warmup: int = 2):
        self.factor = factor
        self.alpha = alpha
        self.warmup = warmup          # ignore the first steps (jit compile time)
        self.seen = 0
        self.ewma: float | None = None
        self.events: list[WatchdogEvent] = []

    def observe(self, step: int, seconds: float) -> WatchdogEvent | None:
        self.seen += 1
        if self.seen <= self.warmup:
            return None
        if self.ewma is None:
            self.ewma = seconds
            return None
        flagged = seconds > self.factor * self.ewma
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * seconds
        if flagged:
            ev = WatchdogEvent(step, seconds, self.ewma,
                               "flag: candidate for elastic reshard / hot spare swap")
            self.events.append(ev)
            return ev
        return None


class Trainer:
    def __init__(self, model: zoo.Model, shape, mesh, tcfg: TrainConfig, *,
                 stream: TokenStream, ckpt_dir: str | None = None,
                 ckpt_every: int = 50, seed: int = 0,
                 ckpt_codec: str = "raw", keep_last: int = 3):
        self.model, self.shape, self.mesh, self.tcfg = model, shape, mesh, tcfg
        self.stream = stream
        self.ckpt_dir, self.ckpt_every = ckpt_dir, ckpt_every
        self.ckpt_codec, self.keep_last = ckpt_codec, keep_last
        self.watchdog = StragglerWatchdog()
        self.step_fn, self.info = build_train_step(model, shape, mesh, tcfg)

        # state is born in its shardings: built on one device and then
        # placed, params + AdamW + error feedback of a model at published
        # widths overflow that device's HBM
        self.params = jax.jit(model.init, out_shardings=self.info["params"])(
            jax.random.key(seed))
        self.opt = jax.jit(adamw_init, out_shardings=self.info["opt"])(
            self.params)
        grads_abs = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), self.params)
        make_err = lambda: self.info["make_err_state"](grads_abs)
        if self.info["err_shardings"] is None:
            self.err = make_err()
        else:
            self.err = jax.jit(make_err, out_shardings=self.info[
                "err_shardings"](grads_abs))()
        self.step = 0
        self.history: list[dict] = []
        if ckpt_dir is not None and ckpt.latest_step(ckpt_dir) is not None:
            self._restore()

    # ------------------------------------------------------------------
    def _state(self):
        return {"params": self.params, "opt": self.opt, "err": self.err}

    def _place_err(self, err):
        """Stacked residuals live pod-sharded, not replicated."""
        if self.info["err_shardings"] is None:
            return err
        grads_abs = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), self.params)
        return jax.device_put(err, self.info["err_shardings"](grads_abs))

    def _restore(self):
        template = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), self._state())
        state, meta = ckpt.restore(self.ckpt_dir, template)
        self.params = jax.device_put(state["params"], self.info["params"])
        self.opt = jax.device_put(state["opt"], self.info["opt"])
        self.err = self._place_err(jax.tree.map(jnp.asarray, state["err"]))
        self.step = int(meta["step"])

    def save(self):
        if self.ckpt_dir is None:
            return
        ckpt.save(self.ckpt_dir, self.step, self._state(),
                  meta={"data_seed": self.stream.seed},
                  codec=self.ckpt_codec, keep_last=self.keep_last)

    # ------------------------------------------------------------------
    def _batch(self, step: int) -> dict:
        arr = self.stream.shard_batch(step, shard=0, num_shards=1)
        return {"tokens": jnp.asarray(arr[:, :-1]), "labels": jnp.asarray(arr[:, 1:])}

    def run(self, n_steps: int, *, delay_injector: Callable[[int], float] | None = None):
        tokens_per_batch = None
        for _ in range(n_steps):
            t0 = time.perf_counter()
            with obs.span("train.step", step=self.step):
                batch = self._batch(self.step)
                self.params, self.opt, self.err, metrics = self.step_fn(
                    self.params, self.opt, self.err, jnp.int32(self.step), batch)
                metrics = {k: float(v) for k, v in metrics.items()}
            if delay_injector is not None:
                time.sleep(delay_injector(self.step))
            dt = time.perf_counter() - t0
            if tokens_per_batch is None:
                tokens_per_batch = int(batch["tokens"].size)
            obs.gauge("train_tokens_per_s").set(tokens_per_batch / max(dt, 1e-9))
            obs.counter("train_steps").inc()
            sentinels.assert_healthy()
            ev = self.watchdog.observe(self.step, dt)
            metrics.update(step=self.step, seconds=dt,
                           straggler=bool(ev))
            self.history.append(metrics)
            self.step += 1
            if self.ckpt_dir is not None and self.step % self.ckpt_every == 0:
                self.save()
        if self.ckpt_dir is not None:
            self.save()
        return self.history
