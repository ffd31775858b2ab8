"""One run of a training cell: set-up, the measured window, the check.

The cell's configuration names a decoder and the mesh it trains on; its
traffic names the batch, the schedule and the gradient exchange. The run
builds the trainer from the parts the program's own launcher uses (model
from ``zoo``, ``make_local_mesh``, ``TrainConfig``, ``Trainer``) and feeds it
the benchmark's seeded weights and token rows (``train_reference``).

Set-up: JAX start, the trainer's state built in its shardings, the step
compiled ahead of time (its text and temp size are read from that
program), then the first ``checked_steps`` steps through the trainer's own
loop. Those steps are the ones the reference follows: their losses, the
first gradient as the optimizer took it (from its first moment after one
step) and the master weights' change after the last of them are kept.

Window: a closed loop of ``Trainer.run(1)``, one step at a time, each
timed from before the batch is made to the host holding its loss, inside a
``bench.step`` span, for ``--seconds`` and at least ``min_steps`` steps
(a traced window: at most ``harness.TRACE_SECONDS`` and at least
``min_traced_steps``). Then the program's state is freed and the
reference runs its own steps from the same seed on the same chips.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from . import flops, harness, steptrace, train_reference as reference, wire, xplane

SPAN = steptrace.SPAN
MISSING_SHARE = 0.01      # share of device time whose op the step lacks


@dataclasses.dataclass
class Window:
    steps: int
    tokens: int
    seconds: float             # summed wall time of the timed steps
    step_s: list[float]


@dataclasses.dataclass
class Context:
    """What a metric's ``read(ctx)`` sees in a training cell."""
    setup_s: float
    window: Window
    workspace_bytes: int
    flops_per_token: float
    peak_flops_per_s: float | None       # bf16 peak of all the cell's chips
    trace: steptrace.StepTrace | None = None
    classes: dict[str, str] | None = None

    def tokens_per_s(self) -> float:
        return self.window.tokens / self.window.seconds

    def mfu(self) -> float | None:
        if not self.peak_flops_per_s:
            return None
        return 100.0 * self.tokens_per_s() * self.flops_per_token / self.peak_flops_per_s

    def idle_share(self) -> float | None:
        t = self.trace
        if t is None or t.span_s <= 0 or t.busy_mean_s <= 0:
            return None
        return 100.0 * (1.0 - t.busy_mean_s / t.span_s)

    def step_ms(self, cls: str) -> float | None:
        """Device ms a step of one ``steptrace.CLASSES`` class, first chip."""
        t = self.trace
        if t is None or self.classes is None or not t.steps or not t.op_s:
            return None
        secs, missing = steptrace.split(t, self.classes)
        if missing > MISSING_SHARE * sum(secs.values()):
            return None
        return 1e3 * secs[cls] / t.steps


class Batches:
    """The trainer's token stream: the benchmark's seeded rows."""

    def __init__(self, vocab: int, seq_len: int, rows: int, seed: int):
        self.vocab, self.seq_len, self.rows, self.seed = vocab, seq_len, rows, seed

    def shard_batch(self, step: int, shard: int, num_shards: int) -> np.ndarray:
        per = self.rows // num_shards
        rows = reference.batch(self.vocab, self.seq_len, self.rows, self.seed, step)
        return rows[shard * per:(shard + 1) * per]


def grad_config(traffic: dict):
    from repro.dist.compressed_allreduce import GradCompressionConfig
    return GradCompressionConfig(**traffic["grad_compress"])


def model_of(conf: dict):
    """The program's model (``zoo.build``) of a configuration file."""
    from repro.configs.base import ArchConfig
    from repro.models import zoo
    a = reference.dims(conf)
    return zoo.build(ArchConfig(
        arch_id=conf["name"], family=conf["family"], n_layers=a["layers"], d_model=a["d"],
        n_heads=a["h"], n_kv_heads=a["kv"], d_ff=a["ff"], vocab=a["vocab"],
        head_dim=a["hd"], rope_theta=a["theta"], norm_eps=a["eps"]))


def build(conf: dict, traffic: dict, seed: int):
    """The trainer of the cell, holding the benchmark's weights from
    ``seed`` and fresh AdamW and error-feedback state."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_local_mesh
    from repro.train import TrainConfig, Trainer

    a = reference.dims(conf)
    model = model_of(conf)
    mesh = make_local_mesh(model_parallel=conf["mesh"]["model"], pods=conf["mesh"]["pod"])
    if dict(mesh.shape) != conf["mesh"]:
        raise RuntimeError(f"mesh {dict(mesh.shape)} is not the configuration's {conf['mesh']}")
    sched = traffic["schedule"]
    tcfg = TrainConfig(peak_lr=sched["peak_lr"], warmup_steps=sched["warmup_steps"],
                       total_steps=sched["total_steps"],
                       microbatches=traffic["microbatches"],
                       grad_compress=grad_config(traffic))
    shape = ShapeConfig(conf["name"], traffic["seq_len"], traffic["global_batch"], "train")
    stream = Batches(a["vocab"], traffic["seq_len"], traffic["global_batch"], seed)
    trainer = Trainer(model, shape, mesh, tcfg, stream=stream)
    want = jax.tree.map(lambda s: tuple(s.shape), model.abstract_params())
    if want != reference.param_shapes(conf):
        raise RuntimeError(f"the program's parameters {want} are not the reference's")
    seed_state(trainer, conf, traffic, seed)
    return trainer


def seed_state(trainer, conf: dict, traffic: dict, seed: int) -> None:
    """Give ``trainer`` the benchmark's weights from ``seed``, fresh AdamW
    and error-feedback state, step 0 and the seed's token rows. What it
    held is freed first."""
    import jax
    from repro.optim import adamw_init
    info = trainer.info
    trainer.params = trainer.opt = trainer.err = None
    trainer.params = reference.init_params(conf, seed, info["params"])
    trainer.opt = jax.jit(adamw_init, out_shardings=info["opt"])(trainer.params)
    grads = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), trainer.params)
    make_err = lambda: info["make_err_state"](grads)
    trainer.err = (make_err() if info["err_shardings"] is None else
                   jax.jit(make_err, out_shardings=info["err_shardings"](grads))())
    trainer.step, trainer.history = 0, []
    trainer.stream = Batches(conf["vocab_size"], traffic["seq_len"],
                             traffic["global_batch"], seed)


def compile_step(trainer):
    """The trainer's step compiled ahead of time at the window's arguments."""
    import jax.numpy as jnp
    return trainer.step_fn.lower(trainer.params, trainer.opt, trainer.err,
                                 jnp.int32(trainer.step),
                                 trainer._batch(trainer.step)).compile()


def first_steps(trainer, conf: dict, seed: int, n: int) -> dict:
    """The first ``n`` steps through the trainer's loop, and what the
    reference compares of them."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(reference.leaf_norms)
    changed = jax.jit(lambda m, w0: reference.leaf_norms(
        jax.tree.map(lambda a, b: a - b.astype(jnp.float32), m, w0)))
    losses, grad_norms = [], None
    for _ in range(n):
        losses.append(trainer.run(1)[-1]["loss"])
        if grad_norms is None:
            grad_norms = np.asarray(norms(trainer.opt["m"])) / (1 - reference.B1)
    init = reference.init_params(conf, seed, trainer.info["params"])
    change = np.asarray(changed(trainer.opt["master"], init))
    del init
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def run_window(trainer, seconds: float, min_steps: int, tokens_per_step: int):
    import jax
    step_s, losses = [], []
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN):
            losses.append(trainer.run(1)[-1]["loss"])
        step_s.append(time.perf_counter() - t)
        if time.perf_counter() - t0 >= seconds and len(step_s) >= min_steps:
            break
    n = len(step_s)
    return Window(n, n * tokens_per_step, sum(step_s), step_s), losses


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the check compares, from the program's and the
    reference's first steps. Norm gaps are taken leaf by leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the change."""
    pl, rl = np.asarray(prog["losses"], float), np.asarray(ref["losses"], float)
    g_p, g_r = np.asarray(prog["grad_norms"], float), np.asarray(ref["grad_norms"], float)
    c_p, c_r = np.asarray(prog["change_norms"], float), np.asarray(ref["change_norms"], float)
    moved = g_r >= 1e-3 * np.median(g_r)

    def gap(p, r):
        return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))
    return {"loss_rel_diff": float(np.max(np.abs(pl - rl) / np.abs(rl))),
            "grad_norm_gap": gap(g_p, g_r),
            "change_norm_gap": gap(c_p[moved], c_r[moved])}


def device_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, log=None) -> dict:
    """One run of a training cell; returns the result object the CLI prints."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = harness.device_facts(cell.chips, require_tpu)
    devices = jax.devices()[:cell.chips]
    table = json.loads((cell.root / "bench" / "peaks.json").read_text())["devices"]
    if device["kind"] not in table and require_tpu:
        raise harness.Refused(f"device kind {device['kind']!r} is not in bench/peaks.json")
    peak = table.get(device["kind"], {}).get("bf16_flops_per_s")

    conf, traffic = cell.config, cell.traffic
    checked = int(traffic["checked_steps"])
    marks = [time.perf_counter()]
    trainer = build(conf, traffic, seed)
    marks.append(time.perf_counter())
    compiled = compile_step(trainer)
    workspace = compiled.memory_analysis().temp_size_in_bytes
    text = compiled.as_text()
    del compiled
    marks.append(time.perf_counter())
    prog = first_steps(trainer, conf, seed, checked)
    setup_s = time.perf_counter() - t_start
    log(f"{cell.name}: set-up {setup_s:.3f} s (start {marks[0] - t_start:.3f}, state "
        f"{marks[1] - marks[0]:.3f}, step compiled {marks[2] - marks[1]:.3f}, first "
        f"steps {t_start + setup_s - marks[2]:.3f}), mesh {dict(trainer.mesh.shape)}, "
        f"{trainer.model.param_count()} params, workspace {workspace} B, "
        f"first losses {prog['losses']}")

    gc.collect()
    trace_dir = None
    min_steps = int(traffic["min_steps"])
    if trace:
        seconds = min(seconds, harness.TRACE_SECONDS)
        min_steps = int(traffic["min_traced_steps"])
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    tokens = traffic["global_batch"] * traffic["seq_len"]
    try:
        window, losses = run_window(trainer, seconds, min_steps, tokens)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_mem = device_peak(devices)
    s = sorted(window.step_s)
    log(f"{cell.name}: window {window.seconds:.3f} s, {window.steps} steps, step s: "
        f"min {s[0]:.6f} median {s[len(s) // 2]:.6f} max {s[-1]:.6f}, losses {losses}")
    trainer.params = trainer.opt = trainer.err = None
    del trainer
    gc.collect()

    reduced = None
    per_pod = len(devices) // conf["mesh"]["pod"]
    if trace:
        try:
            devs, host = xplane.read(xplane.find(trace_dir))
            reduced = steptrace.reduce(devs, host, harness.N_OPS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(setup_s=setup_s, window=window, workspace_bytes=workspace,
                  flops_per_token=flops.per_token(conf, traffic["seq_len"]),
                  peak_flops_per_s=peak and peak * len(devices), trace=reduced,
                  classes=steptrace.classes(text, per_pod) if trace else None)
    metrics = {}
    for name in (cell.per_layer if trace else cell.end_to_end):
        value = harness.reader(cell.root, name)(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell.units[name]}

    t_ref = time.perf_counter()
    ref = reference.readings(conf, traffic, seed, devices)
    log(f"{cell.name}: reference {time.perf_counter() - t_ref:.3f} s, losses {ref['losses']}")
    for i, leaf in enumerate(reference.leaf_names(conf)):
        log(f"{cell.name}: leaf {leaf} first gradient {float(prog['grad_norms'][i])!r} / "
            f"{float(ref['grad_norms'][i])!r}, change {float(prog['change_norms'][i])!r} / "
            f"{float(ref['change_norms'][i])!r} (program / reference)")
    numbers = compare(prog, ref)
    all_losses = np.asarray(prog["losses"] + losses, float)
    numbers["nonfinite_losses"] = int(np.count_nonzero(~np.isfinite(all_losses)))
    numbers.update(wire_numbers(conf, traffic, text, per_pod))
    limits = {**traffic["limits"], "nonfinite_losses": 0, **wire.LIMITS}
    for k, lim in limits.items():
        log(f"check {k} {numbers[k]!r} limit {lim!r}")
    out = {"correct": all(numbers[k] <= lim for k, lim in limits.items()),
           "attempted": window.steps,
           "failed": int(np.count_nonzero(~np.isfinite(np.asarray(losses, float)))),
           "metrics": metrics,
           "device": {**device, "memory_peak_bytes": peak_mem}}
    if reduced is not None:
        out["device"]["busy_s"] = reduced.busy_mean_s
        out["device"]["window_s"] = reduced.window_s
        out["breakdown"] = breakdown(reduced, ctx.classes)
    out["checks"] = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    return out


def wire_numbers(conf: dict, traffic: dict, text: str, per_pod: int) -> dict:
    """The compiled step's cross-pod traffic against the exchange's model."""
    import jax
    import jax.numpy as jnp
    shapes = jax.tree.leaves(reference.param_shapes(conf),
                             is_leaf=lambda s: isinstance(s, tuple))
    exchange = grad_config(traffic)
    want = wire.model([(s, jnp.bfloat16) for s in shapes], exchange.fz_config(),
                      exchange.min_leaf_size, conf["mesh"]["pod"])
    return wire.check(text, per_pod, want)


def breakdown(reduced: steptrace.StepTrace, classes: dict[str, str]) -> dict:
    ops = {k: v for k, v in reduced.op_s.items() if classes.get(k) != steptrace.CONTAINER}
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:harness.N_OPS]
    return {"device_ops": [[f"{classes.get(k, 'other')}:{k}", v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in reduced.gaps]}
