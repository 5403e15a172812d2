"""Runner `train_steps`: a training step fed fresh host batches.

The configuration file names the model builder, its optimizer and its
feeds; the cell's `traffic` block gives rows per chip, the symbols the feed
shapes use and how many batches the ring holds. With `chips` > 1 the same
program runs as a GSPMD data-parallel `CompiledProgram` over a `dp` mesh,
the global batch `chips` times the per-chip rows, feeds staged by the
executor's `feed_placer`.

Protocol (bench._timed_windows' async dispatch with one drain, without its
resident feed and its min-of-windows): steps are dispatched through
`Executor.run_async` (runahead bounded by FLAGS_max_inflight_steps), each
with the next batch of the ring as numpy; the window ends with one drain,
and the rate is steps x items over the seconds to the END of that drain.
Every step fetches its loss as a device handle that nobody reads until the
window is over, so one compiled signature serves warm-up, window and check.

`correct`, against the plain reference's Adam steps on the same batches from
the same seeded init (REFERENCE_STEPS of them, outside the window):

- the trainer's loss at each of those steps lies within the configuration's
  `loss_tolerance` of the reference's. At a random init with random labels
  every batch's loss is near ln V and two steps at lr 1e-4 move it by under
  1e-3, so this holds the forward, the loss weighting and its denominator,
  and little else;
- so the PARAMETERS after those steps are compared too. With u = trainer's
  parameters minus the init and r = reference's minus the init, over every
  parameter: `update_cosine` = <u, r> / (|u| |r|) must reach
  `update_cosine_min` and |u| / |r| must lie within `update_rms_tolerance`
  of 1. Adam's first steps move every element by about lr x sign(gradient),
  so the length of u is the learning rate and the number of steps taken (a
  dropped or doubled step, a wrong rate or bias correction show there), and
  its direction is the gradient's signs, which amplify any turn of the
  gradient: one correlated 0.99 with the right one reads 0.91. At the real
  size the mask ignored reads 0.31 and a quarter of the rows 0.47 (CPU),
  bfloat16 rounding costs under a thousandth (0.9994 on the chip; PR 22);
- every loss of the run is finite, and the run did not diverge: the mean of
  the last ten losses is no more than DIVERGED above the mean of the first
  ten. "Below the first ten" would be luck: the labels are random, what
  falls is memorization of the ring, and at a global batch of 512 one run of
  six ended 0.014 ABOVE where it began (the others 0.10 below; at batch 128
  all fell by 0.44; PR 22). A run that blew up sits whole nats higher or is
  not finite;
- nothing compiled inside the window.
"""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import (RunContext, RunResult, TraceSlice,
                               registry_view, span)
from benchmark.traffic import feeds as feed_gen

REFERENCE_STEPS = 3
DIVERGED = 0.1          # nats; see the module docstring


def update_agreement(init, ours, theirs) -> tuple:
    """(cosine, |u| / |r|) of the two updates u = ours - init and
    r = theirs - init, over every leaf of the parameter trees. Summed on
    the host in float64: a float32 sum of 1e8 squares of 1e-4 stagnates and
    read a ratio 1.7% off (PR 22, on the CPU)."""
    ur = uu = rr = 0.0
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (init, ours, theirs))):
        a = np.asarray(a, np.float64).ravel()
        u = np.asarray(b, np.float64).ravel() - a
        r = np.asarray(c, np.float64).ravel() - a
        ur, uu, rr = ur + u @ r, uu + u @ u, rr + r @ r
    if uu == 0.0 or rr == 0.0:
        return 0.0, 0.0
    return float(ur / (uu * rr) ** 0.5), float((uu / rr) ** 0.5)


def _dotted(path: str):
    module, name = path.rsplit(".", 1)
    return getattr(importlib.import_module(module), name)


def build(ctx: RunContext):
    """(main program, startup, loss var, model config, symbols)."""
    import paddle_tpu as pt

    conf, traffic = ctx.config, ctx.cell["traffic"]
    symbols = dict(traffic["symbols"])
    symbols["rows"] = int(traffic["rows_per_chip"]) * ctx.chips
    model_cfg = _dotted(conf["config_class"])(**conf["config_kwargs"])
    kwargs = {k: feed_gen.resolve(v, symbols)
              for k, v in conf["builder_kwargs"].items()}
    main_p, startup = pt.Program(), pt.Program()
    main_p.random_seed = startup.random_seed = ctx.seed
    with pt.program_guard(main_p, startup), pt.unique_name.guard():
        loss, _ = _dotted(conf["builder"])(model_cfg, **kwargs)
        opt = _dotted(conf["optimizer"]["class"])(
            **conf["optimizer"]["kwargs"])
        if conf["optimizer"].get("amp"):
            opt = pt.contrib.mixed_precision.decorate(
                opt, dest_dtype=conf["optimizer"]["amp"])
        opt.minimize(loss)
    return main_p, startup, loss, model_cfg, symbols


def run(ctx: RunContext) -> RunResult:
    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.pipeline import jit_compile_counter

    conf, traffic = ctx.config, ctx.cell["traffic"]
    main_p, startup, loss, model_cfg, symbols = build(ctx)
    ring = feed_gen.batch_ring(conf["feeds"], symbols, ctx.seed,
                               int(traffic["ring"]))
    items_per_step = symbols["rows"] * int(
        feed_gen.resolve(conf["items_per_row"], symbols))

    target = main_p
    if ctx.chips > 1:
        target = pt.CompiledProgram(main_p).with_data_parallel(
            loss_name=loss.name,
            mesh=make_mesh({"dp": ctx.chips}, devices=ctx.devices))
    exe, scope = pt.Executor(), pt.Scope()
    reference = importlib.import_module(conf["reference"]["module"])

    def snapshot():
        # the parameters as they stand, on the first chip, copied before the
        # next step donates them
        return reference.read_params(
            lambda n: jnp.array(jax.device_put(scope.find_var(n),
                                               ctx.devices[0]), copy=True),
            model_cfg)

    with pt.scope_guard(scope):
        exe.run(startup)
        init = snapshot()           # the seeded init
        stage = (lambda b: b)
        losses = []

        def step(i):
            with span("bench.feed"):
                batch = stage(ring[i % len(ring)])
            with span("bench.dispatch"):
                (lv,) = exe.run_async(target, feed=batch, fetch_list=[loss])
            losses.append(lv)

        # steps 0-2 are the ones the reference repeats; the first compiles
        step(0)
        if ctx.chips > 1:
            # the compiled entry exists now: staged feeds carry its shardings
            stage = exe.feed_placer(target)
        for i in range(1, REFERENCE_STEPS):
            step(i)
        exe.wait()
        jax.block_until_ready(losses)
        after = snapshot()

        obs.reset("pipeline.")
        obs.reset("train.")
        setup_s = time.perf_counter() - ctx.t_start
        slice_ = TraceSlice(ctx, ctx.seconds - float(traffic["trace_slice_s"]))
        n0 = len(losses)
        with jit_compile_counter() as compiles:
            t0 = time.perf_counter()
            while True:
                now = time.perf_counter() - t0
                if now >= ctx.seconds:
                    break
                slice_.maybe_start(now)
                step(len(losses))
            with span("bench.drain"):
                exe.wait()
                jax.block_until_ready(losses[-1])
            window_s = time.perf_counter() - t0
        trace = slice_.finish()
        view = registry_view()
        steps = len(losses) - n0

        with span("bench.readback"):
            host_losses = [float(np.asarray(lv)) for lv in losses]
    ref_losses, ref_after = reference.first_steps(
        init, ring[:REFERENCE_STEPS], model_cfg,
        lr=conf["optimizer"]["kwargs"]["learning_rate"],
        block_rows=int(traffic["reference_block_rows"]))
    cosine, rms_ratio = update_agreement(init, after, ref_after)
    tol = {k: float(conf["reference"][k]) for k in
           ("loss_tolerance", "update_cosine_min", "update_rms_tolerance")}
    worst = max(abs(a - b) for a, b in
                zip(host_losses[:REFERENCE_STEPS], ref_losses))
    agrees = (worst <= tol["loss_tolerance"]
              and cosine >= tol["update_cosine_min"]
              and abs(rms_ratio - 1.0) <= tol["update_rms_tolerance"])
    finite = bool(np.all(np.isfinite(host_losses)))
    first10 = float(np.mean(host_losses[:10]))
    last10 = float(np.mean(host_losses[-10:]))
    correct = (finite and last10 < first10 + DIVERGED and agrees
               and compiles.count == 0)

    rate = steps * items_per_step / window_s
    return RunResult(
        correct=correct, attempted=steps,
        failed=int(sum(not np.isfinite(v) for v in host_losses[n0:])),
        values={"train_items_s": rate, "setup_s": setup_s},
        trace=trace, **view,
        compared={"loss_gap": [worst, tol["loss_tolerance"]],
                  # held from below: the shortfall from 1 against its room
                  "update_cosine_short": [1.0 - cosine,
                                          1.0 - tol["update_cosine_min"]],
                  "update_rms_off": [abs(rms_ratio - 1.0),
                                     tol["update_rms_tolerance"]],
                  "loss_rise": [last10 - first10, DIVERGED],
                  "window_compiles": [compiles.count, 0]},
        notes={"steps": steps, "window_s": window_s,
               "items_per_step": items_per_step,
               "window_compiles": compiles.count,
               "losses_first": host_losses[:REFERENCE_STEPS],
               "reference_losses": ref_losses,
               "loss_gap_worst": worst, "update_cosine": cosine,
               "update_rms_ratio": rms_ratio, "tolerances": tol,
               "loss_first10": first10, "loss_last10": last10})
