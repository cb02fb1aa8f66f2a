"""Driver ``train_sim``: the repo's n-worker simulator step, the paper's
Algorithm 1 on one chip, run back to back for the window.

Set-up makes the weights on the device from the seed, draws the mix's
batches on the host (``markov.py``), and builds the channel, the exchange
plan, the optimizer and the jitted step as ``run_simulation`` builds them
(``make_sim_step``). It then drives that step object from the start
through the mix's first ``check_steps`` steps, through the window's own
call and feed (the first call compiles or loads it): the start is copied
to the host before them, each step's drop masks are drawn from the
program's channel under the step's key, and the norm of every worker's
change of every tensor from the start is read after the first step and
after the last. The window runs the same object on from there, each step's
batch put on the device from host memory as ``run_simulation`` feeds it,
the host up to ``IN_FLIGHT`` steps ahead, until ``--seconds`` have passed;
it ends at ``block_until_ready`` of the last step. Once the memory peak
is read and the program's state is freed, the float32 reference
(``refs/train.py``) follows the checked steps from the seed with those
masks and batches.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import hlo_ops
import markov
import program
import train_work
import weights
from harness import Check, Result

#: the program's exchange: every op built under the simulator's dispatch
#: to its aggregator
EXCHANGE = ("_exchange", "train/simulator.py")
KERNEL = "masked_avg_grid_pallas"
#: steps the host may run ahead of the device in the window.
#: ``run_simulation`` waits only at its evaluations (every tenth step);
#: with one step in flight a host stall of a quarter second idled the
#: chip (a run 5.3% slow among runs 0.004% apart, on a TPU v5e)
IN_FLIGHT = 4


def ref_name(path) -> str:
    """The reference's name of a program parameter (``weights.py``)."""
    p = weights.path_of(path)
    if p[0] == "embed":
        return p[1]
    return weights.PROGRAM_LAYER[p[2:]]


class Sim:
    """The simulator's step and state as ``run_simulation`` builds them."""

    def __init__(self, hf: dict, tr: dict):
        from repro import channels as channels_lib
        from repro.models import build_model
        from repro.optim import make_optimizer
        from repro.train import simulator as sim
        sc = tr["simulator"]
        if (sc["aggregator"] != "rps_model" or sc["optimizer"] != "sgd"
                or sc["wire"] != "f32" or sc["recovery"] != "renorm"):
            raise NotImplementedError(
                "the reference follows RPS model averaging with SGD, an "
                f"f32 wire and renormalisation, not {sc}")
        self.hf = hf
        self.n = n = int(tr["workers"])
        self.scfg = scfg = sim.SimulatorConfig(
            n_workers=n, drop_rate=sc["drop_rate"],
            aggregator=sc["aggregator"], optimizer=sc["optimizer"],
            lr=sc["lr"], batch_size=tr["batch_per_worker"],
            channel=sc["channel"], wire=sc["wire"],
            recovery=sc["recovery"])
        self.model = build_model(program.arch_config(hf), grouped=False)
        self.channel = channels_lib.make_channel(
            scfg.channel, n, scfg.drop_rate, s=scfg.n_servers)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.plan = sim.make_exchange_plan(self.shapes, scfg, self.channel)
        if self.plan.per_bucket_masks:
            raise NotImplementedError("the reference takes one mask pair "
                                      "a step, not one a bucket")
        self.opt = make_optimizer(scfg.optimizer,
                                  state_pack=scfg.state_pack)

        def loss_fn(params, batch):
            return self.model.loss(params, batch)[0]

        self.step = sim.make_sim_step(loss_fn, scfg, self.channel, self.plan,
                                      self.opt)
        self._masks = jax.jit(lambda k, st: self.channel.sample(k, st)[:2])

    def start(self, seed31: int):
        """The start on the device, all workers alike, made in one call
        from the seed; and one worker's copy of it on the host."""
        hf_shapes, n = self.shapes, self.n

        def make(k):
            one = weights.program_params(k, self.hf, hf_shapes)
            return one, jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), one)

        one, params = jax.jit(make)(weights.root_key(seed31))
        host = jax.device_get(one)
        del one
        key = jax.random.PRNGKey(seed31)
        _, self.key = jax.random.split(key)
        self.params = params
        self.opt_state = self.opt.init(params)
        self.ch_state = self.channel.init_state(
            jax.random.fold_in(self.key, 0x636831))
        return host

    def args(self, t: int, tokens, labels):
        """Step ``t``'s arguments, fed as ``run_simulation`` feeds them."""
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        kt = jax.random.fold_in(self.key, t)
        lr = self.scfg.lr * min(1.0, (t + 1) / max(self.scfg.warmup, 1))
        return (self.params, self.opt_state, batch, kt, jnp.float32(lr),
                self.ch_state)

    def masks(self, args):
        """The drop masks the step will draw from these arguments."""
        rs, ag = self._masks(args[3], args[5])
        return np.asarray(jax.device_get(rs)), np.asarray(jax.device_get(ag))

    def call(self, args):
        """One step; returns its loss (on the device)."""
        outs = self.step(*args, exchange=True)
        self.params, self.opt_state, loss, _, self.ch_state = outs[:5]
        return loss


@jax.jit
def change_norm(x, x0):
    """Per worker, the norm of a stacked tensor's change from ``x0``."""
    d = x.astype(jnp.float32) - x0.astype(jnp.float32)[None]
    return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))


def change_norms(params, host_start) -> dict:
    """Per worker, the norm of each tensor's change from the start, the
    start put back on the device one tensor at a time."""
    starts = dict((weights.path_of(p), x) for p, x in
                  jax.tree_util.tree_flatten_with_path(host_start)[0])
    return {ref_name(path): np.asarray(change_norm(
        x, jnp.asarray(starts[weights.path_of(path)])))
        for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}


def checked_steps(sim: Sim, seed31: int, tokens, labels, checked: int):
    """The first ``checked`` steps from the seed's start, through the
    window's own step object, call and feed. Returns each step's masks,
    the steps' losses (on the device) and the program's readings in
    ``refs/train.py``'s form: the losses, and per worker the norm of each
    tensor's change from the start after the first step and the last."""
    host_start = sim.start(seed31)
    nb = tokens.shape[0]
    masks, losses, changes = [], [], {}
    for t in range(checked):
        args = sim.args(t, tokens[t % nb], labels[t % nb])
        masks.append(sim.masks(args))
        losses.append(sim.call(args))
        if t + 1 in (1, checked):
            changes[t + 1] = change_norms(sim.params, host_start)
    return masks, losses, {"loss": [float(x) for x in losses],
                           "change": changes}


def run(ctx: harness.Context) -> Result:
    hf, tr = ctx.cell.config, ctx.cell.traffic
    seed31 = harness.seed32(ctx.seed)
    n, B, S = int(tr["workers"]), int(tr["batch_per_worker"]), \
        int(tr["seq_len"])
    phases, t_ph = {}, time.time()

    def phase(name):
        nonlocal t_ph
        phases[name] = time.time() - t_ph
        t_ph = time.time()

    tokens, labels = markov.batches(seed31, tr["task"], n, B, S)
    nb, checked = tokens.shape[0], int(tr["check_steps"])
    phase("data")
    sim = Sim(hf, tr)
    phase("build")
    masks, losses, prog = checked_steps(sim, seed31, tokens, labels, checked)
    phase("checked_steps")
    info = {}
    if ctx.trace:
        info = trace_info(sim, hf, tr, sim.args(checked, tokens[checked % nb],
                                                labels[checked % nb]))
        phase("trace_info")

    # the window, with the collector held off: the host keeps each step's
    # loss and makes no cycles, so a collection could only stall it
    tracer = Tracer(ctx, tr["trace_seconds"]) if ctx.trace else None
    gc.collect()
    gc.disable()
    setup_s = ctx.mark_window()
    t = checked
    t0 = time.perf_counter()
    el, host_gap = 0.0, (0.0, checked)
    while True:
        losses.append(sim.call(sim.args(t, tokens[t % nb], labels[t % nb])))
        t += 1
        if t - checked > IN_FLIGHT:
            losses[-1 - IN_FLIGHT].block_until_ready()
        el, last = time.perf_counter() - t0, el
        host_gap = max(host_gap, (el - last, t))
        if tracer is not None:
            tracer.at(el)
        if el >= ctx.seconds:
            break
    jax.block_until_ready(sim.params)
    window_s = time.perf_counter() - t0
    gc.enable()
    ctx.compiles.window = False
    if tracer is not None:
        tracer.close()
    mem = program.peak_bytes(ctx.devices)
    steps = t - checked
    tokens_per_step = n * B * S
    nonfinite = int(np.sum(~np.isfinite(np.asarray(
        jax.device_get(losses), np.float64))))
    metrics = {"setup_s": setup_s,
               "train_tokens_per_s": steps * tokens_per_step / window_s}
    notes = {"setup": dict(phases, before_driver=t_ph - ctx.t0 - sum(
                 phases.values())),
             "sizes": {"workers": n, "batch_per_worker": B, "seq_len": S,
                       "tokens_per_step": tokens_per_step,
                       "parameters_per_worker": int(sum(
                           int(np.prod(x.shape)) for x in
                           jax.tree.leaves(sim.shapes))),
                       "distinct_batches": nb},
             "window": {"steps": steps, "seconds": window_s,
                        "step_ms": 1e3 * window_s / max(steps, 1),
                        "longest_host_step_ms": 1e3 * host_gap[0],
                        "longest_at_step": host_gap[1] - checked}}
    if tracer is not None and tracer.result is not None:
        ex = set(info["exchange_ops"])
        notes["exchange_ops_top"] = [
            op for op in tracer.result.top_ops(len(ex) + 100)
            if op[0] in ex][:10]

    # correctness, after the window, with the program's state freed
    del sim, losses
    gc.collect()
    from refs import train as ref_train
    t_ref = time.perf_counter()
    ref = ref_train.Reference(hf).run(
        seed31, tokens[:checked], labels[:checked], masks,
        tr["simulator"]["lr"], checked)
    g = ref_train.gaps(prog, ref)
    notes["reference"] = {"seconds": time.perf_counter() - t_ref,
                          "loss": ref["loss"], "program_loss": prog["loss"],
                          "left_out": g["left_out"], "worst": g["worst"],
                          "masks": [[m.astype(int).tolist() for m in pair]
                                    for pair in masks]}
    lim = tr["limits"]
    checks = [Check(k, g[k], lim[k])
              for k in ("loss_gap", "change1_gap", "change_gap")]
    checks.append(Check("nonfinite", float(nonfinite), lim["nonfinite"]))
    return Result(metrics=metrics, attempted=steps + checked,
                  failed=nonfinite, checks=checks, memory_peak_bytes=mem,
                  info=info,
                  trace=tracer.result if tracer is not None else None,
                  notes=notes)


def trace_info(sim: Sim, hf: dict, tr: dict, args) -> dict:
    """What the training readers read besides the trace: the step
    program's name and its exchange ops (from its compiled text), and the
    work of a step counted from shapes."""
    text = sim.step.lower(*args, exchange=True).compile().as_text()
    prog = hlo_ops.Program(text)
    leaves = jax.tree.leaves(sim.shapes)
    tokens = sim.n * int(tr["batch_per_worker"]) * int(tr["seq_len"])
    return {"step_module": text.split(None, 2)[1].rstrip(","),
            "exchange_ops": prog.ops_where(hlo_ops.called_from(*EXCHANGE)),
            "kernel": KERNEL,
            "step_flops": train_work.step_flops(hf, tokens,
                                                int(tr["seq_len"])),
            "masked_avg_bytes": train_work.masked_avg_bytes(
                [int(np.prod(x.shape)) for x in leaves], sim.n, sim.plan.s,
                int(np.dtype(leaves[0].dtype).itemsize))}


class Tracer:
    """Profiles a stretch of ``seconds`` in the middle of the window,
    started and stopped between steps."""

    def __init__(self, ctx, seconds: float):
        mid = ctx.seconds / 2
        self.ctx = ctx
        self.t_start, self.t_stop = mid - seconds / 2, mid + seconds / 2
        self.log_dir = self.win = self.result = None

    def at(self, el: float) -> None:
        if self.log_dir is None and el >= self.t_start:
            self.log_dir = harness.profile_start(self.ctx)
            self.win = jax.profiler.TraceAnnotation("bench.window")
            self.win.__enter__()
        elif self.result is None and self.log_dir is not None \
                and el >= self.t_stop:
            self.close()

    def close(self) -> None:
        if self.log_dir is not None and self.result is None:
            self.win.__exit__(None, None, None)
            self.result = harness.profile_stop(self.ctx, self.log_dir,
                                               "bench.window")
