"""Driver ``serve_open_loop``: ``ContinuousEngine.run`` in arrival mode
under an open-loop Poisson load at a fixed rate.

Set-up makes the weights on the device from the seed, builds the engine
with a paged pool that holds every lane at ``max_len`` (so nothing is
preempted), and warms every shape the mix can use: one prefill and one
prefill scatter per prompt length of the mix's grid, and the decode
round. The window is the arrivals of ``--seconds``; every request that
arrived is served to its end. Once the program's state is freed, the
float32 reference (``refs/serve.py``) reads the served tokens of a sample
of the finished requests, the one with most served tokens among them.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import harness
import program
import traffic_gen
import weights
from harness import Check, Result


class Tracer:
    """Stands in for the engine's telemetry while a run is traced: the
    engine's spans go to the profiler, and the profiler runs over a
    stretch of the window, started and stopped between rounds (the engine
    calls ``counter`` once per loop)."""

    def __init__(self, ctx, t_start: float, t_stop: float):
        self.ctx, self.t_start, self.t_stop = ctx, t_start, t_stop
        self.t0 = None
        self.log_dir = self.win = self.result = None

    # the engine's telemetry interface --------------------------------------
    @property
    def trace(self):
        return self

    def now_us(self) -> float:
        return time.perf_counter() * 1e6

    def complete(self, name, ts_us, dur_us, **args) -> None:
        pass

    def span(self, name, **args):
        import jax
        return jax.profiler.TraceAnnotation(name, **args)

    def counter(self, name, values) -> None:
        import jax
        el = time.perf_counter() - self.t0
        if self.log_dir is None and el >= self.t_start:
            self.log_dir = harness.profile_start(self.ctx)
            self.win = jax.profiler.TraceAnnotation("bench.window")
            self.win.__enter__()
        elif self.result is None and self.log_dir is not None \
                and el >= self.t_stop:
            self.win.__exit__(None, None, None)
            self.result = harness.profile_stop(self.ctx, self.log_dir,
                                               "bench.window")


class RoundLog:
    """Wraps the engine's decode round to record, per call, the lanes'
    positions and tokens left: what the paged-decode metrics read."""

    def __init__(self, fn, tracer: Tracer):
        self.fn, self.tracer, self.rounds = fn, tracer, []

    def __call__(self, params, pool, bt, tok, pos, n_left, key, ch):
        tr = self.tracer
        if tr.log_dir is not None and tr.result is None:
            self.rounds.append((np.asarray(pos).copy(),
                                np.asarray(n_left).copy()))
        return self.fn(params, pool, bt, tok, pos, n_left, key, ch)


def make_engine(hf: dict, tr: dict, seed31: int):
    import jax
    from repro.models import build_model
    from repro.serve import ContinuousEngine
    model = build_model(program.arch_config(hf), grouped=True)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.jit(lambda k: weights.program_params(k, hf, shapes))(
        weights.root_key(seed31))
    page, lanes, max_len = tr["page"], tr["lanes"], tr["max_len"]
    eng = ContinuousEngine(model=model, params=params, page=page,
                           n_blocks=lanes * (max_len // page) + 1,
                           max_batch=lanes, chunk=tr["chunk"],
                           max_len=max_len, temperature=0.0)
    return eng


def warm(eng, tr: dict, vocab: int) -> int:
    """One request per prompt length of the mix, each long enough for a
    decode round: every program the window can run, compiled or loaded."""
    from repro.serve.scheduler import Request
    grid = traffic_gen.prompt_grid(tr["prompt"])
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, vocab, L),
                    max_new=tr["chunk"] + 2) for i, L in enumerate(grid)]
    eng.run(reqs, drain=True)
    return len(grid)


def requests(seed31: int, seconds: float, tr: dict, vocab: int):
    from repro.serve.scheduler import Request
    load = traffic_gen.open_loop(seed31, seconds,
                                 rate_per_s=tr["rate_per_s"],
                                 prompt=tr["prompt"], output=tr["output"],
                                 vocab=vocab)
    return [Request(rid=i, prompt=p, max_new=m, arrival_ms=a)
            for i, (a, p, m) in enumerate(load)]


def sample(reqs, n: int, seed31: int):
    """``n`` finished requests drawn from the seed, the one with most
    served tokens among them."""
    done = [r for r in reqs if r.finish_ms is not None and r.generated]
    if not done:
        return []
    top = max(done, key=lambda r: (len(r.generated), -r.rid))
    rest = [r for r in done if r is not top]
    rng = np.random.default_rng(seed31 ^ 0x5A)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [top] + [rest[i] for i in sorted(pick)]


def latencies(reqs):
    ttft = np.array([r.first_token_ms - r.arrival_ms for r in reqs
                     if r.first_token_ms is not None])
    tpot = np.array([(r.finish_ms - r.first_token_ms)
                     / (len(r.generated) - 1) for r in reqs
                     if r.finish_ms is not None and len(r.generated) > 1])
    return ttft, tpot


def run(ctx: harness.Context) -> Result:
    hf, tr = ctx.cell.config, ctx.cell.traffic
    seed31 = harness.seed32(ctx.seed)
    vocab = hf["vocab_size"]
    eng = make_engine(hf, tr, seed31)
    n_shapes = warm(eng, tr, vocab)
    reqs = requests(seed31, ctx.seconds, tr, vocab)
    tracer = None
    if ctx.trace:
        mid = ctx.seconds / 2
        tracer = Tracer(ctx, mid - tr["trace_seconds"] / 2,
                        mid + tr["trace_seconds"] / 2)
        eng.telemetry = tracer
        rounds = RoundLog(eng._round, tracer)
        eng._round = rounds
    setup_s = ctx.mark_window()
    if tracer is not None:
        tracer.t0 = time.perf_counter()
    rep = eng.run(reqs)
    ctx.compiles.window = False
    mem = program.peak_bytes(ctx.devices)

    finished = [r for r in rep.requests if r.finish_ms is not None
                and len(r.generated) == r.max_new]
    ttft, tpot = latencies(rep.requests)
    end_s = max(r.finish_ms for r in finished) / 1e3 if finished else 0.0
    metrics = {"setup_s": setup_s,
               "serve_tokens_per_s": rep.tokens / end_s if end_s else 0.0,
               "ttft_p90_ms": float(np.percentile(ttft, 90)),
               "tpot_p90_ms": float(np.percentile(tpot, 90))}
    page = tr["page"]
    kv_token = (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"]
                * (hf.get("head_dim") or hf["hidden_size"]
                   // hf["num_attention_heads"]) * 2)
    info = {"chips": 1, "decode_module": "jit_round_fn",
            "prefill_modules": ("jit__lambda", "jit_write"),
            "weight_bytes": 2 * (program.matmul_params(hf)),
            "kv_bytes_per_token": kv_token, "chunk": tr["chunk"],
            "matmul_params": program.matmul_params(hf),
            "attn_flops_per_ctx_token": program.attn_flops_fwd(hf, 1, 1)}
    if ctx.trace:
        info["rounds"] = [(p.tolist(), n.tolist()) for p, n in rounds.rounds]
    late = [r.admitted_ms - r.arrival_ms for r in rep.requests
            if r.admitted_ms is not None]
    notes = {"sizes": {"lanes": tr["lanes"], "max_len": tr["max_len"],
                       "page": page, "chunk": tr["chunk"],
                       "pool_tokens": tr["lanes"] * tr["max_len"],
                       "warm_prompt_lengths": n_shapes},
             "window": {"requests": len(reqs), "finished": len(finished),
                        "tokens": rep.tokens, "end_s": end_s,
                        "rounds": rep.rounds, "prefills": rep.prefills,
                        "preempted": sum(r.n_preempt for r in rep.requests),
                        "ttft_p50_ms": float(np.percentile(ttft, 50)),
                        "tpot_p50_ms": float(np.percentile(tpot, 50)),
                        "queue_wait_p90_ms": float(np.percentile(late, 90))}}

    # correctness, after the window, with the program's state freed
    picked = sample(rep.requests, tr["check_requests"], seed31)
    prompts = [r.prompt for r in picked]
    served = [list(r.generated) for r in picked]
    del eng, rep, reqs, finished
    if ctx.trace:
        rounds.fn = None
    gc.collect()
    from refs import serve as ref_serve
    t_ref = time.perf_counter()
    seqs, pairs = ref_serve.served_positions(prompts, served)
    logits = ref_serve.Reference(hf).logits(
        seed31, ref_serve.pack(seqs, tr["max_len"]))
    gap = float(ref_serve.gaps(logits, pairs).max()) if pairs else np.inf
    notes["reference"] = {"seconds": time.perf_counter() - t_ref,
                          "requests": len(picked),
                          "served_tokens": sum(len(g) for g in served)}
    n_req, n_fin = notes["window"]["requests"], notes["window"]["finished"]
    checks = [Check("logit_gap", gap, tr["limits"]["logit_gap"]),
              Check("unfinished", float(n_req - n_fin), 0.0)]
    return Result(metrics=metrics, attempted=n_req, failed=n_req - n_fin,
                  checks=checks, memory_peak_bytes=mem, info=info,
                  trace=tracer.result if ctx.trace else None, notes=notes)
