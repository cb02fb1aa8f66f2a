"""Serving engines: the legacy static-batch sampler and the
continuous-batching engine (DESIGN.md §18).

``make_serve_steps`` builds the jitted prefill / decode closures (these are
what the decode-shape dry-runs lower); :class:`ServeEngine` is a small
batched greedy/temperature sampler on top for the examples — static
batches, one host round-trip per token.

:class:`ContinuousEngine` is the production path: per-request admission
and iteration-level join/evict (``serve.scheduler``), a paged KV cache
(``serve.kvcache``), optional drop-masked tensor-parallel decode
(``serve.tp``), and a fused on-device decode loop — ``lax.scan`` over
``chunk`` tokens with in-graph sampling and a donated slot pool, so the
host syncs once per *round* instead of once per token.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import Model
from repro.serve.kvcache import WRITE_PROGRAM, PagedCache, n_pages
from repro.serve.scheduler import FINISHED, RUNNING, Request, Scheduler
from repro.serve.tp import TPDecodeConfig, make_tp_context
from repro.telemetry.trace import NULL_SPAN


def make_serve_steps(model: Model, max_len: Optional[int] = None):
    prefill = jax.jit(lambda params, inputs: model.prefill(params, inputs,
                                                           max_len=max_len))

    # the cache is donated: the decode step updates it in place instead of
    # copying the full (B, max_len, kvh, hd) stack every token
    decode = jax.jit(
        lambda params, cache, token, pos: model.decode_step(
            params, cache, {"token": token}, pos),
        donate_argnums=(1,))

    return prefill, decode


@dataclasses.dataclass
class ServeEngine:
    model: Model
    params: Any
    max_len: int = 512
    temperature: float = 0.0

    def __post_init__(self):
        self._prefill, self._decode = make_serve_steps(self.model,
                                                       self.max_len)

    def generate(self, prompts: jnp.ndarray, n_new: int,
                 key: Optional[jax.Array] = None,
                 extra_inputs: Optional[Dict[str, Any]] = None):
        """prompts: (B, S) int32 -> (B, n_new) generated tokens."""
        B, S = prompts.shape
        if S + n_new > self.max_len:
            raise ValueError(
                f"prompt_len {S} + n_new {n_new} = {S + n_new} exceeds "
                f"ServeEngine.max_len {self.max_len}")
        inputs = {"tokens": prompts, **(extra_inputs or {})}
        last, cache = self._prefill(self.params, inputs)
        out = []
        tok = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
        pos = S
        for i in range(n_new):
            out.append(tok)
            logits, cache = self._decode(self.params, cache, tok,
                                         jnp.int32(pos))
            if self.temperature > 0 and key is not None:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits / self.temperature, axis=-1)[:, None]
            else:
                tok = jnp.argmax(logits, axis=-1)[:, None]
            tok = tok.astype(jnp.int32)
            pos += 1
        return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Continuous batching (DESIGN.md §18)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeReport:
    """Per-session outcome: the finished requests plus aggregate rates."""
    requests: List[Request]
    wall_s: float
    rounds: int
    prefills: int

    @property
    def tokens(self) -> int:
        return sum(len(r.generated) for r in self.requests)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / max(self.wall_s, 1e-9)

    def latencies_ms(self) -> np.ndarray:
        """Per-request arrival → finish latency."""
        return np.asarray([r.finish_ms - r.arrival_ms
                           for r in self.requests], np.float64)

    def latency_quantile(self, q: float) -> float:
        lat = self.latencies_ms()
        return float(np.quantile(lat, q)) if lat.size else float("nan")

    def outputs(self) -> Dict[int, List[int]]:
        return {r.rid: list(r.generated) for r in self.requests}


#: XLA module names of ``ContinuousEngine``'s programs, as a profile names
#: their runs: the prefill, and the fused decode round
PREFILL_PROGRAM = "jit__lambda"
ROUND_PROGRAM = "jit_round_fn"
#: the programs a ``serve.prefill`` span runs (';'-joined: the profiler
#: splits an annotation's arg at commas)
PREFILL_PROGRAMS = f"{PREFILL_PROGRAM};{WRITE_PROGRAM}"


def _no_span(name, **args):
    return NULL_SPAN


def _fetch(x: jax.Array) -> np.ndarray:
    """``x`` on the host. The copy is queued behind the program that makes
    ``x`` and the wait for that program runs under no JAX profiler span
    (``np.asarray`` opens one, ``block_until_ready`` does not), so a
    profile's idle gap after the program falls to the engine span that
    holds the wait, not to ``np.asarray``."""
    x.copy_to_host_async()
    x.block_until_ready()
    return np.asarray(x)


def round_counts(pos: np.ndarray, n_left: np.ndarray, chunk: int
                 ) -> Dict[str, int]:
    """What one decode round does, from the lanes' host positions and
    tokens left (0 on an idle lane): ``steps`` token steps with a live
    lane, ``tokens`` emitted, and ``kv_reads`` K/V positions read — a
    lane live at step s reads positions 0..pos+s."""
    k = np.minimum(n_left, chunk).astype(np.int64)
    p = pos.astype(np.int64)
    return {"steps": int(k.max(initial=0)), "tokens": int(k.sum()),
            "kv_reads": int((k * (p + 1) + k * (k - 1) // 2).sum())}


@dataclasses.dataclass
class ContinuousEngine:
    """Continuous-batching paged-KV serving engine.

    ``run()`` serves a list of :class:`~repro.serve.scheduler.Request`s to
    completion: arrivals respected against the wall clock (or all at once
    with ``drain=True``), FCFS admission with iteration-level join/evict,
    per-request prefill scattered into the paged pool, and fused
    ``chunk``-token decode rounds over ``max_batch`` lanes. ``tp`` switches
    the per-layer decode collectives onto the drop-masked exchange; left
    inactive, the engine is pinned bit-identical to :class:`ServeEngine`
    greedy decoding (tests/test_serve_continuous.py).

    With ``telemetry`` set, each loop iteration records nested host spans
    through ``telemetry.trace.span`` (``serve.step`` over ``serve.admit``,
    ``serve.prefill``, ``serve.batch``, ``serve.round``, ``serve.advance``;
    ``serve.wait`` while nothing is due), one ``serve.queue`` counter after
    ``serve.step`` closes, and per-request ``serve.request`` events via
    ``now_us``/``complete``. A span's context value takes args known only
    at its end through ``set_metadata``, as TraceAnnotation's does.
    """
    model: Model
    params: Any
    page: int = 16
    n_blocks: int = 65                  # 64 usable + the null block
    max_batch: int = 8
    chunk: int = 8
    max_len: int = 512
    temperature: float = 0.0
    tp: Optional[TPDecodeConfig] = None
    telemetry: Optional[Any] = None     # a repro.telemetry.Telemetry
    seed: int = 0

    def __post_init__(self):
        if self.model.decode_paged is None:
            raise ValueError(f"{self.model.cfg.name}: model has no paged "
                             f"decode path")
        if self.max_len % self.page:
            # the block table is sized in whole pages; a ragged tail page
            # would silently shrink the usable context
            self.max_len = n_pages(self.max_len, self.page) * self.page
        self.max_pages = self.max_len // self.page
        self.tp_ctx = make_tp_context(self.tp, self.model.cfg,
                                      self.max_batch)
        self._prefill = jax.jit(                   # PREFILL_PROGRAM
            lambda params, toks: self.model.prefill(params,
                                                    {"tokens": toks},
                                                    paged=True))
        self._round = self._build_round()
        self._writers = {}              # per-length prefill-scatter jits,
                                        # shared across run() sessions

    # -- jitted fused decode round ----------------------------------------

    def _build_round(self):
        model, page, chunk = self.model, self.page, self.chunk
        temp, tp_ctx = self.temperature, self.tp_ctx

        def round_fn(params, pool, bt, tok, pos, n_left, key, ch_state):
            def step(carry, _):
                pool, tok, pos, n_left, key, ch_state = carry
                key, k_step = jax.random.split(key)
                masks = None
                if tp_ctx is not None:
                    k_ch, k_step = jax.random.split(k_step)
                    masks, ch_state = tp_ctx.sample_site_masks(k_ch,
                                                               ch_state)
                active = n_left > 0
                logits, pool = model.decode_paged(
                    params, pool, {"token": tok}, pos, bt, page=page,
                    masks=masks, tp=tp_ctx, key=k_step)
                if temp > 0:
                    key, k_s = jax.random.split(key)
                    nxt = jax.random.categorical(k_s, logits / temp,
                                                 axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(jnp.int32)
                emitted = jnp.where(active, nxt, -1)
                tok = jnp.where(active[:, None], nxt[:, None], tok)
                pos = pos + active.astype(jnp.int32)
                n_left = n_left - active.astype(jnp.int32)
                return (pool, tok, pos, n_left, key, ch_state), emitted

            carry = (pool, tok, pos, n_left, key, ch_state)
            (pool, _, _, _, _, ch_state), toks = jax.lax.scan(
                step, carry, None, length=chunk)
            return pool, toks, ch_state       # toks: (chunk, B)

        return jax.jit(round_fn, donate_argnums=(1,))   # ROUND_PROGRAM

    # -- session ------------------------------------------------------------

    def _check(self, r: Request) -> None:
        S = len(r.prompt)
        if S + r.max_new > self.max_len:
            raise ValueError(
                f"request {r.rid}: prompt_len {S} + max_new {r.max_new} "
                f"= {S + r.max_new} exceeds max_len {self.max_len}")

    def run(self, requests: Sequence[Request], *, drain: bool = False
            ) -> ServeReport:
        """Serve `requests` to completion. ``drain=True`` ignores arrival
        times (offered-load / throughput mode); otherwise requests join
        the waiting queue when the wall clock passes their ``arrival_ms``.
        """
        for r in requests:
            self._check(r)
        cache = PagedCache(self.model, self.page, self.n_blocks,
                           writers=self._writers)
        sched = Scheduler(cache.alloc, max_batch=self.max_batch,
                          page=self.page, chunk=self.chunk)
        pending = sorted(requests, key=lambda r: (r.arrival_ms, r.rid))
        lanes: List[Optional[Request]] = [None] * self.max_batch
        key = jax.random.PRNGKey(self.seed)
        ch_state = (self.tp_ctx.init_state(key)
                    if self.tp_ctx is not None else None)
        reg = self.telemetry
        tel = reg.trace if reg is not None else None
        span = tel.span if tel is not None else _no_span
        t0 = time.perf_counter()
        now = lambda: (time.perf_counter() - t0) * 1e3     # noqa: E731
        rounds = prefills = 0

        while pending or not sched.idle:
            if sched.idle and not drain and pending[0].arrival_ms > now():
                with span("serve.wait"):
                    time.sleep(
                        min(max(pending[0].arrival_ms - now(), 0.0), 50.0)
                        / 1e3)
                continue

            with span("serve.step"):
                with span("serve.admit") as sp:
                    t = now()
                    while pending and (drain or pending[0].arrival_ms <= t):
                        sched.add(pending.pop(0))
                    admitted, _ = sched.schedule()
                    # preempted/finished requests lose their lane
                    for i, r in enumerate(lanes):
                        if r is not None and r.state != RUNNING:
                            lanes[i] = None
                    sp.set_metadata(admitted=len(admitted))

                for r in admitted:
                    full = np.concatenate(
                        [r.prompt, np.asarray(r.generated, np.int32)])
                    with span("serve.prefill", rid=r.rid,
                              tokens=int(full.size),
                              first=int(r.admitted_ms is None),
                              wait_ms=now() - r.arrival_ms,
                              programs=PREFILL_PROGRAMS):
                        last, pcache = self._prefill(
                            self.params, jnp.asarray(full[None, :]))
                        cache.write_prefill(pcache, r.blocks, int(full.size))
                        if r.admitted_ms is None:
                            r.admitted_ms = now()
                        if tel is not None and \
                                getattr(r, "_ts_us", None) is None:
                            r._ts_us = tel.now_us()
                        tok0 = int(_fetch(jnp.argmax(last[0])))
                        if r.first_token_ms is None:
                            r.first_token_ms = now()
                    prefills += 1
                    sched.advance(r, [tok0])
                    if r.state == RUNNING:
                        lane = lanes.index(None)
                        lanes[lane] = r
                        r.lane = lane
                    elif r.state == FINISHED:
                        self._finish(r, now(), tel)

                live = [r for r in lanes if r is not None]
                if live:
                    with span("serve.batch", lanes=len(live)):
                        bt = np.zeros((self.max_batch, self.max_pages),
                                      np.int32)
                        pos = np.zeros(self.max_batch, np.int32)
                        n_left = np.zeros(self.max_batch, np.int32)
                        tok = np.zeros((self.max_batch, 1), np.int32)
                        for i, r in enumerate(lanes):
                            if r is None:
                                continue
                            bt[i] = cache.block_row(r.blocks, self.max_pages)
                            pos[i] = r.pos
                            n_left[i] = r.n_left
                            tok[i, 0] = r.generated[-1]
                        args = (jnp.asarray(bt), jnp.asarray(tok),
                                jnp.asarray(pos), jnp.asarray(n_left))
                        key, k_r = jax.random.split(key)
                    with (span("serve.round", program=ROUND_PROGRAM,
                               **round_counts(pos, n_left, self.chunk))
                          if tel is not None else NULL_SPAN):
                        pool, toks, ch_state = self._round(
                            self.params, cache.pool, *args, k_r, ch_state)
                        cache.pool = pool
                        toks_np = _fetch(toks)
                        t_end = now()
                    rounds += 1
                    with span("serve.advance") as sp:
                        finished = 0
                        for i, r in enumerate(lanes):
                            if r is None:
                                continue
                            k = min(self.chunk, r.n_left)
                            sched.advance(r, toks_np[:k, i].tolist())
                            if r.state == FINISHED:
                                lanes[i] = None
                                self._finish(r, t_end, tel)
                                finished += 1
                        sp.set_metadata(finished=finished)
            if tel is not None:
                tel.counter("serve.queue", {
                    "waiting": len(sched.waiting),
                    "running": len(sched.running),
                    "kv_blocks_used": cache.alloc.capacity
                    - cache.alloc.n_free,
                    "kv_blocks_free": cache.alloc.n_free})

        wall = time.perf_counter() - t0
        done = sorted(requests, key=lambda r: r.rid)
        return ServeReport(requests=list(done), wall_s=wall,
                           rounds=rounds, prefills=prefills)

    @staticmethod
    def _finish(r: Request, t_ms: float, tel) -> None:
        r.finish_ms = t_ms
        if tel is not None and getattr(r, "_ts_us", None) is not None:
            tel.complete("serve.request", r._ts_us,
                         tel.now_us() - r._ts_us, rid=r.rid,
                         prompt_len=int(len(r.prompt)),
                         max_new=int(r.max_new),
                         n_preempt=int(r.n_preempt))


def make_requests(trace: Sequence[Tuple[float, int, int]], vocab: int,
                  seed: int = 0) -> List[Request]:
    """Materialise a ``netsim.request_trace`` load (arrival_ms,
    prompt_len, max_new) into concrete requests with random prompts."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=int(pl)),
                    max_new=int(mn), arrival_ms=float(am))
            for i, (am, pl, mn) in enumerate(trace)]
