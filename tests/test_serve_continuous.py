"""Continuous batching + paged KV + drop-masked TP decode (DESIGN.md §18).

Pins: allocator/scheduler policy invariants (pure Python), paged-vs-
contiguous cache bit-identity, p=0 ContinuousEngine == legacy ServeEngine
greedy decode, preemption-recompute determinism, the TP decode exchange
against the W-matrix oracle, and the serving telemetry schema.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import plan as plan_lib
from repro.core import wmatrix
from repro.models import build_model
from repro.netsim import NetConfig, request_trace
from repro.serve import (BlockAllocator, ContinuousEngine, PagedCache,
                         Request, Scheduler, ServeEngine, TPDecodeConfig,
                         n_pages)
from repro.serve.engine import (PREFILL_PROGRAM, PREFILL_PROGRAMS,
                                ROUND_PROGRAM, round_counts)
from repro.serve.kvcache import NULL_BLOCK, WRITE_PROGRAM
from repro.serve.scheduler import FINISHED, RUNNING, WAITING
from repro.serve.tp import TPContext
from repro.telemetry import Telemetry
from repro.telemetry.trace import validate_chrome_trace


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------

def test_allocator_lowest_first_and_null_reserved():
    a = BlockAllocator(8)
    assert a.capacity == 7
    got = a.alloc(3)
    assert got == [1, 2, 3]          # ascending-contiguous, never block 0
    assert NULL_BLOCK not in got


def test_allocator_all_or_nothing():
    a = BlockAllocator(4)
    assert a.alloc(3) == [1, 2, 3]
    assert a.alloc(1) is None        # empty — and nothing was taken
    a.free([2])
    assert a.n_free == 1
    assert a.alloc(2) is None
    assert a.alloc(1) == [2]


def test_allocator_free_validation():
    a = BlockAllocator(4)
    ids = a.alloc(2)
    a.free(ids)
    with pytest.raises(ValueError, match="double free"):
        a.free([ids[0]])
    with pytest.raises(ValueError, match="foreign"):
        a.free([0])


# ---------------------------------------------------------------------------
# Scheduler (pure Python — no model, no JAX)
# ---------------------------------------------------------------------------

def _req(rid, S=8, max_new=4, arrival=0.0):
    return Request(rid=rid, prompt=np.zeros(S, np.int32), max_new=max_new,
                   arrival_ms=arrival)


def _sched(n_blocks=64, max_batch=4, page=4, chunk=4):
    return Scheduler(BlockAllocator(n_blocks), max_batch=max_batch,
                     page=page, chunk=chunk)


def test_admission_is_fcfs_by_arrival():
    s = _sched(max_batch=2)
    for rid, t in [(0, 5.0), (1, 1.0), (2, 3.0)]:
        s.add(_req(rid, arrival=t))
    admitted, _ = s.schedule()
    assert [r.rid for r in admitted] == [1, 2]     # arrival order, not rid
    assert [r.rid for r in s.waiting] == [0]
    assert all(r.state == RUNNING for r in admitted)
    assert admitted[0].pos == admitted[0].prefill_len


def test_head_of_line_blocking():
    # pool of 4 blocks; r0 takes 3, the big r1 (needs 3) blocks r2 (needs 1)
    s = _sched(n_blocks=5, max_batch=4, page=4, chunk=4)
    s.add(_req(0, S=9, max_new=4, arrival=0.0))    # 12 slots -> 3 blocks
    s.add(_req(1, S=9, max_new=4, arrival=1.0))
    s.add(_req(2, S=2, max_new=2, arrival=2.0))    # 1 block — would fit
    admitted, _ = s.schedule()
    assert [r.rid for r in admitted] == [0]
    assert [r.rid for r in s.waiting] == [1, 2]    # r2 waits behind r1


def test_oom_preempts_youngest():
    # two running requests; the older one's growth evicts the younger
    s = _sched(n_blocks=7, max_batch=2, page=4, chunk=4)
    r0 = _req(0, S=8, max_new=9, arrival=0.0)      # 16 slots -> 4 blocks
    r1 = _req(1, S=8, max_new=9, arrival=1.0)
    s.add(r0), s.add(r1)
    admitted, _ = s.schedule()                     # both admitted, 3+3
    assert [r.rid for r in admitted] == [0, 1]
    s.advance(r0, [0] * 4), s.advance(r1, [0] * 4)  # pos -> 11
    _, preempted = s.schedule()                    # r0 grows, pool dry
    assert [r.rid for r in preempted] == [1]
    assert r1.state == WAITING and r1.blocks == [] and r1.n_preempt == 1
    assert r1.generated == [0] * 4                 # keeps its tokens
    assert r0.state == RUNNING and len(r0.blocks) == 4


def test_no_starvation_oldest_always_finishes_first():
    """Drive rounds on a tiny pool: FCFS + youngest-first preemption means
    the oldest live request is never passed and finishes first."""
    s = _sched(n_blocks=6, max_batch=3, page=4, chunk=4)
    reqs = [_req(i, S=8, max_new=9, arrival=float(i)) for i in range(3)]
    for r in reqs:
        s.add(r)
    finish_order = []
    for _ in range(50):
        if s.idle:
            break
        admitted, _ = s.schedule()
        for r in list(s.running):
            s.advance(r, [0] * min(s.chunk, r.n_left))
            if r.state == FINISHED and r.rid not in finish_order:
                finish_order.append(r.rid)
    assert s.idle
    assert finish_order == [0, 1, 2]


def test_add_rejects_request_larger_than_pool():
    s = _sched(n_blocks=3, page=4)
    with pytest.raises(ValueError, match="blocks"):
        s.add(_req(0, S=12, max_new=8))


def test_request_slot_accounting():
    r = _req(0, S=10, max_new=5)
    assert r.total_slots == 14          # final token emitted, never cached
    assert n_pages(14, 4) == 4
    with pytest.raises(ValueError, match="max_new"):
        _req(1, max_new=0)


# ---------------------------------------------------------------------------
# Paged cache + engine (deepseek-7b reduced: full attention, window=None —
# the strict bit-identity arch; windowed kinds share the masking code path)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg, grouped=True)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, rng, prompt_lens=(6, 10, 14), max_new=(3, 5, 9)):
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.choice(prompt_lens))),
                    max_new=int(rng.choice(max_new)))
            for i in range(n)]


def test_paged_prefill_bitwise_matches_contiguous(served):
    """A fresh pool allocates ascending-contiguous blocks, so the gathered
    per-request view equals the contiguous prefill cache row for row."""
    cfg, model, params = served
    S = 10
    toks = jnp.asarray(np.arange(1, S + 1, dtype=np.int32)[None, :])
    last_c, cache_c = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}))(params, toks)
    last_p, cache_p = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, paged=True))(
            params, toks)
    np.testing.assert_array_equal(np.asarray(last_c), np.asarray(last_p))

    pc = PagedCache(model, page=4, n_blocks=9)
    blocks = pc.alloc.alloc(n_pages(S, 4))
    pc.write_prefill(cache_p, blocks, S)
    view = pc.gather_contiguous(blocks, S)
    for kind in view:
        for leaf in ("k", "v"):
            got = np.asarray(view[kind][leaf])
            want = np.asarray(cache_p[kind][leaf][:, :, :S])
            np.testing.assert_array_equal(got, want)


def test_continuous_matches_legacy_greedy_bitwise(served):
    """p=0 (tp=None): the paged engine's tokens == ServeEngine.generate."""
    cfg, model, params = served
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    legacy = ServeEngine(model, params, max_len=64)
    ref = np.asarray(legacy.generate(jnp.asarray(prompts), 6))
    eng = ContinuousEngine(model, params, page=4, n_blocks=17, max_batch=2,
                           chunk=4, max_len=64)
    rep = eng.run([Request(rid=0, prompt=prompts[0], max_new=6)],
                  drain=True)
    assert rep.outputs()[0] == ref[0].tolist()


def test_preemption_recompute_is_deterministic(served):
    """A pool too small for two requests forces evict + re-prefill; greedy
    decoding makes the recomputed continuation exactly the unpreempted
    one."""
    cfg, model, params = served
    rng = np.random.default_rng(1)
    mk = lambda: [Request(rid=i,                                 # noqa: E731
                          prompt=rng.integers(0, cfg.vocab_size, size=10),
                          max_new=9) for i in range(3)]
    reqs_a = mk()
    reqs_b = [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new)
              for r in reqs_a]
    tight = ContinuousEngine(model, params, page=4, n_blocks=9,
                             max_batch=3, chunk=4, max_len=32)
    roomy = ContinuousEngine(model, params, page=4, n_blocks=65,
                             max_batch=3, chunk=4, max_len=32)
    ra = tight.run(reqs_a, drain=True)
    rb = roomy.run(reqs_b, drain=True)
    assert sum(r.n_preempt for r in ra.requests) > 0     # OOM actually hit
    assert sum(r.n_preempt for r in rb.requests) == 0
    assert ra.outputs() == rb.outputs()


def test_grouped_matches_ungrouped_paged(served):
    """The scanned-stack and faithful-unroll paged decode paths agree."""
    cfg, model, params = served
    model_u = build_model(cfg, grouped=False)
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab_size, size=(1, 7)).astype(np.int32)
    outs = []
    for m in (model, model_u):
        eng = ContinuousEngine(m, params, page=4, n_blocks=17, max_batch=1,
                               chunk=4, max_len=32)
        outs.append(eng.run([Request(rid=0, prompt=prompts[0], max_new=5)],
                            drain=True).outputs())
    assert outs[0] == outs[1]


def test_engine_rejects_oversized_request(served):
    cfg, model, params = served
    eng = ContinuousEngine(model, params, page=4, n_blocks=17, max_len=16)
    bad = Request(rid=0, prompt=np.zeros(12, np.int32), max_new=8)
    with pytest.raises(ValueError, match="prompt_len 12 \\+ max_new 8"):
        eng.run([bad], drain=True)


def test_lossy_tp_decode_serves_to_completion(served):
    """Drop-masked TP decode: every request still gets max_new tokens
    (activation drops perturb values, never the control flow)."""
    cfg, model, params = served
    rng = np.random.default_rng(3)
    reqs = _requests(cfg, 3, rng)
    eng = ContinuousEngine(model, params, page=4, n_blocks=33, max_batch=2,
                           chunk=4, max_len=32,
                           tp=TPDecodeConfig(n_shards=2, p=0.3))
    rep = eng.run(reqs, drain=True)
    assert {r.rid: len(r.generated) for r in rep.requests} \
        == {r.rid: r.max_new for r in reqs}
    assert all(0 <= t < cfg.vocab_size
               for v in rep.outputs().values() for t in v)


# ---------------------------------------------------------------------------
# TP exchange vs the W-matrix oracle
# ---------------------------------------------------------------------------

def test_tp_exchange_matches_wmatrix_oracle():
    """TPContext._exchange on deadline-channel masks == W-matrix algebra:
    renorm block average of n·partial_i over delivered senders, own-partial
    fallback on an AG miss."""
    d, B, n = 24, 3, 4
    cfg = TPDecodeConfig(
        n_shards=n, receiver=1,
        channel="deadline:deadline_ms=8,straggler_frac=0.4")
    ctx = TPContext(cfg, d_model=d, batch=B, n_heads=4, d_ff=8, n_layers=2)
    state = ctx.init_state(jax.random.PRNGKey(0))
    (rs, ag), state = ctx.sample_site_masks(jax.random.PRNGKey(1), state)
    assert rs.shape == (ctx.n_sites, n, ctx.plan.s)

    rng = np.random.default_rng(0)
    partials = rng.normal(size=(n, B, 1, d)).astype(np.float32)
    for site in range(ctx.n_sites):
        got = np.asarray(ctx._exchange(
            jnp.asarray(partials), (rs, ag), site, jax.random.PRNGKey(2)))
        rs_j, ag_j = np.asarray(rs[site]), np.asarray(ag[site])
        s = rs_j.shape[1]
        W = wmatrix.build_w(n, np.arange(s) % n, rs_j, ag_j)
        y = np.transpose(partials[:, :, 0, :] * n,
                         (0, 2, 1)).reshape(n, d * B).astype(np.float64)
        blk = -(-d * B // s)
        yp = np.pad(y, ((0, 0), (0, s * blk - d * B)))
        exp = np.concatenate(
            [(W[j].T @ yp[:, j * blk:(j + 1) * blk])[ctx.receiver]
             for j in range(s)])
        want = exp[:d * B].reshape(d, B).T[:, None, :]
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_tp_context_validation():
    with pytest.raises(ValueError, match="divide"):
        TPContext(TPDecodeConfig(n_shards=3, p=0.1), d_model=16, batch=1,
                  n_heads=4, d_ff=8, n_layers=1)
    with pytest.raises(ValueError, match="renorm"):
        TPContext(TPDecodeConfig(n_shards=2, p=0.1, recovery="ef"),
                  d_model=16, batch=1, n_heads=4, d_ff=8, n_layers=1)
    from repro.serve import make_tp_context
    assert make_tp_context(TPDecodeConfig(n_shards=4, p=0.0), None, 1) \
        is None                        # the structural dense gate
    assert make_tp_context(None, None, 1) is None


def test_decode_plan_shape():
    p = plan_lib.decode_plan(64, 4, n=4)
    assert p.s == 4 and len(p.buckets) == 1
    b = p.buckets[0]
    assert b.blk * p.s >= 64 * 4 and b.pad < p.s


# ---------------------------------------------------------------------------
# Telemetry + load generator
# ---------------------------------------------------------------------------

class _RoundLog:
    """Wraps an engine's decode round to log each call's host copies of
    the lanes' positions and tokens left."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, params, pool, bt, tok, pos, n_left, key, ch):
        self.calls.append((np.asarray(pos).copy(), np.asarray(n_left).copy()))
        return self.fn(params, pool, bt, tok, pos, n_left, key, ch)


def _round_sums(pos, n_left, chunk):
    """The loop form of ``round_counts``: per token step, the live lanes
    and the K/V positions each reads."""
    steps = tokens = kv_reads = 0
    for s in range(chunk):
        live = [p + s for p, k in zip(pos.tolist(), n_left.tolist())
                if k > s]
        if live:
            steps += 1
            tokens += len(live)
            kv_reads += sum(p + 1 for p in live)
    return {"steps": steps, "tokens": tokens, "kv_reads": kv_reads}


def _events(tel, name):
    return [e for e in tel.trace.events if e["name"] == name]


def test_serving_trace_schema(served, tmp_path):
    cfg, model, params = served
    rng = np.random.default_rng(4)
    tel = Telemetry()
    eng = ContinuousEngine(model, params, page=4, n_blocks=17, max_batch=2,
                           chunk=4, max_len=32, telemetry=tel)
    log = eng._round = _RoundLog(eng._round)
    reqs = _requests(cfg, 3, rng)
    eng.run(reqs, drain=True)
    obj = tel.trace.to_chrome()
    assert validate_chrome_trace(obj) == []
    names = {e["name"] for e in obj["traceEvents"]}
    assert {"serve.request", "serve.prefill", "serve.queue", "serve.step",
            "serve.admit", "serve.batch", "serve.round",
            "serve.advance"} <= names
    spans = [e for e in obj["traceEvents"] if e["name"] == "serve.request"]
    assert {s["args"]["rid"] for s in spans} == {r.rid for r in reqs}
    q = [e for e in obj["traceEvents"] if e["name"] == "serve.queue"]
    assert {"waiting", "running", "kv_blocks_used", "kv_blocks_free"} \
        <= set(q[0]["args"])

    # the span tree: every phase lies inside a serve.step, and the
    # serve.queue counter outside every one
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in _events(tel, "serve.step")]
    for name in ("serve.admit", "serve.prefill", "serve.batch",
                 "serve.round", "serve.advance"):
        for e in _events(tel, name):
            assert any(s0 <= e["ts"] and e["ts"] + e["dur"] <= s1
                       for s0, s1 in steps), (name, e)
    for e in q:
        assert not any(s0 < e["ts"] < s1 for s0, s1 in steps)
    assert sum(e["args"]["admitted"] for e in _events(tel, "serve.admit")) \
        == len(_events(tel, "serve.prefill"))
    assert sum(e["args"]["finished"]
               for e in _events(tel, "serve.advance")) <= len(reqs)
    for e in _events(tel, "serve.prefill"):
        assert e["args"]["programs"] == PREFILL_PROGRAMS

    # serve.round's counters are the sums over the lanes the round ran
    rounds = _events(tel, "serve.round")
    assert len(rounds) == len(log.calls) > 0
    for e, (pos, n_left) in zip(rounds, log.calls):
        assert e["args"]["program"] == ROUND_PROGRAM
        assert {k: e["args"][k] for k in ("steps", "tokens", "kv_reads")} \
            == _round_sums(pos, n_left, eng.chunk)
    assert [e["args"]["lanes"] for e in _events(tel, "serve.batch")] \
        == [int((n > 0).sum()) for _, n in log.calls]
    path = tmp_path / "trace.json"
    tel.trace.write(str(path))
    assert path.exists()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_round_counts_match_loop(seed):
    rng = np.random.default_rng(seed)
    pos = rng.integers(0, 300, 6).astype(np.int32)
    n_left = rng.integers(0, 12, 6).astype(np.int32)
    n_left[rng.integers(0, 6)] = 0                      # an idle lane
    assert round_counts(pos, n_left, 8) == _round_sums(pos, n_left, 8)


def test_serving_prefill_spans_first_and_wait(served):
    """``first`` is 1 on a request's first prefill and 0 on each
    re-prefill after preemption; ``wait_ms`` is the prefill's start less
    the request's due time, on the span's own clock."""
    cfg, model, params = served
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=10),
                    max_new=9, arrival_ms=5.0 * i) for i in range(3)]
    tel = Telemetry()
    eng = ContinuousEngine(model, params, page=4, n_blocks=9, max_batch=3,
                           chunk=4, max_len=32, telemetry=tel)
    rep = eng.run(reqs)
    assert sum(r.n_preempt for r in rep.requests) > 0   # OOM actually hit
    pre = _events(tel, "serve.prefill")
    by_rid = {r.rid: [e for e in pre if e["args"]["rid"] == r.rid]
              for r in rep.requests}
    for r in rep.requests:
        evs = by_rid[r.rid]
        assert [e["args"]["first"] for e in evs] \
            == [1] + [0] * r.n_preempt
        first = evs[0]["args"]["wait_ms"]
        assert 0.0 <= first <= r.admitted_ms - r.arrival_ms
        assert all(e["args"]["wait_ms"] >= r.first_token_ms - r.arrival_ms
                   for e in evs[1:])
    # wait_ms + arrival_ms is the prefill's start on the run's clock: it
    # differs from the span's wall-clock start by one offset for all
    # (to within half the 5 ms between arrivals)
    offsets = [e["ts"] / 1e3 - e["args"]["wait_ms"]
               - reqs[e["args"]["rid"]].arrival_ms for e in pre]
    assert max(offsets) - min(offsets) < 2.5


class _StubTrace:
    """Only what a profiler-backed stand-in offers the engine: ``span``
    (a TraceAnnotation), ``complete``, ``now_us`` and ``counter``."""

    def __init__(self):
        self.counters = 0

    @property
    def trace(self):
        return self

    def span(self, name, **args):
        return jax.profiler.TraceAnnotation(name, **args)

    def complete(self, name, ts_us, dur_us, **args):
        pass

    def now_us(self):
        return 0.0

    def counter(self, name, values):
        self.counters += 1


@pytest.mark.parametrize("kind", ["buffer", "stub"])
def test_serving_telemetry_bit_identical(served, kind):
    """Served tokens are the same with telemetry off, with a
    ``Telemetry``, and with a stand-in that has only the four calls the
    engine may make."""
    cfg, model, params = served
    outs = []
    for tel in (None, Telemetry() if kind == "buffer" else _StubTrace()):
        rng = np.random.default_rng(5)
        eng = ContinuousEngine(model, params, page=4, n_blocks=17,
                               max_batch=2, chunk=4, max_len=32,
                               telemetry=tel)
        outs.append(eng.run(_requests(cfg, 3, rng), drain=True).outputs())
    assert outs[0] == outs[1]
    if kind == "stub":
        assert tel.counters > 0


def _module(lowered):
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def test_program_names_match_lowerings(served):
    cfg, model, params = served
    eng = ContinuousEngine(model, params, page=4, n_blocks=17, max_batch=2,
                           chunk=4, max_len=32)
    toks = jnp.zeros((1, 8), jnp.int32)
    assert _module(eng._prefill.lower(params, toks)) == PREFILL_PROGRAM
    _, pcache = jax.eval_shape(eng._prefill, params, toks)
    cache = PagedCache(model, page=4, n_blocks=17)
    assert _module(cache._writer(8).lower(
        cache.pool, pcache, jnp.zeros(8, jnp.int32))) == WRITE_PROGRAM
    B = eng.max_batch
    lanes = jnp.zeros(B, jnp.int32)
    assert _module(eng._round.lower(
        params, cache.pool, jnp.zeros((B, eng.max_pages), jnp.int32),
        jnp.zeros((B, 1), jnp.int32), lanes, lanes,
        jax.random.PRNGKey(0), None)) == ROUND_PROGRAM
    assert PREFILL_PROGRAMS.split(";") == [PREFILL_PROGRAM, WRITE_PROGRAM]


def test_request_trace_deterministic_and_in_range():
    cfg = NetConfig(sim_s=0.5)
    a = request_trace(100.0, cfg, n_requests=20, seed=7)
    b = request_trace(100.0, cfg, n_requests=20, seed=7)
    assert a == b and len(a) == 20
    for t_ms, pl, mn in a:
        assert 0.0 <= t_ms < cfg.sim_s * 1e3
        assert pl in (8, 16, 32) and mn in (4, 8, 16, 32)
    assert [t for t, _, _ in a] == sorted(t for t, _, _ in a)
    c = request_trace(100.0, cfg, n_requests=20, seed=8)
    assert c != a
