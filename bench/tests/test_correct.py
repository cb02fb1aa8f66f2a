"""What decides ``correct``, at a size a test run holds, on the CPU.

A whole run of each cell is driven at tiny widths, skipping the look for
a chip: a sound run comes out correct; with the timed path broken
underneath (each fault the cell can have) it comes out not correct; and
the control, the reference computed in the precision below the
configuration's put in the program's place, fails the cell's limits.

    python -m pytest -q bench/tests
"""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402

SEED = 3_000_000_001
SERVE = "serve-mistral7b-chat"


def tiny(cell):
    c = dict(cell.config, hidden_size=128, intermediate_size=256,
             num_attention_heads=4, num_key_value_heads=2,
             vocab_size=512, num_hidden_layers=2)
    t = dict(cell.traffic, rate_per_s=20.0, max_len=96, lanes=4, chunk=4,
             prompt=dict(median=32, sigma=0.7, lo=16, hi=64, multiple=16),
             output=dict(median=8, sigma=0.8, lo=4, hi=16))
    return dataclasses.replace(cell, config=c, traffic=t)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def run_cell(name, capsys, seconds=1.0):
    rc = harness.run(["--workload", name, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", "0"],
                     require_chip=False, cell_override=tiny)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    return out


def test_sound_run_is_correct(capsys):
    out = run_cell(SERVE, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_serve_token_altered_is_not_correct(monkeypatch, capsys):
    from repro.serve import scheduler
    orig = scheduler.Scheduler.advance

    def advance(self, r, tokens):
        if len(r.generated) == 1 and tokens:      # the second token served
            tokens = [(int(tokens[0]) + 1) % 512] + list(tokens[1:])
        return orig(self, r, tokens)

    monkeypatch.setattr(scheduler.Scheduler, "advance", advance)
    out = run_cell(SERVE, capsys)
    assert out["correct"] is False, out["checks"]


def test_serve_control_reads_three_times_the_program(capsys):
    """The control chooses each position's token by float8 logits; its
    widest gap in the float32 reference is at least three times the
    program's on the same sizes."""
    import jax.numpy as jnp
    from refs import serve as ref_serve
    out = run_cell(SERVE, capsys)
    prog_gap = out["checks"]["logit_gap"]["value"]
    cell = tiny(harness.load_cell(SERVE))
    hf = cell.config
    s31 = harness.seed32(SEED)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, L) for L in (64, 48, 32, 16)]
    ref = ref_serve.Reference(hf)
    # the reference's own greedy continuation stands for what was served
    served = []
    for p in prompts:
        seq = list(p)
        for _ in range(12):
            lg = ref.logits(s31, ref_serve.pack([np.array(seq)], 96))
            seq.append(int(np.asarray(lg[0, len(seq) - 1]).argmax()))
        served.append(seq[len(p):])
    seqs, pairs = ref_serve.served_positions(prompts, served)
    packed = ref_serve.pack(seqs, 96)
    logits = ref.logits(s31, packed)
    assert ref_serve.gaps(logits, pairs).max() == 0.0
    ctrl = ref_serve.Reference(hf, lowp=jnp.float8_e4m3fn).logits(s31, packed)
    gap = ref_serve.gaps(logits, pairs, choose=ctrl).max()
    assert gap >= 3 * prog_gap, (gap, prog_gap)
