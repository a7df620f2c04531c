"""``correct`` comes out false when the timed path is broken underneath,
and the float8 control reads wider gaps than the limit, at the tiny size
on the CPU. The faults a serving cell can have: a token altered where it
is produced, and a step that returns its state unchanged. (A batch mean
and an exchange between chips are not on a one-chip serving path.)"""
import json
from pathlib import Path

import numpy as np

from bench import run as bench_run
from bench.tests import tiny
from repro.core import engine as engine_lib

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 4242


def drive(**kw):
    return bench_run.run_cell({"name": "nsa1b.decode16k"}, tiny.cfg(), tiny.CLOSED,
                              BM["end_to_end"], [], SEED, 2.0, False, tiny.PEAKS, **kw)


def test_altered_token_is_not_correct(monkeypatch):
    step = engine_lib.BatchedSSVEngine.step

    def altered(self, active, strategy=None):
        toks, n = step(self, active, strategy)
        toks = toks.copy()
        rows = np.nonzero(active)[0]
        toks[rows, 0] = (toks[rows, 0] + 1) % self.tcfg.vocab_size
        return toks, n

    monkeypatch.setattr(engine_lib.BatchedSSVEngine, "step", altered)
    out = drive()
    assert out["correct"] is False
    assert out["checks"]["logit_gap_max"]["value"] > tiny.CFG["check"]["logit_gap_max"]


def test_state_left_unchanged_is_not_correct(monkeypatch):
    step = engine_lib.BatchedSSVEngine.step

    def frozen(self, active, strategy=None):
        # every row is stepped as inactive: the launch computes the tokens
        # but commits nothing, so caches and lengths come back unchanged
        return step(self, np.zeros_like(active), strategy)

    monkeypatch.setattr(engine_lib.BatchedSSVEngine, "step", frozen)
    out = drive()
    assert out["correct"] is False


def test_float8_control_fails_the_limit():
    out = drive(control=True)
    assert out["correct"] is True
    for name, limit in tiny.CFG["check"].items():
        assert out["readings"][name] <= limit < out["control"][name]


def test_float8_rounding_matches_the_dtype():
    import jax.numpy as jnp

    from bench.references import nsa_decoder
    x = np.random.default_rng(0).standard_normal(20000).astype(np.float32)
    x = np.concatenate([x * 1e-3, x, x * 100, [0.0, 448.0, 1e4, -1e4]]).astype(np.float32)
    want = np.asarray(jnp.asarray(np.clip(x, -448, 448)).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))
    got = np.asarray(nsa_decoder.round_e4m3(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)
