"""The coded-training cell's check at a size a test run holds, on the CPU.

At these widths the weights are kept in float32 (bfloat16 weights of width
64 round away most of a step's change); the check is otherwise the cell's.
The control (the reference in int8 in the program's place) fails the
cell's limits, and a run with the timed path broken underneath comes out
not correct: a step that returns its state unchanged, half of the batch
left out with the mean taken over the rest, and a token altered where it
is produced.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import calibrate, run
from chipbench.traffic import train

CELL = "ds7b-coded-l1.frc-s2048"
SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, head_dim=16, vocab_size=256,
             param_dtype="float32", compute_dtype="float32")
WL = dict(seq_len=32, max_steps=200)


def _run(seed=5):
    return run.run_cell(CELL, seed, 0.5, False, devices=jax.devices()[:1],
                        wl_override=WL, cfg_override=SMALL)


def _session(seed):
    _, _, wl, cfg = run.load_cell(CELL)
    wl.update(WL)
    cfg.update(SMALL)
    return train.Session(cfg, wl, seed, jax.devices()[:1]), wl


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["window_compiles"] == 0


def test_control_fails_the_limits():
    seed = 2**31 + 17
    s, wl = _session(seed)
    s.setup()
    got = s.calibrate()
    got.update(calibrate.fault_readings(train, s.cfg, wl, seed,
                                        s.devices))
    limits = wl["check"]["limits"]
    assert all(got["program"][k] <= v for k, v in limits.items())
    assert any(got["control"][k] > v for k, v in limits.items())
    assert any(got["fault_half"][k] > v for k, v in limits.items())


def _unchanged(monkeypatch):
    from repro.train import coded
    orig = coded.adamw_update

    def frozen(grads, state, params, **kw):
        _, new_state, metrics = orig(grads, state, params, **kw)
        return params, new_state, metrics
    monkeypatch.setattr(coded, "adamw_update", frozen)


def _half(monkeypatch):
    from repro.train import coded
    orig = coded.coded_combine_call

    def half(flat, decode):
        m = decode.shape[0]
        keep = jnp.asarray(np.where(np.arange(m) % 2 == 0, 2.0, 0.0),
                           decode.dtype)
        return orig(flat, decode * keep)
    monkeypatch.setattr(coded, "coded_combine_call", half)


def _token(monkeypatch):
    from repro.data import pipeline
    orig = pipeline.GroupBatcher.next_batch

    def altered(self, code=None):
        tokens, labels, coeff = orig(self, code)
        tokens = tokens.copy()
        s = tokens.shape[-1] // 2
        tokens[..., s] = (tokens[..., s] + 1) % self.stream.vocab
        return tokens, labels, coeff
    monkeypatch.setattr(pipeline.GroupBatcher, "next_batch", altered)


@pytest.mark.parametrize("fault", [_unchanged, _half, _token],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
