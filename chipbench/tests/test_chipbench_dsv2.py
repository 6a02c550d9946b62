"""The DeepSeek-V2-Lite cell at a size a test run holds, on the CPU.

The program's parts against the plain reference (``reference/dsv2.py``):
MLA with YaRN, the YaRN frequencies and softmax scale against their closed
form, the expert share (the shares of one layer add up to the whole
layer, with and without drops), the whole model's loss and gradients.
Then the cell's check itself, on one device: a sound run is correct, the
int8 control and the half-batch fault fail its limits, and a run with the
timed path broken underneath is not correct.  The counting and the
readers of the cell's per-layer metrics are checked by hand.
"""
import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import run
from chipbench.metrics import _counting_moe, _trace
from chipbench.reference import dsv2, lm
from chipbench.traffic import train_mesh

CELL = "dsv2lite-coded-l5.frc-s4096-x4"
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
             v_head_dim=12, intermediate_size=96, moe_intermediate_size=16,
             router_experts=16, n_routed_experts=4, expert_offset=4,
             num_experts_per_tok=3, vocab_size=256, num_hidden_layers=3,
             attn_chunk=16, param_dtype="float32", compute_dtype="float32")
WL = dict(seq_len=32, max_steps=200)


def _cfg(**kw) -> dict:
    _, _, _, cfg = run.load_cell(CELL)
    return {**cfg, **SMALL, **kw}


def _arch(cfg):
    return train_mesh.Session(cfg, {}, 0, None).arch()


def _params(cfg, seed=3):
    return lm.init(dsv2.layout(cfg), seed, jnp.float32)


def _run(seed=5):
    return run.run_cell(CELL, seed, 0.5, False, devices=jax.devices()[:1],
                        wl_override=WL, cfg_override=SMALL)


# -- the program's parts against the reference --------------------------------

def test_yarn_frequencies_and_scale_match_closed_form():
    from repro.configs import ARCHS
    from repro.models import mla, rope
    arch = ARCHS["deepseek-v2-lite"]
    y = arch.rope_yarn
    # the dims that turn 32 times and once over 4096 positions
    dim, theta = 64, 1e4
    lo = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(theta))
    hi = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(lo), math.ceil(hi)) == (10, 23)
    i = np.arange(32)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = theta ** (-2 * i / dim) * ((1 - ramp) + ramp / 40)
    np.testing.assert_allclose(rope.yarn_inv_freq(dim, theta, y), want,
                               rtol=1e-12)
    cfg = _cfg(qk_rope_head_dim=64, rope_theta=theta)
    np.testing.assert_allclose(
        dsv2.yarn_inv_freq(dim, theta, cfg["rope_scaling"]), want,
        rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(arch) == pytest.approx(192 ** -0.5 * m * m)
    assert dsv2.softmax_scale(cfg | {"qk_nope_head_dim": 128}) == \
        pytest.approx(192 ** -0.5 * m * m)
    # mscale == mscale_all_dim: the rotary tables keep unit magnitude
    cos, sin = rope.rope_angles(jnp.arange(8), dim, theta, y)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.0,
                               rtol=1e-6)


def test_mla_with_yarn_matches_reference():
    from repro.configs import attn_block
    from repro.models import transformer as T
    cfg = _cfg()
    arch = _arch(cfg)
    S = 64                                   # four key chunks of 16
    bp = {k: v[0] for k, v in _params(cfg)["lead"]["0"].items()}
    x = jax.random.normal(jax.random.key(1), (1, S, cfg["hidden_size"]))
    got, _ = T._attn_full(bp, attn_block(), x, arch,
                          T._rope_ctx(arch, jnp.arange(S), None), True,
                          False, None)
    cos, sin = dsv2.rope_tables(S, cfg)
    with jax.default_matmul_precision("highest"):
        want = dsv2.mla(bp, x[0], cos, sin, cfg, lm._mm("highest"))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("capacity_factor,drops", [(8.0, False),
                                                   (1.0, True)],
                         ids=["no-drop", "drop"])
def test_expert_shares_add_up_to_the_whole_layer(capacity_factor, drops):
    """8 shares of 2 experts each: the sum of their outputs, with the
    shared experts counted once, is the reference's whole layer; their
    counters add up to every assignment and to the whole layer's drops."""
    from repro.models.moe import moe_apply
    E, held, B, S, k = 16, 2, 2, 32, 3
    cfg = _cfg(n_routed_experts=E, expert_offset=0,
               capacity_factor=capacity_factor)
    arch = _arch(cfg).with_overrides(experts_held=0, expert_offset=0)
    p = {n: v[0] for n, v in _params(cfg)["blocks"]["0"].items()}
    x = jax.random.normal(jax.random.key(2), (B, S, cfg["hidden_size"]))
    total = routed = dropped = 0
    for j in range(E // held):
        part = dict(p, **{n: p[n][j * held:(j + 1) * held]
                          for n in ("moe_wg", "moe_wi", "moe_wo")})
        out, aux = moe_apply(part, x, arch.with_overrides(
            experts_held=held, expert_offset=j * held))
        total = total + out
        routed += int(aux["moe_routed"])
        dropped += int(aux["moe_dropped"])
    mm = lm._mm("highest")
    shared = jax.vmap(lambda h: dsv2._swiglu(
        h, p["shared_wg"], p["shared_wi"], p["shared_wo"], mm))(x)
    total = total - (E // held - 1) * shared
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([dsv2.moe(p, x[b], cfg, mm)[0] for b in range(B)])
        whole, whole_aux = moe_apply(p, x, arch)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert routed == B * S * k == int(whole_aux["moe_routed"])
    assert dropped == int(whole_aux["moe_dropped"])
    assert (dropped > 0) == drops


def test_whole_model_loss_and_gradients_match_reference():
    from repro.models import transformer as T
    cfg = _cfg()
    arch = _arch(cfg)
    params = _params(cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg["vocab_size"], (33,)), jnp.int32)
    alpha = cfg["aux_loss_alpha"]

    def program(p):
        logits, aux = T.forward(p, arch, toks[None, :-1])
        logp = jax.nn.log_softmax(logits[0], axis=-1)
        ce = -jnp.sum(jnp.take_along_axis(logp, toks[1:, None], -1))
        return ce + alpha * aux["load_balance"], ce

    def reference(p):
        ce, aux = dsv2.row_losses(p, toks[:-1], toks[1:], cfg=cfg,
                                  precision="highest")
        return ce + alpha * aux, ce

    with jax.default_matmul_precision("highest"):
        (_, ce_p), g_p = jax.value_and_grad(program, has_aux=True)(params)
        (_, ce_r), g_r = jax.value_and_grad(reference, has_aux=True)(params)
    assert float(ce_p) == pytest.approx(float(ce_r), rel=1e-5)
    for (path, a), b in zip(lm.flat_leaves(g_p).items(),
                            lm.flat_leaves(g_r).values()):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, path


def test_layout_is_the_published_share():
    """The cell's weights: 508.8 M parameters, 81.0 M in the dense layer,
    100.4 M in each expert layer, 26.2 M in the embedding."""
    _, _, _, cfg = run.load_cell(CELL)
    lay = dsv2.layout(cfg)
    size = lambda pred: sum(int(np.prod(s)) for k, (s, _) in lay.items()
                            if pred(k))
    assert size(lambda k: True) == 508_846_592
    assert size(lambda k: k[0] == "lead") == 81_007_104
    assert size(lambda k: k[0] == "blocks") == 4 * 100_405_760
    assert size(lambda k: k == ("embed",)) == 26_214_400


# -- the cell's check -----------------------------------------------------------

def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["window_compiles"] == 0


def test_control_and_half_batch_fail_the_limits():
    seed = 2**31 + 17
    _, _, wl, cfg = run.load_cell(CELL)
    wl.update(WL)
    cfg.update(SMALL)
    s = train_mesh.Session(cfg, wl, seed, jax.devices()[:1])
    s.setup()
    got = s.calibrate()
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


@pytest.mark.parametrize("fault", [_unchanged, _token],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]


# -- counting and readers -------------------------------------------------------

def test_counting_moe_by_hand():
    _, _, _, cfg = run.load_cell(CELL)
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert _counting_moe.mla_params(2048, 16, 128, 64, 128, 512) == attn
    expert = 3 * 2048 * 1408
    moe_layer = attn + 2048 * 64 + 2 * expert + 6 * 8 / 64 * expert
    dense_layer = attn + 3 * 2048 * 10944
    per_token = dense_layer + 4 * moe_layer + 12800 * 2048
    assert _counting_moe.token_matmul_params(cfg) == per_token
    attention = 3 * 5 * 4096 * 16 * (128 + 64 + 128)
    assert _counting_moe.train_flops_per_token(cfg, 4096) == \
        pytest.approx(6 * per_token + attention)
    assert _counting_moe.train_flops_per_token(cfg, 4096) == \
        pytest.approx(1.862270976e9)


def _ctx(ops, counts, lo=0, hi=1000, window_s=2.0):
    trace = _trace.DeviceTrace(ops=ops, host=[], lines={})
    cfg = run.load_cell(CELL)[3]
    # the step's collectives as train_mesh reads them from its HLO text
    collectives = {"all-reduce-start.1": ("all-reduce-start", "fusion.0"),
                   "all-reduce-done.1": ("all-reduce-done",
                                         "all-reduce-start.1"),
                   "psum.3": ("all-reduce", "fusion.1"),
                   "all-gather.4": ("all-gather", "fusion.2"),
                   "all-reduce.7": ("all-reduce", "fusion.3"),
                   "reduce-scatter.8": ("reduce-scatter", "fusion.4")}
    session = types.SimpleNamespace(devices=[0, 1, 2, 3],
                                    collectives=collectives)
    return types.SimpleNamespace(
        trace=trace, lo_ns=lo, hi_ns=hi, window_s=window_s,
        record=types.SimpleNamespace(counts=counts), cfg=cfg,
        wl={"seq_len": 4096}, session=session,
        peaks={"flops_per_s": 197e12, "bytes_per_s": 819e9})


def test_moe_metric_readers_by_hand():
    # psum.3 is an all-reduce by its opcode; all-reduce.9 is not in the
    # step's HLO
    chip0 = [("all-reduce-start.1", 0, 10), ("fusion.2", 10, 20),
             ("all-reduce-done.1", 50, 60), ("psum.3", 70, 80),
             ("all-gather.4", 75, 90), ("fusion.5", 90, 200),
             ("all-reduce.9", 300, 400)]
    chip1 = [("all-reduce.7", 0, 100), ("reduce-scatter.8", 950, 1200)]
    ctx = _ctx({"/device:TPU:0": chip0, "/device:TPU:1": chip1},
               {"steps": 4, "tokens": 8192 * 4, "moe_routed": 200,
                "moe_dropped": 5, "moe_peak": 40})
    # chip 0: [0, 60] and [70, 90]; chip 1: [0, 100] and [950, 1000]
    assert run.load_reader("exchange_ms.moe")(ctx) == \
        pytest.approx(150e-6 / 4)
    assert run.load_reader("dropped_share.moe")(ctx) == pytest.approx(2.5)
    # 40 at the most loaded held expert against a mean of 200 / 8
    assert run.load_reader("held_load.moe")(ctx) == pytest.approx(1.6)
    want = 100 * 1.862270976e9 * 8192 * 4 / 2.0 / (4 * 197e12)
    assert run.load_reader("mfu.moe")(ctx) == pytest.approx(want)
    empty = _ctx({"/device:TPU:0": [("fusion.1", 0, 10)]},
                 {"steps": 4, "tokens": 0})
    for name in ("exchange_ms.moe", "dropped_share.moe", "mfu.moe",
                 "held_load.moe"):
        assert run.load_reader(name)(empty) is None
    # a session that read no collectives (the one-chip step) reports none
    ctx.session.collectives = {}
    assert run.load_reader("exchange_ms.moe")(ctx) is None
