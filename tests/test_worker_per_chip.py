"""DeepSeek-V2-Lite in coded training: the model through the normal
``spec -> plan -> execute`` path, the expert layers' counters in the
trainer, and the worker-per-chip step (one coded worker per device, the
decode-weighted combine as a cross-device reduce-scatter, the AdamW state
split over the devices) against the one-device
vmap step on a forced 4-device CPU mesh."""
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.configs import ARCHS
from repro.runtime import ClusterEngine, make_delay_model
from repro.train.coded import CodedTrainer, TrainerConfig, TrainProblem

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_deepseek_v2_lite_through_spec_plan_execute(tmp_path, monkeypatch):
    from repro.experiments.execute import execute
    from repro.experiments.plan import plan
    from repro.experiments.spec import (DelayAxis, ExperimentSpec, ObsAxis,
                                        PlacementAxis, ProblemAxis,
                                        StrategyAxis, TrialsAxis)
    monkeypatch.setenv("REPRO_RUNSTORE", str(tmp_path / "store"))
    spec = ExperimentSpec(
        problems=(ProblemAxis.train("deepseek-v2-lite", seq_len=16,
                                    vocab=64),),
        strategies=(StrategyAxis(name="coded-sgd", k=3,
                                 options=(("warmup", 1),)),),
        delays=DelayAxis(delays=("bimodal",), m=4),
        trials=TrialsAxis(trials=1, eval_every=1, seed=0),
        placement=PlacementAxis(mode="single"), steps=2, obs=ObsAxis())
    pl = plan(spec)
    assert [c.skip for c in pl.cells] == [None]
    rec = execute(pl).records[0]
    assert rec["meta"]["arch"] == "deepseek-v2-lite"
    assert np.isfinite(rec["final_metric"])


def test_trainer_counts_expert_assignments_in_one_read():
    """The smoke variant keeps MLA, the shared experts, the leading dense
    layer and an expert share; the trainer reads the loss, the gradient
    norm and both counters back in one read a step."""
    cfg = TrainProblem(arch="deepseek-v2-lite", seq_len=16,
                       vocab=64).build_cfg()
    assert cfg.kv_lora_rank and cfg.n_shared_experts
    assert cfg.first_dense_layers == 1
    assert 0 < cfg.experts_held < cfg.n_experts
    tcfg = TrainerConfig(m_workers=4, beta=2, wait_k=3, rows_per_worker=2,
                         seq_len=16, steps=3, lr=1e-3, warmup=1,
                         log_every=0)
    tr = CodedTrainer(cfg, tcfg, ClusterEngine(make_delay_model("bimodal"),
                                               4, seed=0))
    tr.run()
    got = tr.metrics.summary()
    # every worker routes its 2 rows x 16 tokens x top-k in every expert
    # layer; a share of them lands on the experts held here
    assigned = 3 * 4 * 2 * 16 * cfg.top_k * cfg.n_periods
    assert 0 < got["moe_routed"] < assigned
    assert 0 <= got["moe_dropped"] <= got["moe_routed"]
    # a row's most loaded held expert takes at least the mean of the held
    held_rows = 3 * 4 * 2 * cfg.n_periods
    assert got["moe_routed"] / cfg.experts_held <= got["moe_peak"]
    assert got["moe_peak"] <= min(got["moe_routed"], held_rows * 16)
    assert got["host_syncs"] == 3 * 3          # block, one read, FRC decode


_MESH_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core.gradient_coding import make_frc
    from repro.data.pipeline import GroupBatcher, TokenStream
    from repro.models import transformer as T
    from repro.optim import adamw_init, cosine_schedule
    from repro.train.coded import (TrainProblem, build_coded_train_step,
                                   build_worker_per_chip_step,
                                   split_opt_state)
    assert len(jax.devices()) == 4
    cfg = TrainProblem(arch="deepseek-v2-lite", seq_len=32,
                       vocab=128).build_cfg()
    code = make_frc(4, 2)
    batcher = GroupBatcher(TokenStream(cfg.vocab, seed=0), code, 2, 32,
                           seed=0)
    tokens, labels, coeff = batcher.next_batch()
    mask = np.array([1, 0, 1, 1], np.float32)       # worker 1 straggles
    decode = code.decode_weights(mask)
    lr = cosine_schedule(1e-3, 1, 10)
    kw = dict(rows_per_group=2, num_groups=code.num_groups)
    params = T.init_params(cfg, jax.random.key(0))
    opt = adamw_init(params)
    args = [jnp.asarray(a) for a in (tokens, labels, coeff, decode)]
    p1, o1, m1 = jax.jit(build_coded_train_step(cfg, lr, **kw))(
        params, opt, *args)
    mesh = Mesh(np.asarray(jax.devices()), ("workers",))
    o4 = split_opt_state(opt, params, mesh)
    assert o4.m["embed"].sharding.spec == ("workers",)
    p4, o4, m4 = jax.jit(build_worker_per_chip_step(cfg, lr, mesh, **kw))(
        params, o4, *args)
    # each device holds a quarter of every moment, zero-padded
    whole = lambda t, like: [np.asarray(x)[:y.size].reshape(y.shape)
                             for x, y in zip(jax.tree.leaves(t),
                                             jax.tree.leaves(like))]
    assert all(not np.asarray(x)[y.size:].any()
               for x, y in zip(jax.tree.leaves((o4.m, o4.v)),
                               jax.tree.leaves((params, params))))
    for k in ("moe_routed", "moe_dropped", "moe_peak"):
        assert int(m1[k]) == int(m4[k]), (k, m1[k], m4[k])
    assert int(m4["moe_routed"]) > 0
    assert abs(float(m1["loss"]) - float(m4["loss"])) <= 1e-6 * abs(
        float(m1["loss"]))
    # the moments hold the gradient times the clipping factor; each path
    # takes that from its own norm, and XLA:CPU's long f32 dots of the vmap
    # path's whole leaves round it by a few 1e-5, so the moments are
    # compared with the factor taken out, and the mesh path's norm, summed
    # from the devices' parts, against the decoded gradient's in f64
    unclip = lambda m: 1.0 / min(1.0, 1.0 / float(m["grad_norm"]))
    u1, u4 = unclip(m1), unclip(m4)
    g4 = [np.asarray(x, np.float64) * u4 / 0.1 for x in whole(o4.m, params)]
    exact = np.sqrt(sum((g * g).sum() for g in g4))
    assert abs(float(m4["grad_norm"]) - exact) <= 1e-5 * exact, (
        m4["grad_norm"], exact)
    err = max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-30))
              for a, b in zip(
                  jax.tree.leaves(p1)
                  + [x * u1 for x in jax.tree.leaves(o1.m)]
                  + [x * u1 * u1 for x in jax.tree.leaves(o1.v)],
                  jax.tree.leaves(p4)
                  + [x * u4 for x in whole(o4.m, params)]
                  + [x * u4 * u4 for x in whole(o4.v, params)]))
    assert err < 1e-5, err
    # a state already split is left as it is
    o5 = split_opt_state(o4, p4, mesh)
    assert all(a is b for a, b in zip(jax.tree.leaves(o4.m),
                                      jax.tree.leaves(o5.m)))
    moved = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(params)))
    assert moved > 0
    print("MESH_OK", err)
""")


def test_worker_per_chip_step_matches_vmap_step():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout


def test_trainer_refuses_a_device_count_other_than_the_workers():
    import jax
    import pytest
    cfg = ARCHS["deepseek-v2-lite"].smoke_variant()
    tcfg = TrainerConfig(m_workers=4, beta=2, wait_k=3, seq_len=16, steps=1)
    eng = ClusterEngine(make_delay_model("bimodal"), 4, seed=0)
    with pytest.raises(ValueError, match="one device per worker"):
        CodedTrainer(cfg, tcfg, eng, devices=jax.devices() * 2)
