"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` against a
described (not attached) v5e and compiled by the TPU compiler installed
here, which refuses what the chip's would — blocks that break the (8, 128)
tiling rule, lane-splitting reshapes, scoped-VMEM overruns.  Shapes are the
ridge ``paper`` deployment (m=32 workers, r=256 rows each, p=6000, the
fast-Hadamard frame N=8192) and the 100m coded LM's gradient width.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.coded_reduce import coded_combine_call
from repro.kernels.encode import srht_encode_call
from repro.kernels.fused_step import fused_masked_gradient
from repro.kernels.fwht import fwht_kernel_call

M, R, P, N, LM_WIDTH = 32, 256, 6000, 8192, 97_536_768


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_fused_masked_gradient_compiles(one_chip):
    text = _compiled_text(
        lambda SX, Sy, w, mask: fused_masked_gradient(
            SX, Sy, w, mask, n=4096, beta=2.0, interpret=False),
        one_chip, (M, R, P), (M, R), (P,), (M,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,width", [(M, P), (8, LM_WIDTH)],
                         ids=["ridge-paper", "lm-100m"])
def test_coded_combine_compiles(one_chip, m, width):
    text = _compiled_text(
        lambda g, c: coded_combine_call(g, c, interpret=False),
        one_chip, (m, width), (m, 1))
    assert "tpu_custom_call" in text


def test_fwht_compiles(one_chip):
    text = _compiled_text(lambda x: fwht_kernel_call(x, interpret=False),
                          one_chip, (512, N))
    assert "tpu_custom_call" in text


def test_srht_encode_compiles(one_chip):
    """The fast-Hadamard encode of the ridge paper data and targets:
    p + 1 = 6001 transform rows over the full N-frame."""
    text = _compiled_text(
        lambda xt, d: srht_encode_call(xt, d, lo=0, hi=N, scale=1 / 64.0,
                                       interpret=False),
        one_chip, (P + 1, N), (1, N))
    assert "tpu_custom_call" in text


def test_batched_gd_runner_compiles_with_kernel(one_chip, monkeypatch):
    """The coded-gd runner at the smoke run's shape (4 trials x 100 steps)
    takes the fused kernel — steered here as the chip's backend would."""
    import repro.kernels.fused_step as fused_step
    from repro.core.data_parallel import EncodedProblem
    from repro.runtime.runners import _batched_gd
    monkeypatch.setenv("REPRO_FUSED", "1")
    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)

    def run(SX, Sy, X, y, masks, w0):
        prob = EncodedProblem(SX=SX, Sy=Sy, X=X, y=y, lam=0.1, beta=2.0,
                              n=4096)
        return _batched_gd(prob, masks, 1e-3, w0)

    text = _compiled_text(run, one_chip, (M, R, P), (M, R), (4096, P),
                          (4096,), (4, 100, M), (4, P))
    assert "tpu_custom_call" in text


def test_batched_gd_runner_shares_slab_at_ridge_cell(one_chip, monkeypatch):
    """The ridge cell's solve (16 realizations x 100 steps over the paper
    deployment's SX) lowers the shared-slab body: one kernel call an
    iteration, its output the whole (16, p) batch, within scoped VMEM."""
    import repro.kernels.fused_step as fused_step
    from repro.core.data_parallel import EncodedProblem
    from repro.runtime.runners import _batched_gd
    monkeypatch.setenv("REPRO_FUSED", "1")
    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)

    def run(SX, Sy, X, y, masks, w0):
        prob = EncodedProblem(SX=SX, Sy=Sy, X=X, y=y, lam=0.05, beta=2.0,
                              n=4096)
        return _batched_gd(prob, masks, 1e-3, w0)

    text = _compiled_text(run, one_chip, (M, R, P), (M, R), (4096, P),
                          (4096,), (16, 100, M), (16, P))
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels) == 1
    assert f"f32[16,{P}]" in kernels[0].split("custom-call(")[0]
