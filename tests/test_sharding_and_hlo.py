"""Sharding rules (divisibility fallbacks) + loop-aware HLO analysis."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.launch.hlo_analysis import analyze_hlo, _shape_bytes
from repro.models import transformer as T
from repro.sharding import spec_for_shape, make_specs


MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_divisible_dims_sharded():
    s = spec_for_shape(MESH, (5120, 13824), ("embed", "ff"))
    assert s == P(("data",), "model")


def test_spec_non_divisible_falls_back():
    # 28 heads % 16 != 0 -> replicated head dim
    s = spec_for_shape(MESH, (3584, 28, 128), ("embed", "heads", "head_dim"))
    assert s == P(("data",), None, None)


def test_spec_axis_used_once():
    # expert dim takes `model`; ff cannot reuse it
    s = spec_for_shape(MESH, (16, 4096, 6400), ("expert", "embed", "ff"))
    assert s == P("model", ("data",), None)


def test_spec_multipod_fsdp():
    s = spec_for_shape(MESH3, (8192, 24576), ("embed", "ff"))
    assert s == P(("pod", "data"), "model")


def test_make_specs_whole_model():
    cfg = ARCHS["phi3.5-moe-42b-a6.6b"]
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    specs = make_specs(MESH, shapes, T.param_axes(cfg))
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(jax.tree.leaves(shapes))
    # something must actually be sharded over each axis
    txt = str(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
    assert "model" in txt and "data" in txt


def test_shape_bytes():
    assert _shape_bytes("f32[8,128]") == 8 * 128 * 4
    assert _shape_bytes("bf16[2,2]{1,0}") == 8
    assert _shape_bytes("(f32[4], s32[2])") == 16 + 8


def test_analyze_hlo_scan_multiplier():
    """Loop-aware flops must be trip_count x body flops (cost_analysis is
    known to count while bodies once)."""
    def scanned(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=8)
        return y
    c = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    naive = c.cost_analysis()["flops"]
    aware = analyze_hlo(c.as_text())["flops"]
    single = 2 * 128 ** 3
    assert naive < 1.01 * single      # XLA counts the body once
    assert aware == 8 * single        # we count trips


def test_analyze_hlo_collectives_in_loop():
    import os
    # uses however many local devices exist (1 is fine: no collectives then)
    txt = """
HloModule test
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4] get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %ar = f32[4] all-reduce(%x), to_apply=%add
  ROOT %t = (s32[], f32[4]) tuple(%i2, %ar)
}
%cond (p2: (s32[], f32[4])) -> pred[] {
  %p2 = (s32[], f32[4]) parameter(0)
  %j = s32[] get-tuple-element(%p2), index=0
  %lim = s32[] constant(12)
  ROOT %lt = pred[] compare(%j, %lim), direction=LT
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0)
  %zero = s32[] constant(0)
  %tup = (s32[], f32[4]) tuple(%zero, %a)
  %w = (s32[], f32[4]) while(%tup), condition=%cond, body=%body
  ROOT %out = f32[4] get-tuple-element(%w), index=1
}
"""
    t = analyze_hlo(txt)
    assert t["all-reduce"] == 12 * 16   # 12 trips x 16 bytes
