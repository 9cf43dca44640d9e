"""Validate the analytic roofline cost model + HLO collective parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import ShapeSpec
from repro.core.precision import PrecisionPolicy
from repro.launch.costmodel import (_forward_flops, geostat_cell_cost,
                                    geostat_dag_cost, lm_cell_cost)
from repro.launch.roofline import collective_bytes_from_hlo
from repro.models.config import ArchConfig, MoESpec
from repro.models.transformer import forward_lm, init_lm


def _hlo_flops(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost["flops"])


def test_analytic_flops_matches_xla_dense():
    """Single-cycle model (scan trip count 1) => cost_analysis is exact;
    the analytic model must agree within 25%."""
    cfg = ArchConfig(name="v", family="dense", n_layers=1, d_model=128,
                     n_heads=8, n_kv_heads=4, d_head=16, d_ff=512,
                     vocab=1024, remat=False)
    params, _ = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((4, 256), jnp.int32)
    measured = _hlo_flops(lambda p, t: forward_lm(p, t, cfg,
                                                  compute_dtype=jnp.float32)[0],
                          params, toks)
    analytic = _forward_flops(cfg, 4, 256)
    assert measured == pytest.approx(analytic, rel=0.25), \
        (measured, analytic)


def test_analytic_flops_matches_xla_moe():
    cfg = ArchConfig(name="vm", family="moe", n_layers=1, d_model=128,
                     n_heads=8, n_kv_heads=4, d_head=16, d_ff=0, vocab=1024,
                     moe=MoESpec(n_experts=8, top_k=2, d_expert=256),
                     remat=False)
    params, _ = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((4, 256), jnp.int32)
    measured = _hlo_flops(lambda p, t: forward_lm(p, t, cfg,
                                                  compute_dtype=jnp.float32)[0],
                          params, toks)
    analytic = _forward_flops(cfg, 4, 256)
    assert measured == pytest.approx(analytic, rel=0.3), (measured, analytic)


def test_lm_cell_cost_scaling_laws():
    """Sanity relations the roofline table relies on."""
    cfg = ArchConfig(name="s", family="dense", n_layers=4, d_model=256,
                     n_heads=8, n_kv_heads=4, d_head=32, d_ff=1024,
                     vocab=4096)
    axes = {"data": 16, "model": 16}
    train = ShapeSpec("t", "train", 4096, 256)
    decode = ShapeSpec("d", "decode", 32768, 128)
    c_train = lm_cell_cost(cfg, train, chips=256, mesh_axes=axes)
    c_dec = lm_cell_cost(cfg, decode, chips=256, mesh_axes=axes)
    assert c_train.flops > 100 * c_dec.flops          # train >> decode flops
    assert c_dec.hbm_bytes < c_train.hbm_bytes
    # kv_quant halves (approximately) the decode cache bytes
    c_dec_q = lm_cell_cost(cfg, decode, chips=256, mesh_axes=axes,
                           opts={"kv_quant": True})
    cache = c_dec.detail["cache_bytes"]
    cache_q = c_dec_q.detail["cache_bytes"]
    assert 0.4 < cache_q / cache < 0.6
    # no_fsdp removes the gather term
    c_nf = lm_cell_cost(cfg, train, chips=256, mesh_axes=axes,
                        opts={"no_fsdp": True})
    assert c_nf.collective_bytes_per_chip < c_train.collective_bytes_per_chip


def test_geostat_cost_band_fraction():
    c_mp = geostat_cell_cost(65536, 2048, diag_thick=4, chips=256)
    c_dp = geostat_cell_cost(65536, 2048, diag_thick=32, chips=256)
    assert c_dp.flops > c_mp.flops            # all-fp32 band costs more
    assert 0 < c_mp.detail["band_frac"] < 0.5
    # aligned version cuts the masked-full waste
    c_al = geostat_cell_cost(65536, 2048, diag_thick=4, chips=256,
                             off_update="aligned")
    assert c_al.flops < c_mp.flops


def test_geostat_dag_cost_exact_counts():
    # the DAG-fed sibling of geostat_cell_cost: raw task totals are exactly
    # p^3/3 * nb^3, and widening the fp32 band raises the weighted cost
    c2 = geostat_dag_cost(4096, 512, PrecisionPolicy.tpu(2), chips=16)
    c4 = geostat_dag_cost(4096, 512, PrecisionPolicy.tpu(4), chips=16)
    p, nb = 8, 512
    assert c2.detail["total_flops"] == pytest.approx((p**3 / 3) * nb**3)
    assert c2.model_flops == pytest.approx(4096**3 / 3)
    assert c4.flops > c2.flops                # more x6-weighted hi tiles
    assert c4.detail["hi_frac"] > c2.detail["hi_frac"]
    assert c2.detail["critical_path_tasks"] == 3 * p - 2
    # full policy degenerates to all-hi, conversion-free
    c_full = geostat_dag_cost(4096, 512, PrecisionPolicy.full(), chips=16)
    assert c_full.detail["hi_frac"] == pytest.approx(1.0)
    assert c_full.detail["convert_tiles"] == 0


def test_collective_parser_on_real_hlo():
    """K-sharded matmul must produce one all-reduce of known size."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "model")))
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32,
                             sharding=NamedSharding(mesh, P("model", None)))

    def f(a, b):
        return jax.lax.with_sharding_constraint(
            a @ b, NamedSharding(mesh, P()))

    compiled = jax.jit(f).lower(a, b).compile()
    coll = collective_bytes_from_hlo(compiled.as_text())
    # on 1 device XLA may elide the all-reduce; the parser must not crash
    assert coll["total"] >= 0
    assert set(coll) >= {"all-reduce", "all-gather", "total", "count"}


def test_collective_parser_synthetic_hlo():
    hlo = """
  %ar = f32[512,1024]{1,0} all-reduce(%dot), channel_id=1
  %ag.1 = bf16[64,256]{1,0} all-gather(%x), dimensions={0}
  %ars = f32[16]{0} all-reduce-start(%y)
  %ard = f32[16]{0} all-reduce-done(%ars)
  %cp = s8[128]{0} collective-permute(%z)
  %unrelated = f32[9999]{0} add(%a, %b)
"""
    coll = collective_bytes_from_hlo(hlo)
    assert coll["all-reduce"] == 512 * 1024 * 4 + 16 * 4
    assert coll["all-gather"] == 64 * 256 * 2
    assert coll["collective-permute"] == 128
    assert coll["count"] == 4
