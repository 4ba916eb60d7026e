"""Roofline extractor validation against XLA's own cost_analysis."""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.launch.roofline import (analyze_text, normalize_cost_analysis,
                                   roofline_terms, Cost)


def _compile(fn, *specs, shardings=None):
    j = jax.jit(fn) if shardings is None else jax.jit(fn,
                                                      in_shardings=shardings)
    return j.lower(*specs).compile()


def test_flops_match_cost_analysis_dot_dominated():
    def f(x, ws):
        for i in range(4):
            x = jnp.maximum(x @ ws[i], 0)
        return x.sum()
    comp = _compile(jax.grad(f, argnums=1),
                    jax.ShapeDtypeStruct((256, 512), jnp.float32),
                    jax.ShapeDtypeStruct((4, 512, 512), jnp.float32))
    ca = normalize_cost_analysis(comp.cost_analysis())
    cost = analyze_text(comp.as_text(), world=1)
    assert cost.flops == pytest.approx(ca["flops"], rel=0.05)
    # bytes is a fusion-boundary proxy: where XLA draws fusion boundaries
    # varies by version (0.4.x CPU fuses less), so only the order of
    # magnitude is stable — assert agreement within 3×.
    ratio = cost.bytes / ca["bytes accessed"]
    assert 1 / 3 < ratio < 3, (cost.bytes, ca["bytes accessed"])


def test_scan_trip_count_multiplied():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()
    L = 7
    comp = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                    jax.ShapeDtypeStruct((L, 64, 64), jnp.float32))
    c1 = analyze_text(comp.as_text(), world=1, force_trip_one=True)
    cL = analyze_text(comp.as_text(), world=1)
    assert cL.flops == pytest.approx(L * c1.flops, rel=0.02)


def test_collective_ring_model():
    """all-reduce over an 8-way axis moves 2·(8−1)/8·size bytes/device."""
    import os
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (run under dryrun env)")


def test_collective_bytes_parsed(tmp_path):
    hlo = """
HloModule test

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024] parameter(0)
  ROOT %ar = f32[1024] all-reduce(%p), replica_groups=[64,8]<=[512], to_apply=%add
}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}
"""
    cost = analyze_text(hlo, world=512)
    # f32 halved (CPU bf16-emulation correction): 2·(7/8)·4096 / 2
    assert cost.coll_bytes == pytest.approx(2 * (7 / 8) * 4096 * 0.5)


def test_roofline_terms_bottleneck():
    t = roofline_terms(Cost(flops=197e12, bytes=1.0, coll_bytes=1.0),
                       model_flops_per_device=197e12)
    assert t["bottleneck"] == "compute"
    assert t["roofline_frac"] == pytest.approx(1.0)
    t = roofline_terms(Cost(flops=1.0, bytes=819e9 * 2, coll_bytes=0.0))
    assert t["bottleneck"] == "memory"


def test_peaks_keyed_by_device_kind():
    """Peak rates come from the table keyed by ``device_kind`` (TPU v5e,
    Google Cloud's published figures); an unknown chip is an error."""
    from repro.launch.mesh import TARGET_KIND, peaks
    pk = peaks(TARGET_KIND)
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
