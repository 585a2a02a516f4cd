"""Ahead-of-time compiles of every kernel ``repro.kernels.ops`` dispatches,
for a described TPU v5e chip (no chip attached).

Interpret mode cannot see what the TPU compiler refuses — lane gathers,
unaligned DMA slices, scoped-VMEM overruns, vector compares the target
lacks — so each kernel is compiled at the paper deployment's widths
(d=384, a 128-row batch, a 2^20-row slab, 2048 IVF candidates) for f32 and
int8 slabs, and the compiled program must hold the Mosaic kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library at a time, and every test
worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import cosine_topk as ct
from repro.kernels.ivf_topk import ivf_topk_pallas

D, B, N, M = 384, 128, 1 << 20, 2048

SLABS = (jnp.float32, jnp.int8)


def _cases():
    """``(id, kernel, [(shape, dtype) per operand])`` for each kernel."""
    out = []
    for kd in SLABS:
        name = jnp.dtype(kd).name
        out += [
            (f"cosine_topk-{name}", ct.cosine_topk_pallas,
             [((B, D), jnp.float32), ((N, D), kd), ((N,), jnp.bool_)]),
            (f"cosine_topk_interval-{name}", ct.cosine_topk_interval_pallas,
             [((B, D), jnp.float32), ((N, D), kd), ((N,), jnp.bool_),
              ((B,), jnp.int32), ((B,), jnp.int32)]),
            (f"cosine_topk_masked-{name}", ct.cosine_topk_masked_pallas,
             [((B, D), jnp.float32), ((N, D), kd), ((B, N), jnp.bool_)]),
            (f"ivf_topk-{name}", ivf_topk_pallas,
             [((B, D), jnp.float32), ((N, D), kd), ((B, M), jnp.int32)]),
        ]
    quant = [((B, D), jnp.float32), ((N, D), jnp.int8), ((N,), jnp.float32)]
    out += [
        ("quant_cosine_topk", ct.quant_cosine_topk_pallas,
         quant + [((N,), jnp.bool_)]),
        ("quant_cosine_topk_interval", ct.quant_cosine_topk_interval_pallas,
         quant + [((N,), jnp.bool_), ((B,), jnp.int32), ((B,), jnp.int32)]),
        ("quant_cosine_topk_masked", ct.quant_cosine_topk_masked_pallas,
         quant + [((B, N), jnp.bool_)]),
    ]
    return out


CASES = _cases()


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, or a skip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,kernel,shapes", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, kernel, shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = kernel.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
