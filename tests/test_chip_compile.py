"""The Pallas kernels the serving path selects, compiled for a described TPU
v5e at the widths chip_smoke.py serves (repro.configs.irli_deep1b per
repetition: d=96, B=20000, K=10 probes of m=5, int8 block 32; R=8 and
L=2^20 as cut for one chip). Nothing runs: the TPU compiler refuses here
what it would refuse on the chip — unaligned slices, unsupported ops,
scoped VMEM overruns — at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import irli_deep1b as paper

R_SMOKE, L_SMOKE, Q_BATCH, TOP_C = 8, 1 << 20, 64, 1024
ML = 2 * (L_SMOKE // paper.B_BUCKETS)                 # member-matrix width
WIDTH = R_SMOKE * paper.SERVE_M * ML                  # candidate slots


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_freq_topc_compiles_at_smoke_width(one_chip):
    from repro.kernels.freq_topc.freq_topc import (MAX_WIDTH, freq_topc,
                                                   pow2_width)
    assert pow2_width(WIDTH) <= MAX_WIDTH     # resolution selects the kernel
    cands = _sds(one_chip, (Q_BATCH, WIDTH), jnp.int32)
    compiled = jax.jit(lambda c: freq_topc(c, C=TOP_C)).lower(
        cands).compile()
    assert _custom_calls(compiled) == 1


@pytest.mark.parametrize("metric", ["angular", "l2"])
def test_quant_rerank_compiles_at_deep1b_widths(one_chip, metric):
    from repro.kernels.quant_rerank.quant_rerank import quant_rerank
    from repro.store.rerank import resolve_refine_k
    D, block = paper.D, paper.STORE_BLOCK
    kp = resolve_refine_k(0, paper.SERVE_K, TOP_C)
    args = (_sds(one_chip, (Q_BATCH, D)),
            _sds(one_chip, (L_SMOKE, D), jnp.int8),
            _sds(one_chip, (L_SMOKE, D // block)),
            _sds(one_chip, (Q_BATCH, TOP_C), jnp.int32),
            _sds(one_chip, (Q_BATCH, TOP_C)))
    compiled = jax.jit(lambda *a: quant_rerank(
        *a, tau=paper.SERVE_TAU, k=kp, metric=metric)).lower(*args).compile()
    assert _custom_calls(compiled) == 1


def test_served_kernels_keep_their_instruction_names(one_chip):
    """A trace names each device operation by its HLO instruction, and the
    benchmark's kernel readers match ``freq_topc`` and ``quant_rerank``:
    the served int8 program, compiled for a v5e, holds exactly those two
    Pallas custom-calls under those names (the pallas_calls' fixed
    ``name=``), whatever the kernel functions are called."""
    import re

    from repro.core.query import QueryPipeline
    from repro.store.quantized import QuantizedStore
    L, R, B, H, ML, Q = 1 << 14, 8, 512, 256, 64, 64
    D, block = paper.D, paper.STORE_BLOCK
    params = {"w1": _sds(one_chip, (R, D, H)), "b1": _sds(one_chip, (R, H)),
              "w2": _sds(one_chip, (R, H, B)), "b2": _sds(one_chip, (R, B))}
    store = QuantizedStore("int8", block, _sds(one_chip, (L, D), jnp.int8),
                           _sds(one_chip, (L, D // block)))
    pipe = QueryPipeline(m=paper.SERVE_M, tau=paper.SERVE_TAU,
                         k=paper.SERVE_K, topC=TOP_C, store_dtype="int8",
                         kernels=("freq_topc", "quant_rerank"))
    compiled = jax.jit(pipe.search).lower(
        params, _sds(one_chip, (R, B, ML), jnp.int32), store,
        _sds(one_chip, (Q, D))).compile()
    names = re.findall(r'^\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)? = .*'
                       r'custom_call_target="tpu_custom_call"',
                       compiled.as_text(), re.M)
    assert sorted(names) == ["freq_topc", "quant_rerank"]


def _top_m_program(one_chip, rows, select):
    logits = _sds(one_chip, (32, rows, paper.B_BUCKETS))
    return jax.jit(lambda x: select(x, paper.SERVE_M)).lower(
        logits).compile().as_text()


@pytest.mark.parametrize("rows", [1, 512])
def test_top_m_compiles_without_a_sort_at_deep1b_widths(one_chip, rows):
    """The probe's top-m over B=20000 buckets, R=32 repetitions, for the
    smallest and the largest of the server's batch buckets: the v5e program
    sorts nothing."""
    from repro.core.query import top_m
    assert " sort(" not in _top_m_program(one_chip, rows, top_m)


def test_top_k_compiles_to_a_sort_at_deep1b_widths(one_chip):
    """The control: ``lax.top_k`` there is a sort of every bucket."""
    assert " sort(" in _top_m_program(one_chip, 512, jax.lax.top_k)
