"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode runs the kernel bodies in Python and cannot see what the
chip's compiler (Mosaic) refuses: block shapes off the (8, 128) tile,
unaligned DMA slices, batched contractions it cannot lower, VMEM over the
limit.  These tests compile each kernel, through the ``kernels.ops`` entry
the models call with ``backend="pallas"``, for a v5e that is described and
not attached, at the widths of ``configs.speed_tig.TIG`` (the paper's:
d=172, dim_time=100, K=10, B=200, 2 heads) and of ``TIG_MXU`` (2 layers,
one 128-wide head, K=16).  Nothing runs: a pass means the chip's compiler
accepts the program and put the kernel in it (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and pytest-xdist workers
each import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.speed_tig import TIG, TIG_MXU
from repro.kernels import ops
from repro.kernels.neighbor_sample import export_length

N_NODES = 100_000          # node-memory rows at the issue's test scale
N_EDGES = 1_000_000        # stream edges behind the T-CSR export
CONFIGS = {"tig": TIG, "tig_mxu": TIG_MXU}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no chip
        # compiler here"; the reason is reported in the skip message
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text: str, name: str):
    assert 'custom_call_target="tpu_custom_call"' in text
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and f"%{name}" in line]
    assert calls, f"kernel {name!r} missing from the compiled program"


def _gru_args(cfg, sds):
    r, d, dm = 2 * cfg.batch_size, cfg.dim, cfg.msg_dim
    return (sds((r, dm)), sds((r, d)), sds((dm, 3 * d)), sds((d, 3 * d)),
            sds((3 * d,)), sds((3 * d,)))


def _attn_args(cfg, sds):
    r = 3 * cfg.batch_size            # src ++ dst ++ neg, one launch
    h, k = cfg.n_heads, cfg.num_neighbors
    dh = cfg.dim // h
    return (sds((r, h, dh)), sds((r, k, h, dh)), sds((r, k, h, dh)),
            sds((r, k), jnp.bool_))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_gru_fwd_compiles(sds, name):
    text = _compile(lambda *a: ops.gru(*a, backend="pallas"),
                    *_gru_args(CONFIGS[name], sds))
    _assert_kernel(text, "fused_gru")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_gru_bwd_compiles(sds, name):
    grad = jax.grad(lambda *a: jnp.sum(ops.gru(*a, backend="pallas")),
                    argnums=tuple(range(6)))
    text = _compile(grad, *_gru_args(CONFIGS[name], sds))
    _assert_kernel(text, "fused_gru_bwd")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_temporal_attention_fwd_compiles(sds, name):
    text = _compile(
        lambda q, k, v, m: ops.temporal_attention(q, k, v, m,
                                                  backend="pallas"),
        *_attn_args(CONFIGS[name], sds))
    _assert_kernel(text, "temporal_attn")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_temporal_attention_bwd_compiles(sds, name):
    def loss(q, k, v, m):
        return jnp.sum(ops.temporal_attention(q, k, v, m, backend="pallas"))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    *_attn_args(CONFIGS[name], sds))
    _assert_kernel(text, "temporal_attn_bwd")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_fused_flush_compiles(sds, name):
    cfg = CONFIGS[name]
    r, d, dm = 2 * cfg.batch_size, cfg.dim, cfg.msg_dim
    args = (sds((r,), jnp.int32), sds((r, dm)), sds((r,)),
            sds((N_NODES + 1, d)), sds((N_NODES + 1,)),
            sds((dm, 3 * d)), sds((d, 3 * d)), sds((3 * d,)), sds((3 * d,)))
    text = _compile(lambda *a: ops.fused_flush(*a, backend="pallas"), *args)
    _assert_kernel(text, "fused_flush")


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_neighbor_sample_compiles(sds, name):
    cfg = CONFIGS[name]
    k, depth = cfg.num_neighbors, cfg.n_layers
    ev = export_length(2 * N_EDGES, k, depth)
    rows = 3 * cfg.batch_size * depth     # L * (src ++ dst ++ neg)
    tcsr = {"indptr": sds((N_NODES + 1,), jnp.int32),
            "nbr": sds((ev,), jnp.int32), "t": sds((ev,)),
            "eidx": sds((ev,), jnp.int32), "bat": sds((ev,), jnp.int32)}

    def sample(tcsr, nodes, batch_of, window):
        return ops.neighbor_sample(tcsr, nodes, batch_of, k,
                                   backend="pallas", window=window)
    text = _compile(sample, tcsr, sds((rows,), jnp.int32),
                    sds((), jnp.int32), sds((rows,), jnp.int32))
    _assert_kernel(text, "neighbor_sample")
