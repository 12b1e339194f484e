"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each program is lowered from shapes and compiled by the
TPU compiler for a chip that is described, not attached, so tiling,
VMEM and memory faults surface here instead of on the chip.  The
topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""
import importlib.util

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

HBM_BYTES = 16e9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    # Only a machine without the TPU plugin skips: where libtpu is
    # installed, a failure to describe the chip is a fault.
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU plugin (libtpu) is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A program compiled for a described chip cannot be read back from
    # the persistent cache without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,n,k", [(16384, 128, 8), (65536, 128, 256)])
def test_multi_seed_rows_is_a_tpu_kernel(one_chip, m, n, k):
    from repro.kernels import distance as dist
    compiled = dist.multi_seed_rows.lower(
        _spec(one_chip, (m, n), jnp.float32),
        _spec(one_chip, (m,), jnp.float32),
        _spec(one_chip, (k,), jnp.int32), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lockstep_round_compiles(one_chip):
    """One fused lockstep round at m=16384 shards, one trial per region
    of n=128, with a 256-row device row cache."""
    from repro.core import lockstep
    m, n, nt, w, cap = 16384, 128, 128, 1, 256
    f32, i32 = jnp.float32, jnp.int32
    S = lambda shape, dt: _spec(one_chip, shape, dt)
    lockstep._prep.lower(S((m, n), f32), S((m, n), f32), S((m,), i32),
                         S((nt, w), i32), n=n).compile()
    compiled = lockstep._round.lower(
        S((nt, w, m), f32), S((nt, m), f32), S((nt, m), jnp.bool_),
        S((m,), f32), S((cap, m), f32),
        S((nt,), i32), S((nt,), i32), S((nt,), jnp.bool_), S((nt, m), i32),
        S((nt,), i32), S((nt,), f32), frac=0.10, fixed=None, ct=1).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES


def test_float64_lloyd_loop_compiles(one_chip):
    """The device k-means' float64 Lloyd while-loop over 512 values."""
    from repro.core import clustering
    fn = clustering._lloyd_jit()
    with jax.enable_x64(True):
        fn.lower(_spec(one_chip, (512,), jnp.float64),
                 _spec(one_chip, (5,), jnp.float64), n_iter=100).compile()


@pytest.mark.parametrize("tokens", [1, 8])
def test_full_width_decode_step_fits_one_chip(one_chip, tokens):
    """The h2o-danube-3-4b decode step at its published widths (bf16,
    7.92 GB of parameters), as JitBackend calls it at 1 token and at a
    prefill chunk of 8."""
    from repro.configs import get_arch
    from repro.models import build
    cfg = get_arch("h2o-danube-3-4b").full
    api = build(cfg)
    place = lambda tree: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(lambda key: api.init(key)[0],
                                  jax.random.key(0)))
    state = place(jax.eval_shape(lambda: api.init_decode_state(1, 81)))
    toks = _spec(one_chip, (1, tokens), jnp.int32)
    pos = _spec(one_chip, (tokens,) if tokens > 1 else (), jnp.int32)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n_params > 3.9e9
    compiled = jax.jit(api.decode_step).lower(params, state, toks,
                                              pos).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < HBM_BYTES
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("tokens", [1, 256])
def test_expert_share_decode_step_reads_the_stack_in_place(one_chip, tokens):
    """DeepSeek-V2-Lite at one chip's share of an EP4 host (16 of 64
    experts, 4.91 B parameters, 9.82 GB in bf16) over a 4096-position
    latent cache, as JitBackend calls it at 1 token and at a prefill
    chunk of 256.  The temporaries stay small: neither the guarded
    per-expert products of a decode call nor the held experts of a
    prefill chunk copy a layer's 277 MB block of experts out of the
    stack."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import build
    full = get_arch("deepseek-v2-lite-16b").full
    cfg = full.with_(moe=dataclasses.replace(full.moe, held=16))
    api = build(cfg)
    place = lambda tree: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(lambda key: api.init(key)[0],
                                  jax.random.key(0)))
    state = place(jax.eval_shape(lambda: api.init_decode_state(1, 4096)))
    toks = _spec(one_chip, (1, tokens), jnp.int32)
    pos = _spec(one_chip, (tokens,) if tokens > 1 else (), jnp.int32)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert round(n_params / 1e9, 2) == 4.91
    compiled = jax.jit(api.decode_step).lower(params, state, toks,
                                              pos).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
    assert ("conditional(" in compiled.as_text()) == (tokens == 1)
