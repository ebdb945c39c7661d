"""Compile the served path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed beside JAX, compiles for
a chip that is described, not attached, and refuses what the chip would
refuse (block shapes off the (8, 128) tiling, too much VMEM). Widths are
qwen3-1.7b's (16 query / 8 KV heads, head_dim 128, bf16, page 16); pool
and log sizes are those of ``chip_smoke.py``. The topology is described
inside a fixture, so importing this file loads no TPU library.
"""
import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import tiering
from repro.kernels.log_compact.kernel import log_compact_pallas
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.models.api import ModelSpec

CFG = get_config("qwen3-1.7b")
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
BF16, I32 = jnp.bfloat16, jnp.int32


def _smoke_sizes():
    path = Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # constants only: main() is not called
    args = mod.serve_argv(0)
    from repro.launch import serve

    return serve.kv_config(serve.build_parser().parse_args(args))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a persistent-cache entry written for a described chip cannot be read
    # back without one: keep the cache off while these tests compile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler or library here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def kv():
    return _smoke_sizes()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _place(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), tree
    )


@pytest.mark.parametrize("pool", ["hbm", "host"])
def test_paged_attention_compiles(one_chip, kv, pool):
    P = kv.n_hbm_pages if pool == "hbm" else kv.n_host_pages
    B, N, page = kv.batch, kv.max_pages_per_req, kv.page_size
    args = (
        _sds((B, H, HD), BF16, one_chip),
        _sds((P, page, KV, HD), BF16, one_chip),
        _sds((P, page, KV, HD), BF16, one_chip),
        _sds((B, N), I32, one_chip),
        _sds((B,), I32, one_chip),
    )
    compiled = paged_decode_attention_pallas.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pool", ["hbm", "host"])
def test_log_compact_compiles(one_chip, kv, pool):
    P = kv.n_hbm_pages if pool == "hbm" else kv.n_host_pages
    L, S, page = CFG.n_layers, kv.log_slots, kv.page_size
    args = (
        _sds((L, P, page, KV, HD), BF16, one_chip),
        _sds((L, P, page, KV, HD), BF16, one_chip),
        _sds((L, S, KV, HD), BF16, one_chip),
        _sds((L, S, KV, HD), BF16, one_chip),
        _sds((S, 2), I32, one_chip),
        _sds((S, 3), I32, one_chip),
    )
    compiled = log_compact_pallas.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_served_programs_carry_kernels(one_chip, kv, monkeypatch):
    """One full-width layer of the engine's decode step, and its
    compaction, on the compiled-kernel path the TPU backend selects."""
    monkeypatch.setattr(tiering, "kernel_mode", lambda: "compiled")
    spec = ModelSpec(dataclasses.replace(CFG, n_layers=1))
    params = _place(spec.abstract_params(), one_chip)
    state = _place(
        jax.eval_shape(lambda: tiering.init_state(kv, spec.cfg, dtype=BF16)),
        one_chip,
    )
    B = kv.batch
    step = jax.jit(tiering.build_paged_decode_step(spec, kv))
    compiled = step.lower(
        params, state, _sds((B, 1), I32, one_chip), _sds((B,), I32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()

    flush = _sds((kv.log_slots, 3), I32, one_chip)
    compact = jax.jit(functools.partial(tiering.compact_log, kv), donate_argnums=0)
    compiled = compact.lower(state, flush, flush).compile()
    assert "tpu_custom_call" in compiled.as_text()
