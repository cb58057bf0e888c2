"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode never checks: block shapes
that break the (8, 128) tiling rule and programs that do not fit HBM.
So the served path's Pallas kernels are compiled here at published
widths (LLaDA-8B, and Dream-7B's grouped-query shape), with
``interpret=False`` passed by the test, and so is the served fused
block program at the configuration ``chip_smoke.py`` runs. The topology
is described inside a fixture, never at import: only one process may
hold the TPU library, and every test worker imports this file.
"""
import importlib.util
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(compiled) -> set:
    return ops.compiled_kernels(compiled.as_text())


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,dtype", [
    (8, 129, 641, 32, 32, jnp.float32),     # LLaDA-8B denoise step
    (8, 385, 512, 32, 32, jnp.float32),     # LLaDA-8B block refresh
    (8, 129, 641, 28, 4, jnp.bfloat16),     # Dream-7B GQA step
    (8, 129, 641, 28, 4, jnp.float32),
], ids=["llada8b-step", "llada8b-refresh", "dream7b-gqa-bf16",
        "dream7b-gqa-f32"])
def test_block_attention_compiles_for_v5e(one_chip, B, Sq, Skv, H, Hkv,
                                          dtype):
    D = 128

    def f(q, k, v, qp, kp, km):
        return ops.block_attention(q, k, v, qp, kp, km, interpret=False)

    s = lambda shape, dt: _sds(one_chip, shape, dt)  # noqa: E731
    compiled = jax.jit(f).lower(
        s((B, Sq, H, D), dtype), s((B, Skv, Hkv, D), dtype),
        s((B, Skv, Hkv, D), dtype), s((B, Sq), jnp.int32),
        s((B, Skv), jnp.int32), s((B, Skv), jnp.bool_)).compile()
    assert _kernels(compiled) == {"block_attention"}


@pytest.mark.parametrize("N,dtype", [(264, jnp.float32),
                                     (256, jnp.bfloat16)])
def test_confidence_argmax_compiles_for_v5e(one_chip, N, dtype):
    V = 126_464                             # LLaDA-8B vocabulary
    compiled = jax.jit(
        lambda x: ops.confidence_argmax(x, interpret=False)).lower(
            _sds(one_chip, (N, V), dtype)).compile()
    assert _kernels(compiled) == {"confidence_argmax"}


def test_head_confidence_argmax_compiles_for_v5e(one_chip):
    d, V = 4096, 126_464                    # LLaDA-8B head

    def f(h, head):
        return ops.head_confidence_argmax(h, head, mask_id=V - 1,
                                          interpret=False)

    compiled = jax.jit(f).lower(
        _sds(one_chip, (8, 32, d), jnp.float32),
        _sds(one_chip, (d, V), jnp.float32)).compile()
    assert _kernels(compiled) == {"confidence_argmax"}


@pytest.fixture
def no_interpret(monkeypatch):
    """The served path asks the backend whether to interpret its
    kernels, and the backend here is the CPU: steer that choice for one
    test, and drop every trace cached under it on both sides."""
    from repro.kernels import mode
    jax.clear_caches()
    monkeypatch.setattr(mode, "resolve_interpret", lambda i: False)
    yield
    jax.clear_caches()


def _served_block_program(one_chip):
    """chip_smoke.py's fused block program (streaming, batch 8, first
    block, cache donated as on the chip), compiled for one v5e."""
    from repro.core.decoder import DecodeConfig, DiffusionDecoder
    from repro.core.suffix import suffix_query_region
    from repro.models import init_params
    from repro.models.model import init_cache

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(__file__), "..",
                                   "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = smoke.model_config()
    B, P, G = smoke.MAX_SLOTS, smoke.PROMPT_LEN, smoke.MAX_TOKENS
    place = lambda tree: jax.tree.map(            # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    # the executor fields the fused fn reads; donation as on a TPU
    executor = types.SimpleNamespace(
        params=params, mesh=None, data_axes=("data",), donate_cache=True,
        constrain_cache=lambda cache, b, t: cache)
    dcfg = DecodeConfig(method="streaming", gen_len=G,
                        block_size=cfg.block_size, use_kernels=True)
    dec = DiffusionDecoder(cfg, None, dcfg, executor=executor)
    region = suffix_query_region(gen_start=P, gen_len=G,
                                 block_size=cfg.block_size, block_idx=0,
                                 window=dcfg.effective_window)
    Sq = len(region.positions)
    return dec._fused_fn().lower(
        params, _sds(one_chip, (B, P + G), jnp.int32),
        _sds(one_chip, (B, P + G), jnp.bool_),
        _sds(one_chip, (B,), jnp.bool_),
        place(jax.eval_shape(lambda: init_cache(cfg, B, P + G))),
        _sds(one_chip, (B, Sq), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        None, None, prefix=None, pstart=0).compile()


def test_served_block_program_fits_one_chip(one_chip, no_interpret):
    """The served block program compiles for one v5e: it fits HBM next
    to the params, and calls both kernels."""
    compiled = _served_block_program(one_chip)
    assert _kernels(compiled) == {"block_attention", "confidence_argmax"}
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 15.75 * 2**30


def test_served_block_program_has_no_loop_besides_its_own(one_chip,
                                                          no_interpret):
    """The served block program's top level runs two loops: the
    refresh's scan over the layers and the denoise ``while_loop``, in
    that order. Each row's block is picked by selects, not by per-row
    gathers or scatters, which the TPU compiler runs as serial loops of
    their own (and which would stand after the denoise loop, where
    ``bench/spans.py`` looks for it as the last top-level loop)."""
    text = _served_block_program(one_chip).as_text()
    entry = re.search(r"^ENTRY .*?^}", text, re.S | re.M).group(0)
    loops = re.findall(r"^\s*(%\S+) = .* while\(.*body=%?([\w.\-]+)",
                       entry, re.M)
    assert len(loops) == 2, loops
    # the denoise loop's body holds the steps' own scan over the layers
    body = re.search(r"^%?" + re.escape(loops[-1][1]) + r" .*?^}", text,
                     re.S | re.M).group(0)
    assert " while(" in body
