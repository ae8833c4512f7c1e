"""The main-path kernels compile for a TPU v5e chip at qwen3-0.6b widths.

Interpret mode never checks the TPU compiler's tiling rules (the last two
dims of a block must be (8k, 128k)-aligned or the array's own) nor what the
kernel body lowers to. These tests lower and compile each paged decode-tick
kernel, the chunked-prefill kernel and one whole jitted paged decode tick for
a described — not attached — v5e chip, so a layout the chip would refuse
fails here, at no chip time.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU compiler library, and every test worker
imports this file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

# qwen3-0.6b decode widths: 4 slots, 8 kv heads of 2 query heads, head dim
# 128, 64 heavy channels, 32-token blocks, a 2048-block pool, 16k contexts.
S, KV, G, HD, R, BS, P = 4, 8, 2, 128, 64, 32, 2048
MAX_SEQ = 16384
MB = MAX_SEQ // BS
NSB = 512                  # min(MB, k_cap) at 5% retention of 16k tokens
BH, W = S * KV, R // 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip are written to the persistent cache but
    can never be read back without the chip — keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_cache):
    """Lower + compile ``fn`` on shape/dtype pairs placed on the chip."""
    def run(fn, *shapes, **jit_kw):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled
    return run


def _pool(dt=jnp.int8, hdc=HD, sb=BS):
    return [((P, KV, BS, hdc), dt), ((P, KV, sb), jnp.float32)] * 2


@pytest.mark.parametrize("kv_dtype", ["int8", "int4", "fp16"])
@pytest.mark.parametrize("partials", [False, True])
def test_paged_flash_decode_compiles(compile_tpu, kv_dtype, partials):
    from repro.kernels.flash_decode.kernel import (
        sparse_flash_decode_paged_pallas,
        sparse_flash_decode_paged_partials_pallas)
    fn = (sparse_flash_decode_paged_partials_pallas if partials
          else sparse_flash_decode_paged_pallas)
    pool = {"int8": _pool(), "int4": _pool(hdc=HD // 2, sb=1),
            "fp16": _pool(dt=jnp.float16, sb=1)}[kv_dtype]
    compile_tpu(functools.partial(fn, num_kv=KV, kv_dtype=kv_dtype,
                                  interpret=False),
                ((BH, G, HD), jnp.float32), *pool, ((BH, NSB), jnp.int32),
                ((BH,), jnp.int32), ((BH, NSB, BS), jnp.bool_))


def test_flat_flash_decode_compiles(compile_tpu):
    from repro.kernels.flash_decode.kernel import sparse_flash_decode_pallas
    c = 1024
    compile_tpu(functools.partial(sparse_flash_decode_pallas, interpret=False),
                ((BH, G, HD), jnp.float32), ((BH, c, HD), jnp.int8),
                ((BH, c), jnp.float32), ((BH, c, HD), jnp.int8),
                ((BH, c), jnp.float32), ((BH, c), jnp.bool_))


_SCORE_OPS = [((S, KV, 1, R), jnp.int8), ((S, KV, 1), jnp.float32),
              ((S, KV, 1), jnp.int32), ((P, KV, BS, W), jnp.uint32),
              ((P, KV, BS), jnp.float32), ((P, KV, BS), jnp.float32),
              ((S, MB), jnp.int32)]


def test_paged_score_estimate_compiles(compile_tpu):
    from repro.kernels.score_est.kernel import paged_score_estimate_pallas
    compile_tpu(functools.partial(paged_score_estimate_pallas, interpret=False),
                *_SCORE_OPS)


def test_paged_score_bounds_compiles(compile_tpu):
    from repro.kernels.score_est.kernel import paged_score_bounds_pallas
    compile_tpu(functools.partial(paged_score_bounds_pallas, interpret=False),
                *_SCORE_OPS, ((S, MB, BS), jnp.int8))


def test_paged_fused_select_compiles(compile_tpu):
    from repro.kernels.selection_fused.kernel import paged_fused_select_pallas
    compile_tpu(functools.partial(paged_fused_select_pallas, window=7,
                                  interpret=False),
                ((S, KV, MB, BS), jnp.float32), ((S, KV), jnp.float32),
                ((S, KV), jnp.float32), ((S, KV, MB, 3), jnp.uint8),
                ((S, KV, MB, 3), jnp.uint8), ((S, MB, BS), jnp.int8),
                ((S, MB, BS), jnp.int8))


def test_flat_fused_select_compiles(compile_tpu):
    from repro.kernels.selection_fused.kernel import (
        fused_bin_pool_threshold_pallas)
    compile_tpu(functools.partial(fused_bin_pool_threshold_pallas, window=7,
                                  interpret=False),
                ((BH, MAX_SEQ), jnp.float32), ((BH,), jnp.float32),
                ((BH,), jnp.float32), ((BH,), jnp.int32), ((BH,), jnp.int32))


def test_chunked_flash_prefill_compiles(compile_tpu):
    from repro.kernels.flash_prefill.kernel import flash_attention_pallas
    compile_tpu(functools.partial(flash_attention_pallas, q_offset=512,
                                  interpret=False),
                ((2 * KV * G, 512, HD), jnp.bfloat16),
                ((2 * KV * G, 2048, HD), jnp.bfloat16),
                ((2 * KV * G, 2048, HD), jnp.bfloat16))


def test_paged_selection_has_no_scatter_or_sort(one_chip, no_cache):
    """The paged selection at the decode-16k shapes compiles to compares,
    reductions and gathers: the TPU applies scatter updates nearly one at a
    time, and a sort feeding one costs as much again."""
    import re

    from repro.core.selection import SalcaParams, select_sparse_pattern_blocked
    params = SalcaParams.for_seq(MAX_SEQ, 0.05)
    assert (params.k, params.k_cap) == (819, 1024)
    fn = functools.partial(select_sparse_pattern_blocked, params=params,
                           block_size=BS)
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((S, KV, MAX_SEQ), jnp.float32, sharding=one_chip),
        valid_mask=jax.ShapeDtypeStruct((S, 1, MAX_SEQ), jnp.bool_,
                                        sharding=one_chip)).compile().as_text()
    assert not re.findall(r"\b(scatter|sort)\(", text)


@pytest.fixture(scope="module")
def tick_text(one_chip, no_cache):
    """The compiled text of one whole jitted paged decode tick of qwen3-0.6b
    (2 of its 28 layers, full width) with the state donated.

    The program asks the platform which kernel path to take, and here the
    platform is the CPU, so the fixture steers that choice to the chip's."""
    import dataclasses

    import repro.kernels.common as common
    from repro.configs import get_config
    from repro.kernels.flash_decode import kernel as fd
    from repro.kernels.score_est import kernel as se
    from repro.models import get_model
    with pytest.MonkeyPatch.context() as mp:
        for mod in (common, fd, se):
            mp.setattr(mod, "interpret_default", lambda: False)

        cfg = dataclasses.replace(get_config("qwen3-0.6b"), num_layers=2)
        api = get_model(cfg)
        place = lambda tree: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)
        params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
        state = place(jax.eval_shape(
            lambda: api.init_paged_state(S, MAX_SEQ, BS, P)))
        tok = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip)
        act = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)

        def tick(p, s, t, a):
            logits, s2 = api.decode_step(p, s, t, None, active=a)
            return jnp.argmax(logits, axis=-1), s2

        compiled = jax.jit(tick, donate_argnums=(1,)).lower(
            params, state, tok, act).compile()
        return compiled.as_text()


def test_paged_decode_tick_compiles(tick_text):
    """The whole tick compiles for the chip: the kernels are in the
    program."""
    assert tick_text.count("tpu_custom_call") >= 2     # scoring + exact attention


def test_paged_decode_tick_names_its_kernels_and_stages(tick_text):
    """The chip's tick keeps the kernels' instruction names, which a trace
    shows as op names, and every stage scope of the tick in its ops'
    metadata; no stage scope encloses the layer scan."""
    import re
    kernels = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', tick_text)
    assert sorted(set(kernels)) == ["paged_score_estimate_pallas",
                                    "sparse_flash_decode_paged_pallas"]
    op_names = re.findall(r'op_name="([^"]*)"', tick_text)
    for stage in ("salca.score", "salca.select", "salca.plan",
                  "salca.attend", "salca.history", "kv.write", "dense"):
        assert any(f"/{stage}/" in n for n in op_names), stage
    loops = re.findall(r"%while\.\d+ = .* while\(.*"
                       r'op_name="([^"]*)"', tick_text)
    assert loops and all(re.fullmatch(r"jit\(.*?\)/while", n) for n in loops)
