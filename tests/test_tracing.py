"""Stage scopes on the device and host spans on the engine.

Every op of the paged decode tick is issued under one stage scope
(``salca.score``, ``salca.select``, ``salca.plan``, ``salca.attend``,
``salca.history``, ``kv.write``, ``dense``), which XLA keeps in each
instruction's ``op_name`` metadata and a profiler trace shows per op. No
stage scope may enclose a layer scan: the loop op itself would then count
as that stage, on top of the ops inside it. The engine's host spans add
their seconds to `ServeStats` counters and feed the straggler monitor the
whole scheduler iteration.
"""

from __future__ import annotations

import dataclasses
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.runtime.monitor import StepMonitor
from repro.runtime.serve import Request, ServeStats, ServingEngine
from repro.runtime.tracing import HostSpans, counter_of

CFG = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                          salca_static_channels=True)
MAX_SEQ, BS = 64, 8
DECODE_STAGES = ("salca.score", "salca.select", "salca.plan", "salca.attend",
                 "salca.history", "kv.write", "dense")
ALL_STAGES = DECODE_STAGES + ("prefill.chunk", "prefill.adopt")
SPANS = ("serve.admit", "serve.tick.prep", "serve.tick.dispatch",
         "serve.tick.wait", "serve.tick.post")
COUNTERS = ("admit_s", "tick_prep_s", "tick_dispatch_s", "tick_wait_s",
            "tick_post_s")


@pytest.fixture(scope="module")
def params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


def _engine(params, **kw):
    return ServingEngine(CFG, params, max_seq=MAX_SEQ, slots=2, paged=True,
                         block_size=BS, num_blocks=24, prefix_sharing=True,
                         prefix_cache=True, prefill_chunk=16, **kw)


def _instructions(lowered) -> list[tuple[str, str]]:
    """(opcode, op_name) of every instruction of the lowered program."""
    text = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


def _stages_in(op_name: str) -> list[str]:
    return [s for s in ALL_STAGES
            if re.search(rf"(^|/){re.escape(s)}(/|$)", op_name)]


def _check_scopes(instructions, expected):
    named = [st for _, n in instructions for st in _stages_in(n)]
    assert set(expected) <= set(named), set(expected) - set(named)
    for _, n in instructions:
        assert len(_stages_in(n)) <= 1, f"stages nest: {n}"
    # The layer scan is a top-level loop named just ``jit(<fn>)/while``: a
    # stage scope around it would show in that name.
    assert any(op == "while" and re.fullmatch(r"jit\(.*?\)/while", n)
               for op, n in instructions), "a stage encloses the layer scan"


def test_decode_tick_names_every_stage(params):
    """The default CPU tick: scoring, selection, attention (the gather
    path), the selection history, the K/V append and the dense layers."""
    eng = _engine(params)
    lowered = eng._decode.lower(eng.params, eng._state,
                                jnp.asarray(eng._tokens),
                                jnp.asarray(eng._mask))
    instructions = _instructions(lowered)
    _check_scopes(instructions,
                  [s for s in DECODE_STAGES if s != "salca.plan"])
    for op, n in instructions:   # neither the layer scan nor any inner loop
        assert op != "while" or not _stages_in(n), n


def test_block_plan_path_names_plan_and_attend(params):
    """The paged kernels' path (the chip's default) builds the block plan;
    its ``ref`` implementation lowers here and carries the same scopes."""
    from repro.core.attention import salca_decode_attention_paged
    from repro.models.blocks import salca_params_for
    eng = _engine(params)
    pool = eng._state.period_states[0]
    pool = jax.tree_util.tree_map(lambda x: x[0], pool)   # one layer
    q = jnp.zeros((2, CFG.num_heads, CFG.resolved_head_dim), jnp.float32)
    sp = salca_params_for(CFG, MAX_SEQ)
    lowered = jax.jit(lambda q_, p_: salca_decode_attention_paged(
        q_, p_, sp, impl="ref")).lower(q, pool)
    named = {st for _, n in _instructions(lowered) for st in _stages_in(n)}
    assert {"salca.score", "salca.select", "salca.plan",
            "salca.attend"} <= named


def test_sharded_island_uses_the_same_stage_names(params):
    """The block-sharded island (here over a one-device mesh) names its
    stages as the unsharded tick does, so a multi-chip trace reads the same
    stage metrics."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.sp_decode import sp_salca_decode_paged
    from repro.models.blocks import salca_params_for
    eng = _engine(params)
    pool = jax.tree_util.tree_map(lambda x: x[0], eng._state.period_states[0])
    q = jnp.zeros((2, CFG.num_heads, CFG.resolved_head_dim), jnp.float32)
    sp = salca_params_for(CFG, MAX_SEQ)
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    island = jax.shard_map(
        lambda q_, p_: sp_salca_decode_paged(q_, p_, sp, "x", fused=True,
                                             impl="ref"),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    named = {st for _, n in _instructions(jax.jit(island).lower(q, pool))
             for st in _stages_in(n)}
    assert {"salca.score", "salca.select", "salca.plan",
            "salca.attend"} <= named


def test_prefill_chunk_and_adoption_are_scoped(params):
    eng = _engine(params)
    cursor = eng.api.prefill_begin(24)
    pages = jnp.full((eng.max_blocks,), -1, jnp.int32)
    toks = jnp.zeros((1, 16), jnp.int32)
    for final in (False, True):
        lowered = eng._chunk_step.lower(eng.params, eng._state, toks, cursor,
                                        jnp.int32(0), pages, jnp.int32(0),
                                        final=final)
        _check_scopes(_instructions(lowered), ["prefill.chunk"])
    lowered = eng._adopt.lower(eng.params, eng._state, jnp.int32(0), pages,
                               jnp.int32(24))
    named = {st for _, n in _instructions(lowered) for st in _stages_in(n)}
    assert "prefill.adopt" in named


def test_span_counters_grow_and_decode_is_dispatch_plus_wait(params):
    eng = _engine(params)
    rng = np.random.default_rng(3)
    for rid, n in enumerate((20, 27)):
        eng.submit(Request(rid=rid, max_new_tokens=4,
                           prompt=rng.integers(0, CFG.vocab_size, n)
                           .astype(np.int32)))
    eng.run(max_ticks=2)
    first = dataclasses.asdict(eng.stats)
    eng.run()
    st = eng.stats
    assert st.ticks > 0
    for c in COUNTERS:
        assert getattr(st, c) > first[c] or c == "admit_s", c
        assert getattr(st, c) > 0, c
    assert st.decode_s == pytest.approx(st.tick_dispatch_s + st.tick_wait_s,
                                        rel=1e-9)
    assert "prefill_s" not in st.summary() and "admit_s" in st.summary()


def test_counters_are_named_after_their_spans():
    assert [counter_of(s) for s in SPANS] == list(COUNTERS)
    fields = {f.name for f in dataclasses.fields(ServeStats)}
    assert set(COUNTERS) <= fields and "prefill_s" not in fields


def test_host_span_times_its_body_and_keeps_phases():
    stats = ServeStats()
    spans = HostSpans(stats)
    with spans("serve.tick.wait") as t:
        sum(range(10_000))
    with spans("serve.tick.wait", rid=7):
        pass
    assert t.seconds > 0
    assert stats.tick_wait_s == pytest.approx(spans.phases["serve.tick.wait"])
    assert stats.tick_wait_s >= t.seconds


def test_monitor_sees_the_whole_tick_and_names_its_slowest_phase(caplog):
    mon = StepMonitor(straggler_threshold=2.0)
    assert not hasattr(mon, "history")
    for i in range(3):
        mon.record(i, 1.0, phases={"serve.tick.wait": 0.9,
                                   "serve.admit": 0.1})
    with caplog.at_level(logging.WARNING, logger="repro.monitor"):
        rec = mon.record(3, 5.0, phases={"serve.tick.wait": 0.9,
                                         "serve.admit": 4.1})
    assert rec["flagged"]
    assert "slowest phase serve.admit 4.100s" in caplog.text


def test_engine_feeds_the_monitor_every_phase(params):
    eng = _engine(params, monitor=StepMonitor())
    seen = []
    record = eng.monitor.record

    def spy(step, seconds, loss=None, phases=None):
        seen.append((seconds, dict(phases)))
        return record(step, seconds, loss, phases)

    eng.monitor.record = spy
    rng = np.random.default_rng(4)
    eng.submit(Request(rid=0, max_new_tokens=3,
                       prompt=rng.integers(0, CFG.vocab_size, 18)
                       .astype(np.int32)))
    eng.run()
    assert len(seen) == eng.stats.ticks
    for seconds, phases in seen:
        assert set(SPANS[1:]) <= set(phases) <= set(SPANS)
        assert seconds == pytest.approx(sum(phases.values()))
