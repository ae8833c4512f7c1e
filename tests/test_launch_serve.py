"""The launcher's engine builder serves the config it is given."""

import dataclasses

import jax
import pytest

from repro.configs import get_config
from repro.launch.serve import build_engine
from repro.models import get_model

CFG = get_config("qwen3-0.6b").reduced()


@pytest.fixture(scope="module")
def params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


def test_chunked_prefill_needs_static_channels_in_the_config(params):
    """Chunked prefill without `salca_static_channels` is refused by the
    engine; the builder does not switch the setting on behind the caller."""
    assert not CFG.salca_static_channels
    with pytest.raises(ValueError, match="static_channels"):
        build_engine(CFG, params, slots=2, max_seq=128, paged=True,
                     block_size=16, prefix_cache=True, prefill_chunk=32)


def test_builder_keeps_the_given_config(params):
    cfg = dataclasses.replace(CFG, salca_static_channels=True)
    engine = build_engine(cfg, params, slots=2, max_seq=128, paged=True,
                          block_size=16, prefix_cache=True, prefill_chunk=32)
    assert engine.cfg == cfg
    assert engine.prefill_chunk == 32 and engine.paged
