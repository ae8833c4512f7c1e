"""The traffic generator: seeded, and the same work for every seed."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.generator import generate  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]


def _mixes():
    return [json.loads(p.read_text()) for p in sorted((BENCH / "traffic").glob("*.json"))]


def test_same_seed_same_requests_and_large_seeds_work():
    for mix in _mixes():
        a = generate(mix, 2**31 + 12345, 151936)
        b = generate(mix, 2**31 + 12345, 151936)
        assert [(x.max_new, x.prompt.tolist()) for x in a] == \
               [(x.max_new, x.prompt.tolist()) for x in b]


def test_every_seed_sends_the_same_sizes_in_its_own_order():
    for mix in _mixes():
        a = generate(mix, 1, 151936)
        b = generate(mix, 2, 151936)
        size = lambda t: sorted((len(x.prompt), x.max_new) for x in t)
        assert size(a) == size(b)
        assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in b]


def test_closed_mix_decodes_to_max_seq():
    for mix in _mixes():
        t = generate(mix, 7, 151936)
        assert sorted(len(x.prompt) for x in t) == sorted(mix["contexts"])
        for x in t:
            room = mix["engine"]["max_seq"] - len(x.prompt)
            assert x.max_new == min(mix.get("new_tokens", room), room)
        # the pool holds every client's whole request: no overflow stop
        need = sum(-(-(len(x.prompt) + x.max_new) // 32) for x in t)
        assert need <= mix["engine"]["num_blocks"]


def test_an_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        generate({"loop": "open", "engine": {"max_seq": 64}}, 1, 10)
