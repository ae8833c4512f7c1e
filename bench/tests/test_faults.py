"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a tiny cell on the CPU (no chip check), with
one fault planted in the engine's decode tick, and reads the result line.
The faults a one-chip serving cell can have: a tick that returns its state
unchanged, half of the batch left out, a token altered where it is made.
"""

import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402  (sets JAX_PLATFORMS=cpu before jax loads)
import run  # noqa: E402


def _wrap(engine, change):
    fn = engine._decode

    def tick(params, state, tokens, active):
        return change(fn, params, state, tokens, active)
    engine._decode = tick


def state_unchanged(engine):
    _wrap(engine, lambda fn, p, s, t, a: fn(p, s, t, a)[:3] + (s,))


def half_batch(engine):
    import jax.numpy as jnp
    _wrap(engine, lambda fn, p, s, t, a: fn(
        p, s, t, a & (jnp.arange(a.shape[0]) % 2 == 0)))


def token_altered(engine):
    def change(fn, p, s, t, a):
        nxt, logits, finite, s2 = fn(p, s, t, a)
        return (nxt + 1) % 512, logits, finite, s2
    _wrap(engine, change)


def one_run(root, seed, alter=None) -> dict:
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", tiny.CELL, "--seed", str(seed),
                       "--seconds", "1.5", "--trace", "0"], root=root,
                      require_chip=False, alter_engine=alter)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _gap(res) -> dict:
    (name,) = [k for k in res["checked"] if k.startswith("logit_gap_")]
    assert name == "logit_gap_" + tiny.tiny_config()["check"]["gap_statistic"]
    return res["checked"][name]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(root):
    res = one_run(root, 2**31 + 77)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert list(res)[-1] == "checked"
    gap = _gap(res)
    assert 0 <= gap["value"] <= gap["limit"] == tiny.TINY_LIMIT
    assert set(res["metrics"]) == {"decode_tok_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, token_altered],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_not_correct(root, fault):
    res = one_run(root, 2**31 + 78, fault)
    assert res["correct"] is False
    assert _gap(res)["value"] > tiny.TINY_LIMIT


def test_chip_check_refuses_a_cpu(root, capsys):
    rc = run.main(["--workload", tiny.CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"], root=root)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_directory_without_the_program_gives_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ exits non-zero
    and prints nothing on standard output."""
    import os
    import shutil
    import subprocess
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(tiny.REPO / "BENCHMARK.json", bare)
    shutil.copytree(tiny.BENCH, bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    name = json.loads((bare / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=bare, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout == ""
